"""The control of each cell comes out not correct: the program with its
lower-precision path switched on (the configuration's `control` policy), read
against the plain reference as `calibrate.py` reads it on the chip.  At toy
size on the CPU, judged by the toy limits (`chipbench_toy.TOY_LIMITS`), not
the cell's own, which are set from readings at the cell's size."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import calibrate  # noqa: E402
import chipbench_toy  # noqa: E402

SEED = 2**33 + 11


@pytest.mark.parametrize("name", chipbench_toy.workloads())
def test_the_control_is_not_correct(name):
    cell = chipbench_toy.toy(name)
    program, control = calibrate.readings(cell, SEED, ["program", "control"])
    assert program["correct"] is True, program
    assert control["correct"] is False, control
    # the control trains: it is not the state left unchanged, which reads 1
    assert control["update_gap"] < 0.5

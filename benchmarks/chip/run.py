"""Chip benchmark: run one cell of BENCHMARK.json on the machine it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero, printing no result, without the TPUs the cell asks for.
See chipbench/cli.py for what a run does.
"""
import time

T0 = time.perf_counter()  # set-up is counted from the start of the process

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]

from chipbench.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0))

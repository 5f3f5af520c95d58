"""`correct` comes out false when the timed path is broken underneath: the
harness runs each toy cell on the CPU with a fault planted in the program, the
check done as in any run.  The faults a single-chip training cell can have: a
step that returns its state unchanged, half of the batch left out with the
mean taken over the rest, and a window call that does other work than the
call set-up checked."""
import dataclasses
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import chipbench_toy  # noqa: E402
from chipbench import correct, program  # noqa: E402


@dataclasses.dataclass(frozen=True)
class HalfBatchModel:
    """The program's model with the fault planted: its loss sees half of
    every batch.  A distinct type, so it compiles programs of its own."""

    inner: object

    name = property(lambda self: self.inner.name)
    metric_name = property(lambda self: self.inner.metric_name)
    metric_mode = property(lambda self: self.inner.metric_mode)

    def init(self, key):
        return self.inner.init(key)

    def loss(self, params, batch):
        return self.inner.loss(params, correct.half_batch(batch))

    def eval_metric(self, params, eval_data):
        return self.inner.eval_metric(params, eval_data)


@pytest.mark.parametrize("name", chipbench_toy.workloads())
def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch, name):
    from repro.core import engine

    real = engine.scan_chunk_fn

    def frozen(body):
        fn = real(body)

        def chunk(carry, xs, consts):
            return carry, fn(carry, xs, consts)[1]

        return chunk

    monkeypatch.setattr(engine, "scan_chunk_fn", frozen)
    result = chipbench_toy.run(monkeypatch, name)
    assert result["checks"]["update_gap"]["value"] == pytest.approx(1.0)
    assert result["correct"] is False


@pytest.mark.parametrize("name", chipbench_toy.workloads())
def test_a_window_call_that_differs_from_set_up_is_not_correct(monkeypatch, name):
    from chipbench import traffic

    # state carried from call to call: the data source is rewound only once,
    # so every call after set-up's trains on later draws
    real = traffic.SeededSource.reset

    def reset_once(self, seed):
        if not hasattr(self, "draw_counts"):
            real(self, seed)

    monkeypatch.setattr(traffic.SeededSource, "reset", reset_once)
    result = chipbench_toy.run(monkeypatch, name)
    assert result["checks"]["window_mismatch"]["value"] >= 1
    assert result["correct"] is False


@pytest.mark.parametrize("name", chipbench_toy.workloads())
def test_half_of_the_batch_left_out_is_not_correct(monkeypatch, name):
    inner = program.inner_model
    monkeypatch.setattr(program, "inner_model", lambda config: HalfBatchModel(inner(config)))
    result = chipbench_toy.run(monkeypatch, name)
    assert result["correct"] is False, result["checks"]

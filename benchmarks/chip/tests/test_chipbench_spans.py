"""A driver call's own spans on a small trace recorded on a TPU v5e
(`testdata/toy_qsgd_spans.xplane.pb.gz`, recorded by `record_testdata.py`
from a program whose scanned drivers open `call`, `schedule`, `model_init`,
`draw`, `device_put` and `loss_fetch` spans): the readers of a call's
set-up and host work, the partition of the device's busy time, the nesting
of the new spans, and the idle gaps they label."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import catalog, cli, device  # noqa: E402
from chipbench import trace as tr  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata", "toy_qsgd_spans.xplane.pb.gz")
# the spans a driver call opens besides those the harness reads today
CALL_SPANS = ("call", "schedule", "model_init", "draw", "device_put", "loss_fetch")
SPANS = cli.HOST_SPANS + CALL_SPANS


@pytest.fixture(scope="module")
def recorded():
    t = tr.load(TESTDATA, SPANS)
    (lo, hi), = t.spans("bench_window")
    return t, lo, hi


def _context(t, lo, hi):
    import record_testdata

    config = dict(catalog.load_json(os.path.join(BENCH, "configs", "qwen3-0.6b.json")),
                  **record_testdata.TOY_CONFIG)
    rounds = 1 + record_testdata.TOY_MIX["federation"]["eval_every"]
    return cli.Context(t, [0], (lo, hi), (hi - lo) * 1e-12, rounds, config,
                       record_testdata.TOY_MIX, device.peaks("TPU v5 lite"))


def _inside(span, parents) -> bool:
    s, e = span
    return any(ps <= s and e <= pe for ps, pe in parents)


@pytest.mark.parametrize("name", ["call_setup_ms", "driver_host_ms"])
def test_each_new_reader_reads_the_recorded_trace(recorded, name):
    t, lo, hi = recorded
    value = catalog.metric_reader(name)(_context(t, lo, hi))
    assert value is not None and np.isfinite(value) and value > 0


def test_call_setup_eval_and_round_program_partition_the_busy_time(recorded):
    t, lo, hi = recorded
    ctx = _context(t, lo, hi)
    ops = tr.clip(t.devices[0], lo, hi)
    chunk_ms = sum(int(e - s) for s, e, m in zip(ops.start, ops.end, ops.module)
                   if m.startswith("jit_chunk")) * 1e-9 / ctx.rounds
    parts = sum(catalog.metric_reader(n)(ctx) for n in ("call_setup_ms", "eval_ms")) + chunk_ms
    busy_ms = tr.busy_ps(t.devices[0], lo, hi) * 1e-9 / ctx.rounds
    assert parts == pytest.approx(busy_ms, rel=1e-3)


@pytest.mark.parametrize("child,parent", [
    ("call", "bench_call"), ("precompute", "call"), ("schedule", "precompute"),
    ("model_init", "precompute"), ("draw", "stage"), ("device_put", "stage"),
    ("loss_fetch", "call")])
def test_each_new_span_lies_inside_its_parent(recorded, child, parent):
    t, _, _ = recorded
    spans = t.spans(child)
    assert spans and all(_inside(s, t.spans(parent)) for s in spans)


def test_model_inits_lie_in_the_call_and_a_loss_fetch_follows_each_eval(recorded):
    t, lo, hi = recorded
    calls = [c for c in t.spans("bench_call") if lo <= c[0] < hi]
    inits = t.spans("model_init")
    assert len(calls) == 1 and inits
    assert all(_inside(s, calls) for s in inits)
    # a loss fetch follows every eval, outside it
    evals, fetches = t.spans("eval"), t.spans("loss_fetch")
    assert len(fetches) == len(evals)
    assert all(ev[1] <= f[0] for ev, f in zip(evals, fetches))


def test_idle_gaps_inside_a_call_are_labelled_by_its_spans(recorded):
    t, lo, hi = recorded
    ops = tr.clip(t.devices[0], lo, hi)
    gaps = tr.idle_gaps(ops, lo, hi, t.host, n=10 ** 6)
    assert gaps and {label for label, _ in gaps} <= set(SPANS) | {"host:none"}
    # with the call's spans kept, no gap inside a call is put down to the harness
    (c0, c1), = t.spans("call")
    busy = tr.union(zip(ops.start.tolist(), ops.end.tolist()))
    inner = [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if c0 <= a[1] and b[0] <= c1]
    for s, e in inner:
        mid = (s + e) // 2
        open_spans = [(hs, n) for n, hs, he in t.host if hs <= mid < he]
        assert max(open_spans)[1] != "bench_call"

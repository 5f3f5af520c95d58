"""The reduction from a profiler trace to device time per scope and kernel,
idle intervals and collective exposure: on hand-made intervals, and on a
small trace recorded on a TPU v5e (`testdata/`, see `record_testdata.py`)."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import catalog, cli, device  # noqa: E402
from chipbench import trace as tr  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata", "toy_qsgd.xplane.pb.gz")


def _ops(rows):
    """Ops from (start, end, scope, category) rows, sorted by start."""
    rows = sorted(rows)
    n = len(rows)
    return tr.Ops(np.array([r[0] for r in rows], np.int64), np.array([r[1] for r in rows], np.int64),
                  [f"op.{i}" for i in range(n)], [""] * n, [r[2] for r in rows],
                  [r[3] for r in rows], ["jit_chunk"] * n)


# --------------------------------------------------------------------------
# hand-made intervals
# --------------------------------------------------------------------------


def test_union_and_busy_time():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    ops = _ops([(0, 10, "", "loop fusion"), (5, 20, "", "loop fusion"), (30, 40, "", "copy")])
    assert tr.busy_ps(ops, 0, 100) == 30
    assert tr.busy_ps(ops, 8, 35) == 12 + 5      # clipped to the window


def test_scope_time_follows_the_name_scope_path():
    ops = _ops([(0, 10, "jit(chunk)/while/body/local_train/dot_general", "convolution fusion"),
                (10, 14, "jit(chunk)/while/body/uplink/qsgd_encode/add", "loop fusion"),
                (14, 15, "jit(chunk)/while/body/intra_agg/add", "loop fusion"),
                (15, 16, "jit(chunk)/while/body/local_train_x/add", "loop fusion")])
    assert tr.scope_ps(ops, "local_train") == 10      # "local_train_x" is another scope
    assert tr.scope_ps(ops, "uplink") == 4
    assert tr.scope_ps(ops, "qsgd_encode") == 4
    assert tr.scope_ps(ops, "intra_agg") == 1


def test_collective_exposure_counts_only_time_without_compute():
    ops = _ops([(0, 10, "", "all-gather"),          # 0-4 exposed, 4-10 under compute
                (4, 12, "", "convolution fusion"),
                (20, 30, "", "all-reduce"),         # wholly exposed
                (25, 28, "", "all-gather")])        # inside the all-reduce: counted once
    assert tr.collective_exposed_ps(ops) == 4 + 10


def test_idle_gaps_are_the_longest_first_and_labelled_by_the_open_host_span():
    ops = _ops([(10, 20, "", "loop fusion"), (50, 60, "", "loop fusion")])
    host = [("bench_call", 0, 100), ("stage", 25, 45), ("eval", 70, 95)]
    gaps = tr.idle_gaps(ops, 0, 100, host)
    assert [g[0] for g in gaps] == ["eval", "stage", "bench_call"]
    assert [g[1] for g in gaps] == pytest.approx([40e-12, 30e-12, 10e-12])


def test_top_ops_merge_clones_and_sort_by_time():
    ops = _ops([(0, 5, "", "c"), (5, 7, "", "c"), (7, 20, "", "c")])
    ops.name[:] = ["fusion.1", "fusion.2", "copy.3"]
    top = tr.top_ops(ops)
    assert [k for k, _ in top] == ["copy", "fusion"]
    assert [v for _, v in top] == pytest.approx([13e-12, 7e-12])


def test_clip_keeps_the_ops_that_start_in_the_window():
    ops = _ops([(0, 5, "", "c"), (5, 7, "", "c"), (9, 20, "", "c")])
    part = tr.clip(ops, 4, 10)
    assert part.start.tolist() == [5, 9]


# --------------------------------------------------------------------------
# a trace recorded on the chip
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    t = tr.load(TESTDATA, cli.HOST_SPANS)
    (lo, hi), = t.spans("bench_window")
    return t, lo, hi


def _context(t, lo, hi):
    import record_testdata

    config = dict(catalog.load_json(os.path.join(BENCH, "configs", "qwen3-0.6b.json")),
                  **record_testdata.TOY_CONFIG)
    rounds = 1 + record_testdata.TOY_MIX["federation"]["eval_every"]
    return cli.Context(t, [0], (lo, hi), (hi - lo) * 1e-12, rounds, config,
                       record_testdata.TOY_MIX, device.peaks("TPU v5 lite"))


def test_recorded_trace_has_one_device_and_the_harness_spans(recorded):
    t, lo, hi = recorded
    assert sorted(t.devices) == [0]
    names = {n for n, _, _ in t.host}
    assert {"bench_window", "bench_call", "stage", "scan_chunk", "eval"} <= names
    ops = t.devices[0]
    assert np.all(np.diff(ops.start) >= 0) and np.all(ops.end >= ops.start)
    assert "jit_chunk" in set(ops.module)
    assert 0 < tr.busy_ps(ops, lo, hi) < hi - lo


def test_recorded_scopes_nest_and_fit_in_the_busy_time(recorded):
    t, lo, hi = recorded
    ops = tr.clip(t.devices[0], lo, hi)
    local, up, agg = (tr.scope_ps(ops, s) for s in ("local_train", "uplink", "intra_agg"))
    enc, dec = tr.scope_ps(ops, "qsgd_encode"), tr.scope_ps(ops, "qsgd_decode")
    assert local > 0 and up > 0 and enc > 0 and dec > 0
    assert enc + dec <= up               # the QSGD scopes lie inside the uplink's
    assert local + up + agg <= sum(int(e - s) for s, e in zip(ops.start, ops.end))


def test_recorded_kernels_are_found_by_name(recorded):
    t, lo, hi = recorded
    ops = tr.clip(t.devices[0], lo, hi)
    flash = tr.kernel_events(ops, "flash_attention")
    assert flash and all(d > 0 for d, _ in flash)
    assert tr.kernel_events(ops, "qsgd_quantize_pack_blocks")
    assert tr.kernel_events(ops, "qsgd_unpack_dequantize_blocks")


def test_recorded_trace_reduces_to_the_same_numbers(recorded):
    # pinned from this file, so that any change to the reduction shows
    t, lo, hi = recorded
    ops = tr.clip(t.devices[0], lo, hi)
    assert len(ops.start) == 11324
    assert tr.busy_ps(t.devices[0], lo, hi) == 21385798840
    assert [tr.scope_ps(ops, s) for s in ("local_train", "uplink", "intra_agg", "qsgd_encode",
                                          "qsgd_decode")] == \
        [1831186570, 18162927906, 443662342, 17548280486, 553095702]
    assert [len(tr.kernel_events(ops, k)) for k in (
        "flash_attention", "qsgd_quantize_pack_blocks", "qsgd_unpack_dequantize_blocks")] == \
        [56, 156, 156]


@pytest.mark.parametrize("name", ["stage_ms", "eval_ms", "local_train_ms",
                                  "flash_roofline", "idle_share", "train_mfu"])
def test_each_metric_reader_reads_the_recorded_trace(recorded, name):
    t, lo, hi = recorded
    value = catalog.metric_reader(name)(_context(t, lo, hi))
    assert value is not None and np.isfinite(value) and value > 0
    if name.endswith("_roofline") or name in ("idle_share", "train_mfu"):
        assert value < 100


def test_breakdown_fits_the_result_line(recorded):
    t, lo, hi = recorded
    ops = tr.clip(t.devices[0], lo, hi)
    top, gaps = tr.top_ops(ops), tr.idle_gaps(ops, lo, hi, t.host)
    assert 0 < len(top) <= 10 and 0 < len(gaps) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    assert {label for label, _ in gaps} <= set(cli.HOST_SPANS) | {"host:none"}

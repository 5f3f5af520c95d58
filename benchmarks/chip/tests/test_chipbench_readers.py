"""A metric reader that a later change adds as a file: it declares the host
spans it reads (`SPANS`) and reads the window's counter deltas
(`Context.counts`), with no edit to the harness.  On the small trace
recorded on a TPU v5e (`testdata/toy_qsgd_spans.xplane.pb.gz`), and on a
toy harness run on the CPU for the counters."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import chipbench_toy  # noqa: E402
from chipbench import catalog, cli, device  # noqa: E402
from chipbench import trace as tr  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata", "toy_qsgd_spans.xplane.pb.gz")
CELL = "qwen3-0.6b.chs-dense-s2048"
DISPATCH = "PjitFunction(chunk)"           # the host's dispatch of the round program
READERS = {
    "chunk_dispatch_ms": f'''
SPANS = ({DISPATCH!r},)


def read(ctx):
    lo, hi = ctx.window_ps
    spans = [(s, e) for s, e in ctx.trace.spans(SPANS[0]) if lo <= s < hi]
    return sum(e - s for s, e in spans) * 1e-9 / ctx.rounds if spans else None
''',
    "staged_mb": '''
def read(ctx):
    staged = ctx.counts.get("staged_bytes")
    return staged / 1e6 / ctx.rounds if staged else None
''',
}


@pytest.fixture
def added(tmp_path):
    """A checkout to which a later change added two readers and their entries."""
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = catalog.load_json(os.path.join(catalog.ROOT, "BENCHMARK.json"))
    for name, body in READERS.items():
        (bench_dir / "metrics" / f"{name}.py").write_text(body)
        bench["per_layer"].append({"name": name, "unit": "ms", "better": "lower",
                                   "source": "program_span", "layer": "driver host loop",
                                   "moves": "round_ms", "workloads": [CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = catalog.find_cell(CELL, root=str(root), bench_dir=str(bench_dir))
    return cell, str(bench_dir)


def _context(t, counts=None):
    """The recorded run's context, as `test_chipbench_spans` builds it."""
    import record_testdata

    (lo, hi), = t.spans("bench_window")
    config = dict(catalog.load_json(os.path.join(BENCH, "configs", "qwen3-0.6b.json")),
                  **record_testdata.TOY_CONFIG)
    rounds = 1 + record_testdata.TOY_MIX["federation"]["eval_every"]
    extra = {} if counts is None else {"counts": counts}
    return cli.Context(t, [0], (lo, hi), (hi - lo) * 1e-12, rounds, config,
                       record_testdata.TOY_MIX, device.peaks("TPU v5 lite"), **extra)


def test_the_driver_calls_spans_are_kept_in_every_traced_run():
    assert {"call", "schedule", "model_init", "draw", "device_put",
            "loss_fetch"} <= set(cli.HOST_SPANS)


def test_a_readers_declared_spans_are_loaded_with_the_trace(added):
    cell, bench_dir = added
    spans = cli.host_spans(cell, bench_dir)
    assert spans[: len(cli.HOST_SPANS)] == cli.HOST_SPANS and spans.count(DISPATCH) == 1
    assert cli.host_spans(catalog.find_cell(CELL)) == cli.HOST_SPANS
    assert tr.load(TESTDATA, cli.HOST_SPANS).spans(DISPATCH) == []
    t = tr.load(TESTDATA, spans)
    assert t.spans(DISPATCH)
    values = cli.read_layers(cell, _context(t, {"staged_bytes": 3_000_000}), bench_dir)
    assert values["chunk_dispatch_ms"]["value"] > 0
    assert values["staged_mb"] == {"value": 1.0, "unit": "ms"}
    # the readers that were there read what they read without the new ones
    before = cli.read_layers(catalog.find_cell(CELL), _context(t))
    assert {k: v for k, v in values.items() if k in before} == before


def test_a_reader_with_no_counts_to_read_is_left_out(added):
    cell, bench_dir = added
    t = tr.load(TESTDATA, cli.host_spans(cell, bench_dir))
    ctx = _context(t)                       # a Context built without counts
    assert ctx.counts == {}
    assert "staged_mb" not in cli.read_layers(cell, ctx, bench_dir)


def test_the_window_counts_what_its_calls_added(monkeypatch, capsys):
    result = chipbench_toy.run(monkeypatch, CELL)
    line, = [ln for ln in capsys.readouterr().err.splitlines()
             if ln.startswith("window counters: ")]
    counts = json.loads(line.split(": ", 1)[1])
    # every round of every window call is staged and trained once
    assert counts["trained_rounds"] == result["attempted"]
    assert counts["staged_bytes"] > 0

"""The harness end to end at toy size on the CPU: every cell's mix through
the harness's own path, discovery of a new mix by name, the refusal of a
machine without a TPU, and a compile inside the window failing the run."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import chipbench_toy  # noqa: E402
from chipbench import catalog, traffic  # noqa: E402

ROOT = catalog.ROOT


@pytest.mark.parametrize("name", chipbench_toy.workloads())
def test_each_cell_runs_through_the_harness_at_toy_size(monkeypatch, name):
    cell = chipbench_toy.toy(name)
    result = chipbench_toy.run(monkeypatch, name)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0
    assert result["attempted"] >= 1 + 2          # at least one call of 1 + eval_every rounds
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        assert result["metrics"][m["name"]]["unit"] == m["unit"]
    assert result["metrics"]["round_ms"]["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    assert list(result)[-1] == "checks"          # the compared numbers come last
    assert result["checks"]["window_compiles"] == {"value": 0.0, "limit": 0.0}
    assert result["checks"]["window_mismatch"] == {"value": 0.0, "limit": 0.0}
    json.dumps(result)


def test_a_compile_inside_the_window_fails_the_run(monkeypatch):
    import jax

    import repro.core

    real, calls = repro.core.run_fed_chs, []

    def compiling(task, config):
        calls.append(1)
        if len(calls) > 1:    # every call after set-up's compiles a fresh program
            jax.jit(lambda x: x * len(calls))(1.0).block_until_ready()
        return real(task, config)

    monkeypatch.setattr(repro.core, "run_fed_chs", compiling)
    result = chipbench_toy.run(monkeypatch, "qwen3-0.6b.chs-dense-s2048")
    assert result["checks"]["window_compiles"]["value"] >= 1
    assert result["correct"] is False


def test_the_command_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "qwen3-0.6b.chs-dense-s2048", "--seed", str(2**33 + 1), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""                # no result line
    assert "no TPU" in proc.stderr
    assert "platform=cpu" in proc.stderr            # every run prints the device it found


def test_a_new_mix_is_found_by_name_without_editing_a_file(tmp_path):
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = catalog.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    # a later change adds a mix file, a limits file and a workload entry
    mix = catalog.load_json(str(bench_dir / "mixes" / "chs-dense-s2048.json"))
    mix["data"].update(seq=64, batch=2)
    mix["population"].update(clusters=2)
    (bench_dir / "mixes" / "chs-dense-s64.json").write_text(json.dumps(mix))
    (bench_dir / "limits" / "qwen3-0.6b.chs-dense-s64.json").write_text(
        (bench_dir / "limits" / "qwen3-0.6b.chs-dense-s2048.json").read_text())
    bench["workloads"].append({"name": "qwen3-0.6b.chs-dense-s64", "config": "qwen3-0.6b",
                               "traffic": "chs-dense-s64", "chips": 1, "why": "a new mix"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = catalog.find_cell("qwen3-0.6b.chs-dense-s64", root=str(root), bench_dir=str(bench_dir))
    assert cell.mix["data"]["seq"] == 64 and cell.chips == 1
    assert [m["name"] for m in cell.per_layer] == []    # no metric lists the new cell yet
    cell.config.update(chipbench_toy.LM_TOY)
    fed = traffic.build(cell.mix, cell.config, seed=2**33 + 3)
    assert fed.batch_shape == {"tokens": (2, 64), "labels": (2, 64)}
    assert fed.num_clusters == 2
    assert all(p.read_bytes() == b for p, b in before.items())

"""Toy-size cells for the CPU tests: each cell of `BENCHMARK.json` with the
configuration cut to toy widths and the mix to a toy sequence, run through
the harness's own path (`chipbench.cli.run`) with the chip check steered to
the CPU.  Numbers from these runs are never device metrics."""
import argparse
import copy
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench import catalog, cli, device  # noqa: E402

LM_TOY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=512)
# A cell's limits are set from chip readings at its own size; at toy size on
# the CPU the readings differ (the bf16 error is relatively larger at width
# 64), so the toy runs have limits of their own, between the program's toy
# readings and those of the control and the faults.  Over twelve seeds the
# program read update_gap 0.0050-0.0194 and the bf16-master control
# 0.060-0.099; the half-batch fault reads 0.42-0.59.
TOY_LIMITS = {"loss_gap": 2e-3, "update_gap": 0.035, "change_gap": 0.15, "metric_gap": 0.012}


def cut(config: dict) -> dict:
    """A configuration at toy size: `LM_TOY`, then the file's own `toy`
    object (its experts, window and period of layers at toy size); an
    object in it, such as `arch`, updates the file's object of that key."""
    out = dict(config, **LM_TOY)
    for key, value in config.get("toy", {}).items():
        out[key] = dict(out.get(key) or {}, **value) if isinstance(value, dict) else value
    return out


def toy(name: str, root: str = catalog.ROOT, bench_dir: str = catalog.BENCH_DIR):
    """The cell `name` of `BENCHMARK.json` at toy size: the same mix kind and
    schedule, with the toy limits."""
    cell = copy.deepcopy(catalog.find_cell(name, root=root, bench_dir=bench_dir))
    cell.limits = dict(TOY_LIMITS)
    cell.config = cut(cell.config)
    cell.mix["data"].update(seq=32)
    cell.mix["federation"].update(eval_every=2)
    return cell


def cpu_chips(chips):
    import jax

    info = {"platform": jax.devices()[0].platform, "kind": "TPU v5 lite", "count": 1}
    return info, jax.devices()[:1]


def run(monkeypatch, name: str, *, seed: int = 2**33 + 5, seconds: float = 0.5) -> dict:
    """One harness run of the toy cell `name` on the CPU."""
    toy_cell = toy(name)
    monkeypatch.setattr(catalog, "find_cell", lambda _: toy_cell)
    monkeypatch.setattr(device, "require_chips", cpu_chips)
    # the persistent compilation cache stays off: the setting is process-wide
    monkeypatch.setattr(cli, "enable_cache", lambda: "off")
    args = argparse.Namespace(workload=name, seed=seed, seconds=seconds, trace=0)
    return cli.run(args, time.perf_counter())


def workloads() -> list:
    """The cells the toy tests run: every listed one."""
    bench = catalog.load_json(os.path.join(catalog.ROOT, "BENCHMARK.json"))
    return [w["name"] for w in bench["workloads"]]

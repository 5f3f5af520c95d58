"""Find a cell's files by the names `BENCHMARK.json` gives them.

A cell names a configuration (`configs/<file>` from its entry), a traffic
mix (`mixes/<traffic>.json`) and its limits (`limits/<cell>.json`); each
per-layer metric is read by `metrics/<metric>.py`.  Adding a cell, mix,
configuration or metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by its path (metric readers and references)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list        # the BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, root: str = ROOT, bench_dir: str = BENCH_DIR) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=load_json(os.path.join(root, conf["file"])),
        mix=load_json(os.path.join(bench_dir, "mixes", w["traffic"] + ".json")),
        limits=load_json(os.path.join(bench_dir, "limits", name + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def metric_module(name: str, bench_dir: str = BENCH_DIR):
    """The reader file of one per-layer metric: `read(ctx) -> float | None`,
    and optionally `SPANS`, the host spans it reads besides `cli.HOST_SPANS`."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    return load_module(path, "chipbench_metric_" + name.replace(".", "_"))


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """`read(ctx) -> float | None` of one per-layer metric."""
    return metric_module(name, bench_dir).read


def reference(name: str, bench_dir: str = BENCH_DIR):
    """A configuration's plain reference module (`refs/<name>.py`)."""
    path = os.path.join(bench_dir, "refs", name + ".py")
    return load_module(path, "chipbench_ref_" + name.replace(".", "_"))

"""`BENCHMARK.json` and the files the harness finds by its names: every cell
has its configuration, mix and limits, every per-layer metric its reader,
and every name and text keeps to the benchmark's format."""
import json
import os
import re
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import catalog  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = catalog.load_json(os.path.join(catalog.ROOT, "BENCHMARK.json"))


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_paths():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs", "workloads",
                              "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["benchmarks/chip"]
    assert BENCHMARK["command"][1].startswith("benchmarks/chip/")
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(catalog.ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("cell", BENCHMARK["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"]) and _text(cell["why"])
    assert cell["chips"] in (1, 4)
    found = catalog.find_cell(cell["name"])
    assert found.limits and found.mix["data"]["kind"] == "tokens"
    assert {m["name"] for m in found.end_to_end} >= {"setup_s", "round_ms"}
    assert found.per_layer


@pytest.mark.parametrize("config", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_each_configuration_file_states_its_source(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["file"].startswith("benchmarks/chip/configs/")
    body = catalog.load_json(os.path.join(catalog.ROOT, config["file"]))
    assert body["source"] == config["source"]
    assert {"cut", "assumed", "reference", "control", "precision"} <= set(body)
    assert os.path.exists(os.path.join(BENCH, "refs", body["reference"] + ".py"))
    assert any(w["config"] == config["name"] for w in BENCHMARK["workloads"])


@pytest.mark.parametrize("metric", BENCHMARK["end_to_end"] + BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_each_metric_is_well_formed(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if "bound" in metric:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["moves"] == "round_ms" and _text(metric["layer"])
        assert callable(catalog.metric_reader(metric["name"]))


def test_the_file_round_trips_as_json():
    assert json.loads(json.dumps(BENCHMARK)) == BENCHMARK

"""Observability stack: span tracer, telemetry carrier, merged Chrome-trace
export + validation, netsim drop surfacing, named_scope round attribution,
and the RunResult empty-log metric-direction fix."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedCHSConfig, run_fed_chs
from repro.core.ledger import CommLedger
from repro.core.simulation import RunResult
from repro.netsim import Timeline, edge_cloud_network, replay_run
from repro.netsim.events import JobTimes
from repro.obs import (
    RunTelemetry,
    SpanTracer,
    build_chrome_trace,
    validate_chrome_trace,
    write_metrics_jsonl,
)

# --------------------------------------------------------------------------
# RunResult: empty logs must read as WORST, respecting metric direction
# --------------------------------------------------------------------------


def test_empty_run_result_reads_worst_for_both_metric_modes():
    for mode, worst in (("max", 0.0), ("min", float("inf"))):
        r = RunResult("x", [], [], [], CommLedger(), None, metric_mode=mode)
        assert r.best_acc() == worst
        assert r.final_acc() == worst


def test_min_mode_best_and_final_are_consistent():
    r = RunResult("lm", [0, 1, 2], [9.0, 3.5, 4.0], [0.0, 0.0, 0.0],
                  CommLedger(), None, metric_mode="min")
    assert r.best_acc() == 3.5
    assert r.final_acc() == 4.0
    assert r.rounds_to_accuracy(4.0) == 1  # min mode: first eval <= gamma


# --------------------------------------------------------------------------
# SpanTracer
# --------------------------------------------------------------------------


def test_span_tracer_nesting_and_wall():
    tr = SpanTracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
        with tr.span("inner"):
            pass
    assert [(k, n) for k, n, _ in tr.events] == [
        ("B", "outer"), ("B", "inner"), ("E", "inner"),
        ("B", "inner"), ("E", "inner"), ("E", "outer")]
    ts = [t for _, _, t in tr.events]
    assert ts == sorted(ts) and ts[0] == 0.0
    assert tr.wall("outer") >= tr.wall("inner") >= 0.0


def test_run_telemetry_rows_and_jsonl(tmp_path):
    obs = RunTelemetry()
    obs.record_round(0, {"update_norm": jnp.float32(1.5), "mass": jnp.float32(3)})
    obs.record_stacked([1, 2], {"update_norm": jnp.asarray([2.0, 2.5]),
                                "mass": jnp.asarray([3.0, 2.0])})
    rows = obs.metrics_rows()
    assert [r["round"] for r in rows] == [0, 1, 2]
    assert rows[1]["update_norm"] == 2.0
    path = tmp_path / "m.jsonl"
    assert write_metrics_jsonl(obs, path) == 3
    back = [json.loads(line) for line in path.read_text().splitlines()]
    assert back == rows
    s = obs.summary()
    assert s["rounds"] == 3
    assert s["metrics"]["mass"]["max"] == 3.0


# --------------------------------------------------------------------------
# export + validation
# --------------------------------------------------------------------------


def test_validate_catches_malformed_traces():
    assert validate_chrome_trace({}) == ["traceEvents missing or not a list"]
    mismatched = {"traceEvents": [
        {"ph": "B", "pid": 1, "tid": "t", "name": "a", "ts": 1.0},
        {"ph": "E", "pid": 1, "tid": "t", "name": "b", "ts": 2.0}]}
    assert any("closes" in p for p in validate_chrome_trace(mismatched))
    unclosed = {"traceEvents": [
        {"ph": "B", "pid": 1, "tid": "t", "name": "a", "ts": 1.0}]}
    assert any("unclosed" in p for p in validate_chrome_trace(unclosed))
    backwards = {"traceEvents": [
        {"ph": "i", "pid": 2, "tid": "h", "name": "x", "ts": 5.0},
        {"ph": "i", "pid": 2, "tid": "h", "name": "y", "ts": 1.0}]}
    assert any("<" in p for p in validate_chrome_trace(backwards))
    ok = {"traceEvents": [
        {"ph": "i", "pid": 2, "tid": "h", "cat": "comm", "name": "x", "ts": 1.0}]}
    assert validate_chrome_trace(ok, expected_comm_events=2)  # count mismatch
    assert validate_chrome_trace(ok, expected_comm_events=1) == []


def test_ledger_event_index_groups_in_stream_order():
    led = CommLedger()
    led.record("client_to_es", 100, round=0, phase=0, sender="client:1",
               receiver="es:0")
    led.record("client_to_es", 100, round=0, phase=1, sender="client:1",
               receiver="es:0")
    led.record("es_to_es", 200, round=0, phase=2, sender="es:0", receiver="es:1")
    idx = led.event_index()
    assert idx[(0, "client_to_es", "client:1->es:0")] == [0, 1]
    assert idx[(0, "es_to_es", "es:0->es:1")] == [2]


def test_timeline_drop_counts():
    tl = Timeline(JobTimes(), {0: 1.0, 1: 2.0}, 2.0,
                  dropped={0: frozenset({"client:1", "client:2"}),
                           1: frozenset()})
    assert tl.drop_counts() == {0: 2}


def test_merged_trace_end_to_end(small_task):
    """One instrumented Fed-CHS run -> replay -> merged trace: valid, with
    every ledger event present as a comm instant and every netsim job as an
    X slice; drop bookkeeping rides along in otherData."""
    obs = RunTelemetry()
    cfg = FedCHSConfig(rounds=4, local_steps=4, local_epochs=2, eval_every=2,
                       seed=0, track_events=True, obs=obs)
    res = run_fed_chs(small_task, cfg)
    net = edge_cloud_network(seed=0)
    jobs, tl = replay_run(res, net, local_steps=cfg.local_steps,
                          batch_size=small_task.batch_size,
                          num_params=small_task.num_params())
    trace = build_chrome_trace(obs, res.ledger, jobs, tl)
    assert validate_chrome_trace(
        trace, expected_comm_events=len(res.ledger.events)) == []
    evs = trace["traceEvents"]
    assert sum(e.get("ph") == "X" for e in evs) == len(jobs)
    assert {e["pid"] for e in evs} == {1, 2, 3}
    assert trace["otherData"]["makespan_s"] == tl.makespan
    # comm instants sit at their carrying job's finish time, so none can
    # land after the simulated makespan
    comm_ts = [e["ts"] for e in evs if e.get("cat") == "comm"]
    assert comm_ts and max(comm_ts) <= tl.makespan * 1e6 + 1e-6


def test_trace_without_replay_uses_stream_order_clock(small_task):
    obs = RunTelemetry(taps=False)
    cfg = FedCHSConfig(rounds=2, local_steps=4, local_epochs=2, eval_every=1,
                       seed=1, track_events=True, obs=obs)
    res = run_fed_chs(small_task, cfg)
    trace = build_chrome_trace(obs, res.ledger)
    assert validate_chrome_trace(
        trace, expected_comm_events=len(res.ledger.events)) == []
    assert not obs.metrics  # taps=False: spans only, no tele


def test_sweep_rejects_telemetry(small_task):
    from repro.core import run_sweep

    cfg = FedCHSConfig(rounds=2, local_steps=2, eval_every=1,
                       obs=RunTelemetry())
    with pytest.raises(AssertionError, match="telemetry"):
        run_sweep(small_task, cfg, (0, 1))


# --------------------------------------------------------------------------
# named_scope round attribution: the engine's phase tags survive jit, so
# roofline.attribution.phase_bytes can bill a WHOLE Fed-CHS round by phase
# --------------------------------------------------------------------------


def test_phase_bytes_attributes_delta_round(small_task):
    from repro.core.engine import RoundEngine, _delta_round_fn, dummy_subs
    from repro.roofline.attribution import phase_bytes

    engine = RoundEngine(small_task.model)
    params = small_task.init_params()
    n = len(small_task.cluster_members[0])
    opt_state = engine.init_opt_state(params, n)
    batch = small_task.sample_round_batches(0, 4, 2)
    gammas = jnp.asarray(small_task.cluster_weights(0))
    lrs = jnp.full((2, 2), 0.05, jnp.float32)
    fn = _delta_round_fn(engine.model, engine.channel, engine.local_opt, False)
    hlo = fn.lower(params, opt_state, batch, gammas, lrs,
                   dummy_subs(2)).compile().as_text()
    got = phase_bytes(hlo, {"local_train": r"local_train",
                            "uplink": r"uplink",
                            "intra_agg": r"intra_agg"})
    assert got.get("local_train", 0.0) > 0.0
    assert got.get("uplink", 0.0) > 0.0
    assert got.get("intra_agg", 0.0) > 0.0
    # local training (per-client fwd+bwd over E steps) dominates the round
    assert got["local_train"] > got["intra_agg"]


def test_phase_bytes_attributes_multi_round_es_hop(small_task):
    from repro.core.engine import RoundEngine, _multi_round_fn, dummy_subs
    from repro.roofline.attribution import phase_bytes

    engine = RoundEngine(small_task.model)
    params = small_task.init_params()
    gammas, mask = small_task.padded_cluster_weights()
    M = small_task.num_clusters
    opt_state = engine.init_opt_state(params, M, mask.shape[1])
    batch = small_task.sample_all_cluster_batches(4, 2)
    es_weights = jnp.asarray(
        np.array(small_task.cluster_sizes, np.float32)
        / sum(small_task.cluster_sizes))
    lrs = jnp.full((2, 2), 0.05, jnp.float32)
    fn = _multi_round_fn(engine.model, engine.channel, engine.channel,
                         engine.local_opt, False)
    hlo = fn.lower(params, opt_state, batch, gammas, mask, es_weights, lrs,
                   dummy_subs(2, M), dummy_subs(M)).compile().as_text()
    got = phase_bytes(hlo, {"local_train": r"local_train",
                            "uplink": r"uplink",
                            "intra_agg": r"intra_agg",
                            "es_hop": r"es_hop"})
    for phase in ("local_train", "uplink", "intra_agg", "es_hop"):
        assert got.get(phase, 0.0) > 0.0, phase


# --------------------------------------------------------------------------
# a driver call's own work: the "call" span and its children, and the
# staged_bytes / trained_rounds counters (every scanned driver)
# --------------------------------------------------------------------------

from repro.core.baselines import (  # noqa: E402
    FedAvgConfig,
    HierLocalQSGDConfig,
    WRWGDConfig,
    run_fedavg,
    run_hier_local_qsgd,
    run_wrwgd,
)

# each span's parent in a direct call of a scanned driver
PARENT = {"call": None, "precompute": "call", "schedule": "precompute",
          "model_init": "precompute", "stage": "call", "draw": "stage",
          "device_put": "stage", "scan_chunk": "call", "eval": "call",
          "loss_fetch": "call", "materialize": "call"}

SCANNED = [
    ("fed_chs", run_fed_chs, FedCHSConfig,
     dict(rounds=5, local_steps=4, local_epochs=2, eval_every=2, seed=0)),
    ("fedavg", run_fedavg, FedAvgConfig, dict(rounds=3, local_steps=3, eval_every=2, seed=0)),
    ("wrwgd", run_wrwgd, WRWGDConfig, dict(rounds=4, local_steps=3, eval_every=2, seed=0)),
    ("hier_local_qsgd", run_hier_local_qsgd, HierLocalQSGDConfig,
     dict(rounds=3, local_steps=4, local_epochs=2, eval_every=2, seed=0)),
]

# Fed-CHS's two scanned bodies: per-step gradients (E=1, dense, no
# precision policy) and per-interaction client deltas
FED_CHS_MODES = {
    "grad": dict(rounds=5, local_steps=3, local_epochs=1, eval_every=2, seed=1),
    "delta": dict(rounds=5, local_steps=4, local_epochs=2, eval_every=2, seed=2,
                  qsgd_levels=8),
}


def _span_parents(events) -> list:
    """(name, parent name) of every span, in the order the spans opened."""
    out, stack = [], []
    for kind, name, _ in events:
        if kind == "B":
            out.append((name, stack[-1] if stack else None))
            stack.append(name)
        else:
            assert stack.pop() == name
    assert not stack
    return out


@pytest.mark.parametrize("name,run,cfg_cls,kwargs", SCANNED, ids=[c[0] for c in SCANNED])
def test_driver_call_spans_nest_under_their_parents(small_task, name, run, cfg_cls, kwargs):
    obs = RunTelemetry(taps=False)
    run(small_task, cfg_cls(**kwargs, obs=obs))
    pairs = _span_parents(obs.tracer.events)
    assert all(PARENT[n] == p for n, p in pairs), pairs
    names = [n for n, _ in pairs]
    assert names[0] == "call" and names.count("call") == 1
    assert {"precompute", "stage", "draw", "device_put", "scan_chunk", "eval",
            "loss_fetch", "materialize"} <= set(names)
    # each stage draws, then hands over; each eval is followed by its loss fetch
    stages = [i for i, n in enumerate(names) if n == "stage"]
    assert all(names[i + 1: i + 3] == ["draw", "device_put"] for i in stages)
    evals = [i for i, n in enumerate(names) if n == "eval"]
    assert evals and all(names[i + 1] == "loss_fetch" for i in evals)
    if name == "fed_chs":
        assert names.count("schedule") == 1 and names.count("model_init") >= 1


@pytest.mark.parametrize("mode", sorted(FED_CHS_MODES))
def test_one_model_init_span_per_init_call(small_task, monkeypatch, mode):
    real, calls = small_task.init_params, []

    def counting():
        calls.append(1)
        return real()

    monkeypatch.setattr(small_task, "init_params", counting)
    obs = RunTelemetry(taps=False)
    run_fed_chs(small_task, FedCHSConfig(**FED_CHS_MODES[mode], obs=obs))
    spans = [n for k, n, _ in obs.tracer.events if k == "B" and n == "model_init"]
    assert calls and len(spans) == len(calls)


@pytest.mark.parametrize("mode", sorted(FED_CHS_MODES))
def test_staged_bytes_counts_the_plans_own_staged_inputs(small_task, mode):
    import jax

    from repro.core.fed_chs import _fed_chs_scan_plan

    obs = RunTelemetry(taps=False)
    run_fed_chs(small_task, FedCHSConfig(**FED_CHS_MODES[mode], obs=obs))
    # every staged leaf has a leading round axis and fixed trailing dims, so
    # the chunks' bytes add up to one staging of every trained round
    plan, _, _ = _fed_chs_scan_plan(small_task, small_task.source,
                                    FedCHSConfig(**FED_CHS_MODES[mode]))
    idxs = np.flatnonzero(np.asarray(plan.trained))
    staged = sum(leaf.nbytes for leaf in jax.tree.leaves(plan.stage(idxs)))
    assert obs.counts == {"staged_bytes": staged, "trained_rounds": len(idxs)}
    assert obs.summary()["counts"] == obs.counts


@pytest.mark.parametrize("name,run,cfg_cls,kwargs", SCANNED, ids=[c[0] for c in SCANNED])
def test_obs_none_records_nothing_and_spans_change_no_bit(small_task, monkeypatch,
                                                          name, run, cfg_cls, kwargs):
    import jax

    seen = []
    real_span, real_count = SpanTracer.span, RunTelemetry.count

    def span(self, span_name):
        seen.append(span_name)
        return real_span(self, span_name)

    def count(self, counter, n=1):
        seen.append(counter)
        real_count(self, counter, n)

    monkeypatch.setattr(SpanTracer, "span", span)
    monkeypatch.setattr(RunTelemetry, "count", count)
    base = run(small_task, cfg_cls(**kwargs))
    assert seen == [] and base.telemetry is None
    traced = run(small_task, cfg_cls(**kwargs, obs=RunTelemetry(taps=False)))
    assert {"call", "staged_bytes", "trained_rounds"} <= set(seen)
    assert base.rounds == traced.rounds
    np.testing.assert_array_equal(base.train_loss, traced.train_loss)
    np.testing.assert_array_equal(base.test_acc, traced.test_acc)
    assert base.ledger.bits == traced.ledger.bits
    for a, b in zip(jax.tree.leaves(base.final_params), jax.tree.leaves(traced.final_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

"""Weighted Random-Walk Gradient Descent (Ayache & El Rouayheb, 2019) baseline.

The model walks over a *client-level* graph; each visited client runs K local
SGD steps (one engine grad-round with a single client), then forwards the
model to a neighbor chosen with probability proportional to a per-client
importance weight (the original uses local Lipschitz estimates; we use
dataset-size weighting, the standard "weighted" variant, with uniform as an
option). One client->client model hop per round, metered via the dense
channel.  The driver is model-agnostic: the batch is an opaque pytree staged
by the task's `DataSource`.

Participation (repro.part): `WRWGDConfig.sampler` gates both ends of the
walk — a visited client that is unavailable this round forwards the model
without training (pass-through), and the next hop is drawn from the
neighbors available *next* round (EdgeFLow-style: the walk skips dead
edges; if every neighbor is down the draw falls back to the full neighbor
set and the receiver passes through).  The default `FullParticipation`/None
path is bit-identical to the pre-participation stack.

Whole-run execution: the walk itself is host-side numpy rng — deterministic
given (seed, topology, sampler) — so with `scan_rounds=True` (default) the
entire visit sequence is precomputed and the training rounds run as chunked
`lax.scan`s over rounds (`engine.run_scan`); pass-through visits are skipped
by the scan and consume no data draws, exactly like the looped driver.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.channels import DenseChannel, channel_wire_bits
from repro.core.engine import RoundEngine, ScanPlan, run_scan, scan_grad_body
from repro.core.ledger import CommLedger
from repro.core.simulation import FLTask, RunRecorder, RunResult
from repro.core.topology import make_topology
from repro.data.sources import scatter_put, stage_chunk
from repro.obs.trace import maybe_span
from repro.optim.schedules import Schedule, paper_sqrt_schedule
from repro.part import Sampler, is_full_participation
from repro.sharding.fed import resolve_mesh, shard_plan


@dataclasses.dataclass
class WRWGDConfig:
    rounds: int = 200
    local_steps: int = 20
    topology: str = "random_sparse"   # client-level graph, degree <= 3 (paper B.1)
    topology_seed: int = 0
    weighting: str = "data_size"      # or "uniform"
    sampler: Sampler | None = None    # per-round participation (repro.part);
                                      # None / FullParticipation = seed-parity path
    track_events: bool = True          # False: bits only, no CommEvent stream
    scan_rounds: bool = True           # whole-run lax.scan executor
    chunk_rounds: int = 32             # scanned mode: rounds staged per chunk
    eval_every: int = 10
    bits_per_param: int = 32
    client_microbatch: int | None = None  # accepted for config-surface parity
                                          # with the other drivers; a walk
                                          # visits ONE client per round, so
                                          # any value degrades to mb=1
    seed: int = 0
    schedule: Schedule | None = None  # walk round t -> eta_t, constant over the
                                      # K local steps of that visit; default
                                      # eta_t = 1/(K sqrt(t+1)) (B.1 decay
                                      # indexed by the GLOBAL round — see
                                      # run_wrwgd)
    obs: Any = None                    # repro.obs.RunTelemetry; None = the
                                       # byte-for-byte untapped fast path
    mesh: Any = None                   # jax Mesh ("clusters", "clients");
                                       # a 1-client walk degrades gracefully
                                       # to replicated compute — accepted so
                                       # all four drivers share the config
                                       # surface (repro.sharding.fed)


def _precompute_walk(task: FLTask, config: WRWGDConfig):
    """Replay the walk's host rng draw-for-draw: returns (visits (R,),
    trains (R,) bool, hops list of (sender, receiver)).  The looped driver
    issues exactly these `rng.integers`/`rng.choice` calls."""
    topo = make_topology(config.topology, task.num_clients, seed=config.topology_seed)
    rng = np.random.default_rng(config.seed)
    current = int(rng.integers(task.num_clients))
    full_part = is_full_participation(config.sampler)

    visits, trains, hops = [], [], []
    for t in range(config.rounds):
        visits.append(current)
        trains.append(
            full_part or bool(config.sampler.participants(t, [current]))
        )
        nbrs = list(topo.neighbors(current))
        if not full_part:
            live = config.sampler.participants(t + 1, nbrs)
            nbrs = live or nbrs
        if config.weighting == "data_size":
            w = task.client_sizes[nbrs]
            w = w / w.sum()
        else:
            w = np.full(len(nbrs), 1.0 / len(nbrs))
        nxt = int(rng.choice(nbrs, p=w))
        hops.append((current, nxt))
        current = nxt
    return np.asarray(visits), np.asarray(trains), hops


def _walk_round_lrs(config: WRWGDConfig) -> np.ndarray:
    """(R, K) step sizes: row t is eta_t repeated over the K local steps.

    The random walk revisits clients forever, so the decaying schedule must
    be indexed by the GLOBAL walk round t — restarting it at eta_0 on every
    visit (the old behaviour) keeps the step size permanently large and the
    single-client updates never anneal: the model rattles between client
    optima instead of converging (final_acc ~0.67 on the tier-1 task vs
    ~0.93 with per-round decay).  Within one visit the K local steps share
    eta_t, matching the per-iteration decay of Ayache & El Rouayheb's
    random-walk SGD where one walk step IS one SGD iteration."""
    K = config.local_steps
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
    etas = np.asarray([sched_fn(t) for t in range(config.rounds)], np.float32)
    return np.repeat(etas[:, None], K, axis=1)


def run_wrwgd(task: FLTask, config: WRWGDConfig) -> RunResult:
    if config.scan_rounds:
        return _run_wrwgd_scanned(task, config)
    task.reset_loaders(config.seed)
    lrs_r = jnp.asarray(_walk_round_lrs(config))

    params = task.init_params()
    d = task.num_params()
    ledger = CommLedger(track_events=config.track_events)
    channel = DenseChannel(config.bits_per_param)
    engine = RoundEngine(task.model, channel,
                         client_microbatch=config.client_microbatch)
    hop_bits = channel_wire_bits(channel, d, task.param_leaf_sizes())
    gamma_one = jnp.ones((1,), jnp.float32)

    # the walk is pure host rng, independent of the training state — both
    # paths consume the ONE precomputed replay (the walk rng and the data
    # loaders are separate streams, so hoisting the draws changes nothing)
    visits, trains_r, hops = _precompute_walk(task, config)
    obs = config.obs
    taps = obs is not None and obs.taps
    recorder = RunRecorder(task, config.rounds, config.eval_every, obs=obs)
    losses = jnp.full((1,), jnp.nan)  # stays nan until a first trained round
    for t in range(config.rounds):
        if trains_r[t]:
            batch = jax.tree.map(
                lambda a: a[:, None],
                task.sample_client_batches(int(visits[t]), config.local_steps),
            )  # (K, 1, B, ...): a walk step is a 1-client cluster running Eq.(5)
            with maybe_span(obs, "round"):
                out = engine.grad_round(params, batch, gamma_one, lrs_r[t], taps=taps)
                params, losses, tele = out if taps else (*out, None)
            if tele is not None:
                obs.record_round(t, tele)
        # else: the visited client is down — pass-through, the model is
        # forwarded untouched (and the round consumes no data draws)
        prev, nxt = hops[t]
        ledger.record("client_to_client", hop_bits, round=t, phase=0,
                      sender=f"client:{prev}", receiver=f"client:{nxt}")
        engine.end_round(ledger, t)
        recorder.record(t, params, losses)

    return recorder.result("wrwgd", ledger, params)


# --------------------------------------------------------------------------
# scanned whole-run path
# --------------------------------------------------------------------------


def _wrwgd_scan_plan(task: FLTask, source, config: WRWGDConfig):
    """Whole-run `ScanPlan` + deferred glue (see `fed_chs._fed_chs_scan_plan`)."""
    source.reset(config.seed)
    K = config.local_steps
    lrs_r = _walk_round_lrs(config)

    params = task.init_params()
    d = task.num_params()
    channel = DenseChannel(config.bits_per_param)
    engine = RoundEngine(task.model, channel,
                         client_microbatch=config.client_microbatch)
    visits, trains, hops = _precompute_walk(task, config)
    R = config.rounds
    ones = np.ones((R, 1), np.float32)

    def stage(idxs):
        C = len(idxs)
        occ: dict[int, list[int]] = {}
        for c, t in enumerate(idxs):
            occ.setdefault(int(visits[t]), []).append(c)
        batch = stage_chunk(
            source,
            [(client, K * len(cs),
              scatter_put((cs, slice(None), 0),
                          lambda dl, n=len(cs): dl.reshape(n, K, *dl.shape[1:])))
             for client, cs in occ.items()],
            lambda a: (C, K, 1) + a.shape[1:],
        )
        return {"batch": batch, "gammas": ones[idxs], "lrs": lrs_r[idxs]}

    taps = config.obs is not None and config.obs.taps
    plan = ScanPlan(
        body=scan_grad_body(engine.model, taps, config.client_microbatch),
        carry=params,
        consts={},
        stage=stage,
        trained=trains,
        rounds=R,
        eval_every=config.eval_every,
        chunk_rounds=config.chunk_rounds,
        obs=config.obs,
    )

    mesh = resolve_mesh(config.mesh)
    if mesh is not None:
        plan = shard_plan(plan, mesh, "grad", model=engine.model, clients=1)

    hop_bits = channel_wire_bits(channel, d, task.param_leaf_sizes())

    def traffic(track_events: bool):
        del track_events  # one metered hop per round either way
        for t, (prev, nxt) in enumerate(hops):
            yield t, [("client_to_client", hop_bits, 1, 0,
                       f"client:{prev}", f"client:{nxt}")]

    return plan, (lambda c: c), traffic


def _run_wrwgd_scanned(task: FLTask, config: WRWGDConfig) -> RunResult:
    obs = config.obs
    with maybe_span(obs, "call"):
        with maybe_span(obs, "precompute"):
            plan, params_of, traffic = _wrwgd_scan_plan(task, task.source, config)
        recorder = RunRecorder(task, config.rounds, config.eval_every, obs=obs)
        carry = run_scan(
            plan, lambda t, c, losses, _lt: recorder.record(t, params_of(c), losses)
        )
        ledger = CommLedger(track_events=config.track_events)
        with maybe_span(obs, "materialize"):
            ledger.materialize(traffic(config.track_events))
        return recorder.result("wrwgd", ledger, params_of(carry))

"""Fed-CHS (Algorithm 1) — the paper's contribution, as a thin strategy driver
over the jitted round engine (`repro.core.engine`).

Round t:
  1. ES m(t) broadcasts w^t to its cluster's clients.
  2. K/E interactions: clients run E local optimizer steps from the broadcast
     model (E=1 + plain SGD reproduces Eq. (5) literally: the uploaded
     "delta" is eta_k * grad), upload their update, and the ES takes the
     gamma-weighted aggregate.  The whole inner loop — local steps, deltas,
     channel compression, aggregation — is one fused `lax.scan` on device;
     batches are staged a round at a time, and the only per-round host
     traffic is the params handle, the cluster's client-held optimizer
     states, plus one stacked loss array.
  3. m(t) selects m(t+1) by the 2-step least-traversed / largest-dataset rule
     and pushes w^{t+1} over a single ES->ES hop. No PS anywhere.

The driver is generic over the task's `FedModel` / `DataSource` / `LocalOpt`:
an Appendix-A MLP and a transformer LM take exactly this code path.
Communication is metered bit-exactly via CommLedger; uplinks traverse a
pluggable `Channel` (dense / Pallas-backed QSGD / Top-K) which owns both the
in-graph lossy transform and the per-message bit accounting.  Client-held
optimizer state (e.g. AdamW moments) never traverses a channel.  Every
message is also recorded as a structured `CommEvent` (round, interaction
phase, sender, receiver) so `repro.netsim` can replay the run through link
models and answer the wall-clock question §3.2's bit counting cannot:
whether the serial ES->ES chain beats the baselines' parallel-but-PS-bound
uploads.

Participation (repro.part): `FedCHSConfig.sampler` decides which of the
active cluster's clients report each round.  Participants run the masked
engine round (renormalized gammas, frozen opt state for everyone else); a
cluster whose clients are ALL unavailable degrades to a pass-through hop —
the ES forwards the model over the ES->ES pass without training, the
HiFlash-style staleness answer to dead clusters.  With
`availability_scheduler=True` the 2-step rule itself skips unreachable
neighbors (`AvailabilityAwareScheduler`).  The default
`FullParticipation`/None path is bit-identical to the pre-participation
stack.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.channels import Channel, DenseChannel, channel_wire_bits
from repro.core.engine import (
    RoundEngine,
    ScanPlan,
    run_scan,
    scan_cluster_delta_body,
    scan_grad_body,
    split_chain,
)
from repro.core.ledger import CommLedger
from repro.core.precision import (
    Precision,
    downlink_bits_per_param,
    resolve_channel,
)
from repro.core.scheduler import (
    AvailabilityAwareScheduler,
    FedCHSScheduler,
    LatencyAwareScheduler,
)
from repro.core.simulation import FLTask, RunRecorder, RunResult
from repro.core.topology import make_topology
from repro.obs.trace import maybe_span
from repro.data.sources import scatter_put, stage_chunk
from repro.optim.local import LocalOpt, PlainSGD
from repro.optim.schedules import Schedule, paper_sqrt_schedule
from repro.part import Sampler, is_full_participation, participation_mask
from repro.sharding.fed import resolve_mesh, shard_plan


@dataclasses.dataclass
class FedCHSConfig:
    rounds: int = 200                      # T
    local_steps: int = 20                  # K (total in-cluster iterations)
    local_epochs: int = 1                  # E (local steps per upload); K % E == 0
    topology: str = "random_sparse"        # paper B.1: random sparse, degree <= 3
    topology_seed: int = 0
    dynamic: str | None = None             # "leo" / "iov": per-round graphs
                                           # (core/dynamics.py, Appendix D)
    initial_cluster: int | None = None     # None -> random per Algorithm 1 line 4
    eval_every: int = 10
    bits_per_param: int = 32
    qsgd_levels: int | None = None         # uplink compression (None = dense)
    channel: Channel | None = None         # explicit uplink channel; overrides
                                           # qsgd_levels/bits_per_param
    local_opt: LocalOpt | None = None      # client-held optimizer; None = the
                                           # seed-parity plain-SGD Eq. (5) step
    client_microbatch: int | None = None   # engine memory knob: at most this
                                           # many client replicas train at once
                                           # (None = the all-clients vmap);
                                           # <=1 ulp/interaction, exact at
                                           # client_microbatch >= n
    precision: Precision | None = None     # mixed-precision policy
                                           # (core/precision.py): bf16 client
                                           # compute, f32 master params at the
                                           # ES, wire-dtype dense messages.
                                           # None = the exact f32 seed path.
                                           # Forces delta mode (grad mode is
                                           # the paper-literal f32 arm).
    link_delay: Callable[[int, int], float] | None = None
                                           # ES-pair delay (seconds); switches the
                                           # scheduler to LatencyAwareScheduler
    sampler: Sampler | None = None         # per-round participation (repro.part);
                                           # None / FullParticipation = the exact
                                           # seed-parity pre-participation path
    availability_scheduler: bool = False   # with a sampler: 2-step rule over
                                           # reachable neighbors only
                                           # (AvailabilityAwareScheduler)
    track_events: bool = True              # False: bits only, no CommEvent stream
                                           # (saves memory at --full scale)
    scan_rounds: bool = True               # whole-run lax.scan executor (all
                                           # topologies: dynamic IoV/LEO graphs
                                           # replay host-side — seed-deterministic)
    chunk_rounds: int = 32                 # scanned mode: rounds staged/scanned per
                                           # chunk (bounds staged-batch memory)
    seed: int = 0
    schedule: Schedule | None = None       # default: paper eta_k = 1/(K sqrt(k+1))
    obs: Any = None                        # repro.obs.RunTelemetry: in-graph taps
                                           # + host spans; None (default) keeps the
                                           # compiled graphs byte-for-byte unchanged
    mesh: Any = None                       # jax Mesh with axes ("clusters",
                                           # "clients"): shard the scanned round's
                                           # stacked client axis over the devices
                                           # (repro.sharding.fed, bit-identical).
                                           # None adopts an ambient federation mesh
                                           # (sharding.ctx.model_mesh) if one is
                                           # published, else runs the byte-for-byte
                                           # single-device path.  Looped runs
                                           # (scan_rounds=False) ignore it.
    checkpoint: str | None = None          # path prefix: save the full run state
                                           # every checkpoint_every rounds (forces
                                           # the looped path — the scanned executor
                                           # has no round boundary to save at)
    checkpoint_every: int = 1
    resume: bool = False                   # load the checkpoint if present; the
                                           # resumed run is bit-identical to one
                                           # that was never interrupted


def _make_scheduler(task: FLTask, config: FedCHSConfig, topo, m0: int):
    """The looped and scanned paths build the identical scheduler."""
    if config.availability_scheduler:
        assert config.sampler is not None, "availability_scheduler needs a sampler"

        def reachable(m_: int, r: int) -> bool:
            return len(config.sampler.participants(r, task.cluster_members[m_])) > 0

        return AvailabilityAwareScheduler(topo, task.cluster_sizes, reachable, initial=m0)
    if config.link_delay is not None:
        return LatencyAwareScheduler(topo, task.cluster_sizes, config.link_delay, initial=m0)
    return FedCHSScheduler(topo, task.cluster_sizes, initial=m0)


def _fed_chs_scannable(task: FLTask, config: FedCHSConfig) -> bool:
    """Whether this run can take the whole-run scan path bit-identically.

    Always True now.  Ragged cluster sizes used to force stacked-leaf QSGD
    onto the looped driver (padding to n_max shifted block alignment); with
    per-leaf block boundaries and per-sender fold_in keys every channel is
    padding-invariant.  Dynamic topologies used to need per-round host
    decisions; IoV/LEO graphs are seed-deterministic functions of the round
    index, so `Scheduler.precompute(dynamic=...)` replays the whole visit
    order host-side (step-exact with the looped driver's
    `set_topology`/`advance` sequence).  Kept as a function: it documents
    the gate and gives future genuinely-unscannable configs a seam.
    """
    del task, config
    return True


def _save_sync_state(path: str, task, t_next: int, params, opt_states, key,
                     losses, scheduler, ledger, recorder) -> None:
    """Persist the looped driver's complete round-boundary state (atomic)."""
    from repro.checkpoint.io import save_run_state

    arrays = {
        "params": params,
        "key": key,
        "losses": losses,
        "opt": {str(m): s for m, s in opt_states.items()},
    }
    meta = {
        "algo": "fed_chs",
        "round": t_next,
        "scheduler": {
            "current": int(scheduler.state.current),
            "visit_counts": [int(c) for c in scheduler.state.visit_counts],
            "step": int(scheduler.state.step),
        },
        "opt_clusters": sorted(opt_states),
        "losses_shape": list(np.shape(losses)),
        "draw_counts": list(task.source.draw_counts),
        "ledger": ledger.state_dict(),
        "recorder": {
            "rounds": recorder.rounds_log,
            "acc": recorder.acc_log,
            "loss": recorder.loss_log,
        },
    }
    save_run_state(path, arrays, meta)


def _load_sync_state(path: str, task, params0, engine, scheduler, ledger,
                     recorder):
    """Restore the looped driver's state; returns (t, params, opt_states,
    key, losses).  Mutates scheduler/ledger/recorder/data-source in place."""
    import json

    from repro.checkpoint.io import load_run_state

    with open(path + ".meta.json") as f:
        meta = json.load(f)
    like = {
        "params": params0,
        "key": jax.random.PRNGKey(0),
        "losses": np.zeros(meta["losses_shape"], np.float32),
        "opt": {
            str(m): engine.init_opt_state(
                params0, len(task.cluster_members[int(m)]))
            for m in meta["opt_clusters"]
        },
    }
    arrays, meta = load_run_state(path, like)
    st = meta["scheduler"]
    scheduler.state.current = int(st["current"])
    scheduler.state.visit_counts = np.asarray(st["visit_counts"], np.int64)
    scheduler.state.step = int(st["step"])
    ledger.load_state(meta["ledger"])
    recorder.rounds_log = list(meta["recorder"]["rounds"])
    recorder.acc_log = list(meta["recorder"]["acc"])
    recorder.loss_log = list(meta["recorder"]["loss"])
    task.source.fast_forward(meta["draw_counts"])
    opt_states = {int(m): s for m, s in arrays["opt"].items()}
    return (int(meta["round"]), arrays["params"], opt_states, arrays["key"],
            arrays["losses"])


def run_fed_chs(task: FLTask, config: FedCHSConfig) -> RunResult:
    if (config.scan_rounds and _fed_chs_scannable(task, config)
            and not config.checkpoint):
        return _run_fed_chs_scanned(task, config)
    task.reset_loaders(config.seed)
    assert config.local_steps % config.local_epochs == 0, "K must divide by E"
    K, E = config.local_steps, config.local_epochs
    interactions = K // E
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
    lrs = np.array([sched_fn(k) for k in range(K)], dtype=np.float32)
    lrs_flat = jnp.asarray(lrs)                              # (K,)  grad mode
    lrs_grouped = jnp.asarray(lrs.reshape(interactions, E))  # (J,E) delta mode

    dyn = None
    if config.dynamic is not None:
        from repro.core.dynamics import make_dynamic

        dyn = make_dynamic(config.dynamic, task.num_clusters, seed=config.topology_seed)
        topo = dyn(0)
    else:
        topo = make_topology(config.topology, task.num_clusters, seed=config.topology_seed)
    rng = np.random.default_rng(config.seed)
    m0 = (
        int(rng.integers(task.num_clusters))
        if config.initial_cluster is None
        else config.initial_cluster
    )
    full_part = is_full_participation(config.sampler)
    scheduler = _make_scheduler(task, config, topo, m0)

    params = task.init_params()
    d = task.num_params()
    ledger = CommLedger(track_events=config.track_events)
    channel = resolve_channel(config.precision, config.channel,
                              config.qsgd_levels, config.bits_per_param)
    engine = RoundEngine(task.model, channel, local_opt=config.local_opt,
                         client_microbatch=config.client_microbatch,
                         precision=config.precision)
    key = jax.random.PRNGKey(config.seed + 1)

    # model broadcast travels at the wire width under a precision policy
    down_bits = DenseChannel(
        downlink_bits_per_param(config.precision, config.bits_per_param)
    ).message_bits(d)
    up_bits = channel_wire_bits(channel, d, task.param_leaf_sizes())

    # literal Eq. (5): E=1 dense plain-SGD interactions are gradient uplinks
    # fused into the per-step gamma-weighted SGD scan (explicit PlainSGD is
    # the same mathematical step, so it takes the same path as the default).
    # A non-full sampler forces delta mode: dropouts need the masked round.
    # Mixed precision also forces delta mode — grad mode is the paper-literal
    # f32 arm — as does a lossy dense wire (its cast must enter the uplink).
    grad_mode = (
        full_part
        and E == 1
        and isinstance(channel, DenseChannel)
        and channel.wire_dtype is None
        and config.precision is None
        and (config.local_opt is None or isinstance(config.local_opt, PlainSGD))
    )
    opt_states: dict[int, object] = {}  # cluster -> stacked client-held opt state

    obs = config.obs
    taps = obs is not None and obs.taps
    recorder = RunRecorder(task, config.rounds, config.eval_every, obs=obs)
    m = scheduler.state.current
    losses = jnp.full((1,), jnp.nan)  # stays nan until a first trained round
    start_round = 0
    if config.resume and config.checkpoint:
        from repro.checkpoint.io import run_state_exists

        if run_state_exists(config.checkpoint):
            (start_round, params, opt_states, key, losses) = _load_sync_state(
                config.checkpoint, task, params, engine, scheduler, ledger,
                recorder,
            )
            m = scheduler.state.current
    for t in range(start_round, config.rounds):
        members = task.cluster_members[m]
        participating = (
            members if full_part else config.sampler.participants(t, members)
        )

        tele = None
        if grad_mode:
            gammas = jnp.asarray(task.cluster_weights(m))
            batch = task.sample_cluster_batches(m, K)
            with maybe_span(obs, "round"):
                out = engine.grad_round(params, batch, gammas, lrs_flat, taps=taps)
                params, losses, tele = out if taps else (*out, None)
        elif full_part:
            gammas = jnp.asarray(task.cluster_weights(m))
            batch = task.sample_round_batches(m, K, E)
            subs = None
            if channel.stochastic:
                key, subs = split_chain(key, interactions)
            if m not in opt_states:
                opt_states[m] = engine.init_opt_state(params, len(members))
            with maybe_span(obs, "round"):
                out = engine.cluster_round(
                    params, batch, gammas, lrs_grouped, subs, opt_states[m],
                    taps=taps,
                )
                params, opt_states[m], losses, tele = out if taps else (*out, None)
        elif participating:
            # masked round: gammas renormalized over the participating set;
            # batches are staged at full cluster width so the per-client data
            # schedule is independent of churn (dropped clients' draws are
            # consumed but masked out — their opt state stays frozen)
            pmask = participation_mask(members, participating)
            w = task.cluster_weights(m) * pmask
            gammas = jnp.asarray((w / w.sum()).astype(np.float32))
            batch = task.sample_round_batches(m, K, E)
            subs = None
            if channel.stochastic:
                key, subs = split_chain(key, interactions)
            if m not in opt_states:
                opt_states[m] = engine.init_opt_state(params, len(members))
            with maybe_span(obs, "round"):
                out = engine.cluster_round(
                    params, batch, gammas, lrs_grouped, subs, opt_states[m],
                    mask=pmask, taps=taps,
                )
                params, opt_states[m], losses, tele = out if taps else (*out, None)
        # else: the whole cluster is unavailable — the ES becomes a pass-
        # through hop: no training, no client traffic, the model is simply
        # forwarded on the ES->ES pass below (losses keeps its last value)
        if tele is not None:
            obs.record_round(t, tele)

        # comm accounting: one broadcast + one upload per *participating*
        # client per interaction, metered per message so netsim sees the
        # phase barriers (with events off, the aggregate-identical single
        # records suffice).  Dropped clients cost zero uplink bits.
        es, prev_m = f"es:{m}", m
        if participating:
            if ledger.track_events:
                for j in range(interactions):
                    for i in participating:
                        ledger.record("es_to_client", down_bits, round=t, phase=j,
                                      sender=es, receiver=f"client:{i}")
                        ledger.record("client_to_es", up_bits, round=t, phase=j,
                                      sender=f"client:{i}", receiver=es)
            else:
                ledger.record("es_to_client", down_bits,
                              interactions * len(participating))
                ledger.record("client_to_es", up_bits,
                              interactions * len(participating))

        # next passing cluster (2-step rule) + one ES->ES model hop.
        # Under a dynamic network the ES sees *this round's* visibility graph
        # when choosing the next hop (Appendix-D scenarios).
        if dyn is not None:
            scheduler.set_topology(dyn(t))
        m = scheduler.advance()
        ledger.record("es_to_es", down_bits, round=t, phase=interactions,
                      sender=f"es:{prev_m}", receiver=f"es:{m}")
        engine.end_round(ledger, t)
        recorder.record(t, params, losses)
        if config.checkpoint and (t + 1) % config.checkpoint_every == 0:
            _save_sync_state(config.checkpoint, task, t + 1, params,
                             opt_states, key, losses, scheduler, ledger,
                             recorder)

    return recorder.result("fed_chs", ledger, params)


# --------------------------------------------------------------------------
# scanned whole-run path (engine.run_scan): the entire schedule — visit
# order, participation masks, renormalized gammas, PRNG subkeys — is
# precomputed host-side, batches are staged a chunk of rounds at a time, and
# the hot loop is one lax.scan per chunk with zero host transfers between
# eval points.  Communication accounting is deferred (`CommLedger.
# materialize`).  Bit-identical params/metrics to the looped path at fixed
# seed (tests/test_engine_parity.py); pass-through rounds consume no data
# draws or subkeys, exactly like the looped driver.
# --------------------------------------------------------------------------


def _fed_chs_scan_plan(task: FLTask, source, config: FedCHSConfig):
    """Build the whole-run `ScanPlan` + deferred glue for one Fed-CHS run.

    `source` is the staging DataSource (the task's own for a single run; a
    per-seed copy for `run_sweep`).  Returns (plan, params_of, traffic) —
    `params_of(carry)` extracts the model params, `traffic(track_events)`
    yields the deferred per-round ledger entries.
    """
    source.reset(config.seed)
    assert config.local_steps % config.local_epochs == 0, "K must divide by E"
    K, E = config.local_steps, config.local_epochs
    interactions = K // E
    R = config.rounds
    M = task.num_clusters
    members_of = task.cluster_members
    n_max = max(len(m) for m in members_of)
    full_part = is_full_participation(config.sampler)
    obs = config.obs
    channel = resolve_channel(config.precision, config.channel,
                              config.qsgd_levels, config.bits_per_param)

    with maybe_span(obs, "schedule"):
        sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
        lrs = np.array([sched_fn(k) for k in range(K)], dtype=np.float32)

        dyn = None
        if config.dynamic is not None:
            from repro.core.dynamics import make_dynamic

            dyn = make_dynamic(config.dynamic, M, seed=config.topology_seed)
            topo = dyn(0)
        else:
            topo = make_topology(config.topology, M, seed=config.topology_seed)
        rng = np.random.default_rng(config.seed)
        m0 = (
            int(rng.integers(M))
            if config.initial_cluster is None
            else config.initial_cluster
        )
        scheduler = _make_scheduler(task, config, topo, m0)
        # visit order incl. m(R): round R-1's ES->ES hop names its receiver;
        # dynamic (IoV/LEO) graphs replay seed-deterministically inside
        ms = scheduler.precompute(R + 1, dynamic=dyn)

        parts = [
            list(members_of[ms[t]]) if full_part
            else config.sampler.participants(t, members_of[ms[t]])
            for t in range(R)
        ]
        trained = np.array([len(p) > 0 for p in parts])

        # per-round gamma/mask rows, padded to n_max (zero-weight slots
        # contribute exact zeros — the padded computation matches the looped
        # unpadded one)
        gammas_r = np.zeros((R, n_max), np.float32)
        mask_r = np.zeros((R, n_max), np.float32)
        for t in np.flatnonzero(trained):
            members = members_of[ms[t]]
            w = task.cluster_weights(ms[t])
            if full_part:
                gammas_r[t, : len(members)] = w
                mask_r[t, : len(members)] = 1.0
            else:
                pmask = participation_mask(members, parts[t])
                w = w * pmask
                gammas_r[t, : len(members)] = (w / w.sum()).astype(np.float32)
                mask_r[t, : len(members)] = pmask

        # PRNG subkeys: one fused split chain over the trained rounds
        # reproduces the looped per-round `split_chain(key, J)` calls
        # draw-for-draw
        subs_r = np.zeros((R, interactions, 2), np.uint32)
        if channel.stochastic:
            n_tr = int(trained.sum())
            if n_tr:
                _, flat = split_chain(jax.random.PRNGKey(config.seed + 1), n_tr * interactions)
                subs_r[trained] = np.asarray(flat).reshape(n_tr, interactions, 2)

    # every call that runs the model's init has a "model_init" span of its own
    with maybe_span(obs, "model_init"):
        params = task.init_params()
    with maybe_span(obs, "model_init"):
        d = task.num_params()
    engine = RoundEngine(task.model, channel, local_opt=config.local_opt,
                         client_microbatch=config.client_microbatch,
                         precision=config.precision)

    grad_mode = (
        full_part
        and E == 1
        and isinstance(channel, DenseChannel)
        and channel.wire_dtype is None
        and config.precision is None
        and (config.local_opt is None or isinstance(config.local_opt, PlainSGD))
    )
    taps = obs is not None and obs.taps

    def _occurrences(idxs):
        """chunk positions grouped by active cluster, in round order."""
        occ: dict[int, list[int]] = {}
        for c, t in enumerate(idxs):
            occ.setdefault(int(ms[t]), []).append(c)
        return occ

    def _stage_batches(idxs, reshape, alloc):
        """Draw every staged batch of the chunk with one bulk read per
        client; per-client draw order is identical to looped round-by-round
        staging (clients hold independent rng streams, so cross-client order
        is immaterial)."""
        plan, pads = [], []
        for m, cs in _occurrences(idxs).items():
            members = members_of[m]
            plan += [
                (client, K * len(cs),
                 scatter_put((cs, slice(None), slot),
                             lambda dl, n=len(cs): reshape(n, dl)))
                for slot, client in enumerate(members)
            ]
            if len(members) < n_max:
                pads.append((cs, len(members)))
        batch = stage_chunk(source, plan, lambda a, C=len(idxs): alloc(C, a))
        for cs, n_real in pads:  # padded slots replicate member 0
            jax.tree.map(
                lambda bl: bl.__setitem__(
                    (cs, slice(None), slice(n_real, None)), bl[cs, :, 0:1]),
                batch,
            )
        return batch

    if grad_mode:
        # leaves (C, K, n_max, B, ...); per-client draws (occ*K, B, ...) land
        # at [cs, :, slot] as (occ, K, B, ...)
        # Fed-CHS restarts the B.1 within-round decay every round (Eq. (5)),
        # so the staged per-round lrs rows are all identical
        lrs_r = np.broadcast_to(np.asarray(lrs, np.float32), (R, K))

        def stage(idxs):
            batch = _stage_batches(
                idxs,
                reshape=lambda n_occ, dl: dl.reshape(n_occ, K, *dl.shape[1:]),
                alloc=lambda C, a: (C, K, n_max) + a.shape[1:],
            )
            return {"batch": batch, "gammas": gammas_r[idxs],
                    "lrs": np.ascontiguousarray(lrs_r[idxs])}

        body = scan_grad_body(engine.model, taps, config.client_microbatch)
        carry = params
        consts = {}
        params_of = lambda c: c  # noqa: E731
    else:
        # leaves (C, J, n_max, E, B, ...); per-client draws reshape to
        # (occ, J, E, B, ...) — the same K -> (J, E) grouping as
        # FLTask._stage_round_np
        def stage(idxs):
            batch = _stage_batches(
                idxs,
                reshape=lambda n_occ, dl: dl.reshape(n_occ, interactions, E, *dl.shape[1:]),
                alloc=lambda C, a: (C, interactions, n_max, E) + a.shape[1:],
            )
            return {
                "m": ms[idxs].astype(np.int32),
                "batch": batch,
                "gammas": gammas_r[idxs],
                "mask": mask_r[idxs],
                "subs": subs_r[idxs],
            }

        body = scan_cluster_delta_body(engine.model, channel, engine.local_opt,
                                       taps, config.client_microbatch,
                                       config.precision)
        carry = (params, engine.init_opt_state(params, M, n_max))
        consts = {"lrs": jnp.asarray(lrs.reshape(interactions, E))}
        params_of = lambda c: c[0]  # noqa: E731

    plan = ScanPlan(body=body, carry=carry, consts=consts, stage=stage,
                    trained=trained, rounds=R, eval_every=config.eval_every,
                    chunk_rounds=config.chunk_rounds, obs=obs)

    mesh = resolve_mesh(config.mesh)
    if mesh is not None:
        # mutually exclusive memory strategies: the mesh shards the client
        # axis across devices, the microbatch scan folds it in time
        assert config.client_microbatch is None, \
            "client_microbatch and a federation mesh are mutually exclusive"
        # population sharding: the active cluster's client axis spreads over
        # the whole mesh (one cluster trains per round — see sharding.fed)
        if grad_mode:
            plan = shard_plan(plan, mesh, "grad", model=engine.model,
                              clients=n_max)
        else:
            plan = shard_plan(plan, mesh, "cluster_delta", model=engine.model,
                              channel=channel, opt=engine.local_opt,
                              clients=n_max)

    down_bits = DenseChannel(
        downlink_bits_per_param(config.precision, config.bits_per_param)
    ).message_bits(d)
    with maybe_span(obs, "model_init"):
        leaf_sizes = task.param_leaf_sizes()
    up_bits = channel_wire_bits(channel, d, leaf_sizes)

    def traffic(track_events: bool):
        """Closed-form per-round ledger entries from the precomputed
        schedule — byte-for-byte the looped driver's record stream."""
        for t in range(R):
            entries = []
            p = parts[t]
            if p:
                es = f"es:{ms[t]}"
                if track_events:
                    for j in range(interactions):
                        for i in p:
                            entries.append(("es_to_client", down_bits, 1, j,
                                            es, f"client:{i}"))
                            entries.append(("client_to_es", up_bits, 1, j,
                                            f"client:{i}", es))
                else:
                    entries.append(("es_to_client", down_bits,
                                    interactions * len(p), 0, None, None))
                    entries.append(("client_to_es", up_bits,
                                    interactions * len(p), 0, None, None))
            entries.append(("es_to_es", down_bits, 1, interactions,
                            f"es:{ms[t]}", f"es:{ms[t + 1]}"))
            yield t, entries

    return plan, params_of, traffic


def _run_fed_chs_scanned(task: FLTask, config: FedCHSConfig) -> RunResult:
    obs = config.obs
    with maybe_span(obs, "call"):
        with maybe_span(obs, "precompute"):
            plan, params_of, traffic = _fed_chs_scan_plan(task, task.source, config)
        recorder = RunRecorder(task, config.rounds, config.eval_every, obs=obs)
        carry = run_scan(
            plan, lambda t, c, losses, _lt: recorder.record(t, params_of(c), losses)
        )
        ledger = CommLedger(track_events=config.track_events)
        with maybe_span(obs, "materialize"):
            ledger.materialize(traffic(config.track_events))
        return recorder.result("fed_chs", ledger, params_of(carry))

"""Hier-Local-QSGD (Liu et al., 2023a) baseline — classic 3-tier HFL with
quantized uplinks, driven by the engine's vmapped multi-cluster round.

Per global round:
  * K/E edge aggregations: every cluster's clients run E local steps from the
    cluster model; the ES aggregates their channel-compressed deltas.  All M
    clusters advance together inside one jit call — the engine vmaps the
    cluster interaction over a padded/masked (M, n_max) client grid instead
    of looping clusters in Python.
  * After the K in-cluster steps, every ES uploads its compressed cluster
    delta to the PS (per-cluster PRNG keys, split per leaf inside the
    channel), which takes the D_{A,m}/D_A-weighted average and broadcasts —
    the star-shaped, communication-heavy step Fed-CHS removes.

The driver is generic over the task's `FedModel` / `DataSource` / `LocalOpt`:
batches are opaque pytrees, and client-held optimizer state lives in one
(M, n_max)-stacked pytree that persists across global rounds without ever
traversing a channel.

Participation (repro.part): `HierLocalQSGDConfig.sampler` picks each
cluster's reporters per round.  Dropouts fold into the engine's existing
padded/masked client slots (zero gamma, zero uplink bits, frozen opt
state); a fully-dropped cluster's ES is a pass-through — zero delta, zero
PS weight, no ES->PS upload, though it still receives the broadcast so it
stays in sync.  The default `FullParticipation`/None path is bit-identical
to the pre-participation stack.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.channels import Channel, DenseChannel, channel_wire_bits
from repro.core.engine import (
    RoundEngine,
    ScanPlan,
    run_scan,
    scan_multi_body,
    split_chain,
)
from repro.core.ledger import CommLedger
from repro.core.precision import (
    Precision,
    downlink_bits_per_param,
    resolve_channel,
)
from repro.core.simulation import FLTask, RunRecorder, RunResult
from repro.data.sources import scatter_put, stage_chunk
from repro.obs.trace import maybe_span
from repro.optim.local import LocalOpt
from repro.optim.schedules import Schedule, paper_sqrt_schedule
from repro.part import Sampler, is_full_participation, participation_mask
from repro.sharding.fed import resolve_mesh, shard_plan


@dataclasses.dataclass
class HierLocalQSGDConfig:
    rounds: int = 200
    local_steps: int = 20          # K in-cluster iterations per global round
    local_epochs: int = 5          # E (paper B.1: 5 local iterations per round)
    eval_every: int = 10
    bits_per_param: int = 32
    qsgd_levels: int | None = 16   # uplink quantization (client->ES and ES->PS)
    channel: Channel | None = None     # explicit client->ES channel
    es_channel: Channel | None = None  # explicit ES->PS channel (defaults to channel)
    local_opt: LocalOpt | None = None  # client-held optimizer (None = plain SGD)
    client_microbatch: int | None = None  # at most this many client replicas
                                          # per cluster train at once
                                          # (None = full vmap)
    precision: Precision | None = None    # mixed-precision policy: bf16
                                          # client compute, f32 master at the
                                          # PS, wire-dtype dense messages
    sampler: Sampler | None = None     # per-round participation (repro.part);
                                       # None / FullParticipation = seed-parity path
    track_events: bool = True          # False: bits only, no CommEvent stream
    scan_rounds: bool = True           # whole-run lax.scan executor
    chunk_rounds: int = 32             # scanned mode: rounds staged per chunk
    seed: int = 0
    schedule: Schedule | None = None
    obs: Any = None                    # repro.obs.RunTelemetry; None = the
                                       # byte-for-byte untapped fast path
    mesh: Any = None                   # jax Mesh ("clusters", "clients"):
                                       # shard clusters over "clusters" and
                                       # in-cluster clients over "clients"
                                       # (repro.sharding.fed, bit-identical);
                                       # None adopts an ambient federation
                                       # mesh or stays single-device


def _participation_arrays(task: FLTask, parts_t, M: int, n_max: int):
    """One round's participation-renormalized (gammas, mask, sizes) rows —
    the ONE implementation both the looped and scanned paths build their
    masked (M, n_max) slots from (scanned==looped bit-parity depends on it).
    Gamma rows renormalize over each cluster's reporters; a fully-dropped
    cluster keeps an all-zero row (its ES is a pass-through)."""
    pmask = np.zeros((M, n_max), np.float32)
    gnp = np.zeros((M, n_max), np.float32)
    sizes = np.zeros(M, np.float32)
    for m, members in enumerate(task.cluster_members):
        row = participation_mask(members, parts_t[m])
        pmask[m, : len(members)] = row
        w = task.cluster_weights(m) * row
        if w.sum() > 0:
            gnp[m, : len(members)] = w / w.sum()
        sizes[m] = sum(task.client_sizes[i] for i in parts_t[m])
    return gnp, pmask, sizes


def run_hier_local_qsgd(task: FLTask, config: HierLocalQSGDConfig) -> RunResult:
    if config.scan_rounds:
        return _run_hier_scanned(task, config)
    task.reset_loaders(config.seed)
    assert config.local_steps % config.local_epochs == 0, "K must divide by E"
    K, E = config.local_steps, config.local_epochs
    interactions = K // E
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
    lrs = np.asarray([sched_fn(k) for k in range(K)], dtype=np.float32)
    lrs_grouped = jnp.asarray(lrs.reshape(interactions, E))

    params = task.init_params()
    d = task.num_params()
    ledger = CommLedger(track_events=config.track_events)
    channel = resolve_channel(config.precision, config.channel,
                              config.qsgd_levels, config.bits_per_param)
    es_channel = config.es_channel if config.es_channel is not None else channel
    engine = RoundEngine(task.model, channel, es_channel, local_opt=config.local_opt,
                         client_microbatch=config.client_microbatch,
                         precision=config.precision)
    key = jax.random.PRNGKey(config.seed + 1)

    down_bits = DenseChannel(
        downlink_bits_per_param(config.precision, config.bits_per_param)
    ).message_bits(d)
    up_bits = channel_wire_bits(channel, d, task.param_leaf_sizes())
    es_up_bits = channel_wire_bits(es_channel, d, task.param_leaf_sizes())

    M = task.num_clusters
    gammas, mask = task.padded_cluster_weights()
    es_weights = jnp.asarray(
        np.array(task.cluster_sizes, dtype=np.float32) / sum(task.cluster_sizes)
    )

    n_max = mask.shape[1]
    full_part = is_full_participation(config.sampler)
    opt_state = engine.init_opt_state(params, M, n_max)  # client-held, cross-round
    obs = config.obs
    taps = obs is not None and obs.taps
    recorder = RunRecorder(task, config.rounds, config.eval_every, obs=obs)
    losses = jnp.full((1, 1), jnp.nan)  # stays nan until a first trained round
    for t in range(config.rounds):
        if full_part:
            parts = list(task.cluster_members)
            gammas_t, mask_t, es_weights_t = gammas, mask, es_weights
            any_participants = True
        else:
            # per-cluster participant sets -> masked (M, n_max) slots; gamma
            # rows renormalize over each cluster's reporters, ES weights over
            # the clusters that trained at all.  A fully-dropped cluster's ES
            # is a pass-through: zero delta, zero weight, no ES->PS upload.
            parts = [config.sampler.participants(t, members)
                     for members in task.cluster_members]
            gnp, pmask, sizes = _participation_arrays(task, parts, M, n_max)
            any_participants = sizes.sum() > 0
            if any_participants:
                gammas_t = jnp.asarray(gnp)
                mask_t = jnp.asarray(pmask)
                es_weights_t = jnp.asarray(sizes / sizes.sum())

        if any_participants:
            batch = task.sample_all_cluster_batches(K, E)  # (J, M, n_max, E, B, ...)
            subs = es_subs = None
            if channel.stochastic:
                key, flat = split_chain(key, interactions * M)
                subs = flat.reshape(interactions, M, 2)
            if es_channel.stochastic:
                key, es_subs = split_chain(key, M)
            with maybe_span(obs, "round"):
                out = engine.multi_cluster_round(
                    params, batch, gammas_t, mask_t, es_weights_t, lrs_grouped,
                    subs, es_subs, opt_state, taps=taps,
                )
                params, opt_state, losses, tele = out if taps else (*out, None)
            if tele is not None:
                obs.record_round(t, tele)
            if not full_part:
                # report loss over the clusters that actually trained (empty
                # clusters read 0 from the engine's guarded average)
                losses = losses[:, sizes > 0]

            if ledger.track_events:
                for j in range(interactions):
                    for m in range(M):
                        es = f"es:{m}"
                        for i in parts[m]:
                            ledger.record("es_to_client", down_bits, round=t, phase=j,
                                          sender=es, receiver=f"client:{i}")
                            ledger.record("client_to_es", up_bits, round=t, phase=j,
                                          sender=f"client:{i}", receiver=es)
                for m in range(M):
                    if parts[m]:  # pass-through ESs upload nothing
                        ledger.record("es_to_ps", es_up_bits, round=t,
                                      phase=interactions,
                                      sender=f"es:{m}", receiver="ps")
                    # every ES still receives the broadcast (stays in sync)
                    ledger.record("ps_to_es", down_bits, round=t,
                                  phase=interactions + 1,
                                  sender="ps", receiver=f"es:{m}")
            else:
                n_part = sum(len(p) for p in parts)
                ledger.record("es_to_client", down_bits, interactions * n_part)
                ledger.record("client_to_es", up_bits, interactions * n_part)
                ledger.record("es_to_ps", es_up_bits, sum(1 for p in parts if p))
                ledger.record("ps_to_es", down_bits, M)
        # else: nobody anywhere this round — zero traffic, params unchanged
        engine.end_round(ledger, t)
        recorder.record(t, params, losses)

    return recorder.result("hier_local_qsgd", ledger, params)


# --------------------------------------------------------------------------
# scanned whole-run path: per-round (gammas, mask, ES weights) and the
# uplink/ES subkey chains are precomputed, batches staged a chunk of global
# rounds at a time, every chunk one lax.scan; all-dark rounds are skipped by
# the scan and the ledger is reconstructed afterwards.  Bit-identical to the
# looped path at fixed seed — the looped driver already runs the padded/
# masked multi-cluster round, so the scan body is the very same computation.
# --------------------------------------------------------------------------


def _hier_scan_plan(task: FLTask, source, config: HierLocalQSGDConfig):
    """Whole-run `ScanPlan` + deferred glue.  Returns (plan, params_of,
    traffic, sel_of) — `sel_of(t)` is the boolean cluster selector the
    looped driver applies to round t's (J, M) loss grid before logging
    (None under full participation)."""
    source.reset(config.seed)
    assert config.local_steps % config.local_epochs == 0, "K must divide by E"
    K, E = config.local_steps, config.local_epochs
    interactions = K // E
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
    lrs = np.asarray([sched_fn(k) for k in range(K)], dtype=np.float32)

    params = task.init_params()
    d = task.num_params()
    channel = resolve_channel(config.precision, config.channel,
                              config.qsgd_levels, config.bits_per_param)
    es_channel = config.es_channel if config.es_channel is not None else channel
    engine = RoundEngine(task.model, channel, es_channel, local_opt=config.local_opt,
                         client_microbatch=config.client_microbatch,
                         precision=config.precision)

    M = task.num_clusters
    gammas_full, mask_full = task.padded_cluster_weights()
    n_max = mask_full.shape[1]
    es_weights_full = np.asarray(
        np.array(task.cluster_sizes, dtype=np.float32) / sum(task.cluster_sizes)
    )
    full_part = is_full_participation(config.sampler)

    R = config.rounds
    members_of = task.cluster_members
    parts = [
        [list(m) for m in members_of] if full_part
        else [config.sampler.participants(t, m) for m in members_of]
        for t in range(R)
    ]

    gammas_r = np.zeros((R, M, n_max), np.float32)
    mask_r = np.zeros((R, M, n_max), np.float32)
    esw_r = np.zeros((R, M), np.float32)
    sizes_r = np.zeros((R, M), np.float32)
    trained = np.zeros(R, bool)
    for t in range(R):
        if full_part:
            gammas_r[t] = np.asarray(gammas_full)
            mask_r[t] = np.asarray(mask_full)
            esw_r[t] = es_weights_full
            sizes_r[t] = 1.0  # unused under full participation
            trained[t] = True
        else:
            gammas_r[t], mask_r[t], sizes_r[t] = _participation_arrays(
                task, parts[t], M, n_max)
            trained[t] = sizes_r[t].sum() > 0
            if trained[t]:
                esw_r[t] = sizes_r[t] / sizes_r[t].sum()

    # subkeys: per trained round, the looped driver splits J*M uplink keys
    # then M ES keys (each only when that channel is stochastic) — one fused
    # chain reproduces the interleaving draw-for-draw
    subs_r = np.zeros((R, interactions, M, 2), np.uint32)
    es_subs_r = np.zeros((R, M, 2), np.uint32)
    if channel.stochastic or es_channel.stochastic:
        key = jax.random.PRNGKey(config.seed + 1)
        per_round = (interactions * M if channel.stochastic else 0) + (
            M if es_channel.stochastic else 0
        )
        n_tr = int(trained.sum())
        if n_tr and per_round:
            _, flat = split_chain(key, n_tr * per_round)
            flat = np.asarray(flat).reshape(n_tr, per_round, 2)
            ofs = 0
            if channel.stochastic:
                subs_r[trained] = flat[:, : interactions * M].reshape(
                    n_tr, interactions, M, 2)
                ofs = interactions * M
            if es_channel.stochastic:
                es_subs_r[trained] = flat[:, ofs : ofs + M]

    def stage(idxs):
        C = len(idxs)
        cs = list(range(C))  # every trained round stages every cluster
        batch = stage_chunk(
            source,
            [(client, K * C,
              scatter_put((cs, slice(None), m, slot),
                          lambda dl: dl.reshape(C, interactions, E, *dl.shape[1:])))
             for m, members in enumerate(members_of)
             for slot, client in enumerate(members)],
            lambda a: (C, interactions, M, n_max, E) + a.shape[1:],
        )
        for m, members in enumerate(members_of):
            if len(members) < n_max:  # padded slots replicate member 0
                jax.tree.map(
                    lambda bl: bl.__setitem__(
                        (cs, slice(None), m, slice(len(members), None)),
                        bl[cs, :, m, 0:1],
                    ),
                    batch,
                )
        return {
            "batch": batch,
            "gammas": gammas_r[idxs],
            "mask": mask_r[idxs],
            "es_weights": esw_r[idxs],
            "subs": subs_r[idxs],
            "es_subs": es_subs_r[idxs],
        }

    taps = config.obs is not None and config.obs.taps
    plan = ScanPlan(
        body=scan_multi_body(engine.model, channel, es_channel, engine.local_opt,
                             taps, config.client_microbatch, config.precision),
        carry=(params, engine.init_opt_state(params, M, n_max)),
        consts={"lrs": jnp.asarray(lrs.reshape(interactions, E))},
        stage=stage,
        trained=trained,
        rounds=R,
        eval_every=config.eval_every,
        chunk_rounds=config.chunk_rounds,
        obs=config.obs,
    )

    mesh = resolve_mesh(config.mesh)
    if mesh is not None:
        assert config.client_microbatch is None, \
            "client_microbatch and a federation mesh are mutually exclusive"
        plan = shard_plan(plan, mesh, "multi", model=engine.model,
                          channel=channel, es_channel=es_channel,
                          opt=engine.local_opt, clusters=M, clients=n_max)

    down_bits = DenseChannel(
        downlink_bits_per_param(config.precision, config.bits_per_param)
    ).message_bits(d)
    up_bits = channel_wire_bits(channel, d, task.param_leaf_sizes())
    es_up_bits = channel_wire_bits(es_channel, d, task.param_leaf_sizes())

    def traffic(track_events: bool):
        for t in range(R):
            entries = []
            if trained[t]:
                if track_events:
                    for j in range(interactions):
                        for m in range(M):
                            es = f"es:{m}"
                            for i in parts[t][m]:
                                entries.append(("es_to_client", down_bits, 1, j,
                                                es, f"client:{i}"))
                                entries.append(("client_to_es", up_bits, 1, j,
                                                f"client:{i}", es))
                    for m in range(M):
                        if parts[t][m]:  # pass-through ESs upload nothing
                            entries.append(("es_to_ps", es_up_bits, 1, interactions,
                                            f"es:{m}", "ps"))
                        # every ES still receives the broadcast (stays in sync)
                        entries.append(("ps_to_es", down_bits, 1, interactions + 1,
                                        "ps", f"es:{m}"))
                else:
                    n_part = sum(len(p) for p in parts[t])
                    entries.append(("es_to_client", down_bits,
                                    interactions * n_part, 0, None, None))
                    entries.append(("client_to_es", up_bits,
                                    interactions * n_part, 0, None, None))
                    entries.append(("es_to_ps", es_up_bits,
                                    sum(1 for p in parts[t] if p), 0, None, None))
                    entries.append(("ps_to_es", down_bits, M, 0, None, None))
            yield t, entries

    def sel_of(t: int):
        return None if full_part else sizes_r[t] > 0

    return plan, (lambda c: c[0]), traffic, sel_of


def _run_hier_scanned(task: FLTask, config: HierLocalQSGDConfig) -> RunResult:
    obs = config.obs
    with maybe_span(obs, "call"):
        with maybe_span(obs, "precompute"):
            plan, params_of, traffic, sel_of = _hier_scan_plan(task, task.source, config)
        recorder = RunRecorder(task, config.rounds, config.eval_every, obs=obs)

        def record(t, carry, losses, last_t):
            if losses is not None:
                sel = sel_of(last_t)
                if sel is not None:
                    # the looped driver logs the mean over the clusters that
                    # actually trained in the last trained round
                    losses = losses[:, sel]
            recorder.record(t, params_of(carry), losses)

        carry = run_scan(plan, record)
        ledger = CommLedger(track_events=config.track_events)
        with maybe_span(obs, "materialize"):
            ledger.materialize(traffic(config.track_events))
        return recorder.result("hier_local_qsgd", ledger, params_of(carry))

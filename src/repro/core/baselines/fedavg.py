"""FedAvg (McMahan et al., 2017) baseline, with optional compressed uplinks
(the paper's Fig. 2 "FedAvg compressed by QSGD" arm), driven by the shared
round engine.

Per round: every client runs K local optimizer steps from the PS model,
uploads the channel-compressed model delta to the PS (multi-hop in a real
deployment; the ledger records the client<->PS hop type so Fig. 2's
structural comparison is visible), and the PS takes the D_n/D_A-weighted
average.  A FedAvg round is one engine interaction with E=K: the whole round
is a single fused jit call.  Client-held `LocalOpt` state persists across
rounds without ever traversing the channel.

Participation (repro.part): `FedAvgConfig.sampler` picks the reporting
subset each round — dropped clients send nothing (zero uplink bits), keep
their opt state frozen, and the D_n weights renormalize over the reporters.
A round with zero reporters is skipped outright.  The default
`FullParticipation`/None path is bit-identical to the pre-participation
stack.

Whole-run execution: with `scan_rounds=True` (the default) the run executes
through `engine.run_scan` — per-round masks/gammas and PRNG subkeys are
precomputed, batches staged `chunk_rounds` rounds at a time, and every chunk
is one `lax.scan` over rounds; zero-reporter rounds are skipped by the scan
itself and the ledger is reconstructed after the run
(`CommLedger.materialize`).  Bit-identical to the looped path at fixed seed
(tests/test_engine_parity.py).
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
    scan_delta_body,
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
from repro.part import (
    Sampler,
    is_full_participation,
    participation_mask,
    schedule_participants,
    stack_masks,
)
from repro.sharding.fed import resolve_mesh, shard_plan


@dataclasses.dataclass
class FedAvgConfig:
    rounds: int = 200
    local_steps: int = 20          # paper B.1: "training epochs in clients ... K=20"
    eval_every: int = 10
    bits_per_param: int = 32
    qsgd_levels: int | None = None
    channel: Channel | None = None  # explicit uplink channel
    local_opt: LocalOpt | None = None  # client-held optimizer (None = plain SGD)
    client_microbatch: int | None = None  # at most this many client replicas
                                          # train at once (None = full vmap)
    precision: Precision | None = None    # mixed-precision policy: bf16
                                          # client compute, f32 PS master,
                                          # wire-dtype dense messages
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
                                       # shard the scanned client axis
                                       # (repro.sharding.fed, bit-identical);
                                       # None adopts an ambient federation
                                       # mesh or stays single-device


def run_fedavg(task: FLTask, config: FedAvgConfig) -> RunResult:
    if config.scan_rounds:
        return _run_fedavg_scanned(task, config)
    task.reset_loaders(config.seed)
    K = config.local_steps
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
    lrs = jnp.asarray([[sched_fn(k) for k in range(K)]], dtype=jnp.float32)  # (1, K)

    params = task.init_params()
    d = task.num_params()
    ledger = CommLedger(track_events=config.track_events)
    channel = resolve_channel(config.precision, config.channel,
                              config.qsgd_levels, config.bits_per_param)
    engine = RoundEngine(task.model, channel, local_opt=config.local_opt,
                         client_microbatch=config.client_microbatch,
                         precision=config.precision)
    gammas = jnp.asarray(task.global_weights())
    key = jax.random.PRNGKey(config.seed + 1)

    down_bits = DenseChannel(
        downlink_bits_per_param(config.precision, config.bits_per_param)
    ).message_bits(d)
    up_bits = channel_wire_bits(channel, d, task.param_leaf_sizes())

    obs = config.obs
    taps = obs is not None and obs.taps
    recorder = RunRecorder(task, config.rounds, config.eval_every, obs=obs)
    n = task.num_clients
    full_part = is_full_participation(config.sampler)
    all_clients = list(range(n))
    opt_state = engine.init_opt_state(params, n)  # client-held, cross-round
    losses = jnp.full((1,), jnp.nan)  # stays nan until a first trained round
    for t in range(config.rounds):
        participating = (
            all_clients if full_part else config.sampler.participants(t, all_clients)
        )
        if participating:
            # all clients stage K batches (full width even under churn, so the
            # data schedule is participation-independent); one E=K interaction
            per_client = [task.sample_client_batches(i, K) for i in range(n)]
            batch = jax.tree.map(lambda *leaves: jnp.stack(leaves)[None], *per_client)
            subs = None
            if channel.stochastic:
                key, subs = split_chain(key, 1)
            if full_part:
                with maybe_span(obs, "round"):
                    out = engine.cluster_round(
                        params, batch, gammas, lrs, subs, opt_state, taps=taps
                    )
                    params, opt_state, losses, tele = out if taps else (*out, None)
            else:
                # masked round: D_n weights renormalized over the participants,
                # dropped clients contribute zero delta + frozen opt state
                pmask = participation_mask(all_clients, participating)
                w = task.global_weights() * pmask
                gammas_r = jnp.asarray((w / w.sum()).astype(np.float32))
                with maybe_span(obs, "round"):
                    out = engine.cluster_round(
                        params, batch, gammas_r, lrs, subs, opt_state, mask=pmask,
                        taps=taps,
                    )
                    params, opt_state, losses, tele = out if taps else (*out, None)
            if tele is not None:
                obs.record_round(t, tele)

            if ledger.track_events:
                for i in participating:
                    ledger.record("ps_to_client", down_bits, round=t, phase=0,
                                  sender="ps", receiver=f"client:{i}")
                    ledger.record("client_to_ps", up_bits, round=t, phase=0,
                                  sender=f"client:{i}", receiver="ps")
            else:
                ledger.record("ps_to_client", down_bits, len(participating))
                ledger.record("client_to_ps", up_bits, len(participating))
        # else: nobody reported — the PS round is skipped outright (zero
        # traffic, params unchanged)
        engine.end_round(ledger, t)
        recorder.record(t, params, losses)

    return recorder.result("fedavg", ledger, params)


# --------------------------------------------------------------------------
# scanned whole-run path
# --------------------------------------------------------------------------


def _fedavg_scan_plan(task: FLTask, source, config: FedAvgConfig):
    """Whole-run `ScanPlan` + deferred glue (see `fed_chs._fed_chs_scan_plan`)."""
    source.reset(config.seed)
    K = config.local_steps
    sched_fn = config.schedule or paper_sqrt_schedule(K, half=False)
    lrs = np.asarray([[sched_fn(k) for k in range(K)]], dtype=np.float32)  # (1, K)

    params = task.init_params()
    d = task.num_params()
    channel = resolve_channel(config.precision, config.channel,
                              config.qsgd_levels, config.bits_per_param)
    engine = RoundEngine(task.model, channel, local_opt=config.local_opt,
                         client_microbatch=config.client_microbatch,
                         precision=config.precision)

    R = config.rounds
    n = task.num_clients
    full_part = is_full_participation(config.sampler)
    all_clients = list(range(n))
    parts = schedule_participants(config.sampler, R, all_clients)
    trained = np.array([len(p) > 0 for p in parts])

    mask_r = stack_masks(all_clients, parts)
    gammas_r = np.zeros((R, n), np.float32)
    gw = task.global_weights()
    for t in np.flatnonzero(trained):
        if full_part:
            gammas_r[t] = gw
        else:
            w = gw * mask_r[t]
            gammas_r[t] = (w / w.sum()).astype(np.float32)

    subs_r = np.zeros((R, 1, 2), np.uint32)
    if channel.stochastic:
        n_tr = int(trained.sum())
        if n_tr:
            _, flat = split_chain(jax.random.PRNGKey(config.seed + 1), n_tr)
            subs_r[trained] = np.asarray(flat).reshape(n_tr, 1, 2)

    def stage(idxs):
        C = len(idxs)
        cs = list(range(C))  # every trained round stages every client
        batch = stage_chunk(
            source,
            [(i, K * C,
              scatter_put((cs, 0, i), lambda dl: dl.reshape(C, K, *dl.shape[1:])))
             for i in range(n)],
            lambda a: (C, 1, n, K) + a.shape[1:],
        )
        return {
            "batch": batch,
            "gammas": gammas_r[idxs],
            "mask": mask_r[idxs],
            "subs": subs_r[idxs],
        }

    taps = config.obs is not None and config.obs.taps
    body = scan_delta_body(engine.model, channel, engine.local_opt, taps,
                           config.client_microbatch, config.precision)
    plan = ScanPlan(
        body=body,
        carry=(params, engine.init_opt_state(params, n)),
        consts={"lrs": jnp.asarray(lrs)},
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
        plan = shard_plan(plan, mesh, "delta", model=engine.model,
                          channel=channel, opt=engine.local_opt, clients=n)

    down_bits = DenseChannel(
        downlink_bits_per_param(config.precision, config.bits_per_param)
    ).message_bits(d)
    up_bits = channel_wire_bits(channel, d, task.param_leaf_sizes())

    def traffic(track_events: bool):
        for t in range(R):
            entries = []
            p = parts[t]
            if p:
                if track_events:
                    for i in p:
                        entries.append(("ps_to_client", down_bits, 1, 0,
                                        "ps", f"client:{i}"))
                        entries.append(("client_to_ps", up_bits, 1, 0,
                                        f"client:{i}", "ps"))
                else:
                    entries.append(("ps_to_client", down_bits, len(p), 0, None, None))
                    entries.append(("client_to_ps", up_bits, len(p), 0, None, None))
            yield t, entries

    return plan, (lambda c: c[0]), traffic


def _run_fedavg_scanned(task: FLTask, config: FedAvgConfig) -> RunResult:
    obs = config.obs
    with maybe_span(obs, "call"):
        with maybe_span(obs, "precompute"):
            plan, params_of, traffic = _fedavg_scan_plan(task, task.source, config)
        recorder = RunRecorder(task, config.rounds, config.eval_every, obs=obs)
        carry = run_scan(
            plan, lambda t, c, losses, _lt: recorder.record(t, params_of(c), losses)
        )
        ledger = CommLedger(track_events=config.track_events)
        with maybe_span(obs, "materialize"):
            ledger.materialize(traffic(config.track_events))
        return recorder.result("fedavg", ledger, params_of(carry))

"""Unified jitted cluster-round engine shared by all four FL algorithms.

Layering — the FedTask stack
----------------------------
The simulation stack is generic over one task abstraction: a `FedModel`
(params init + batch-pytree loss + eval metric), a `DataSource` (per-client
batch staging + held-out eval data), and a `LocalOpt` (client-held local
optimizer state).  An MLP classifier and a 100M-param transformer LM run
through the *same* layers:

  driver   (fed_chs.py, baselines/*.py)
      Owns the *protocol*: which cluster trains when, scheduler hops,
      ledger entries, evaluation cadence.  Pure host-side Python, one
      engine call per round, no per-interaction device syncs.  Drivers
      never look inside a batch — batches are opaque pytrees staged by the
      task's `DataSource`.

  engine   (this module)
      Owns the *round*: the E-local-steps x K/E-interactions inner loop —
      local optimizer steps (`core/oracles.py`), delta computation, channel
      compression, gamma-weighted aggregation — fused into a single
      jit-compiled `lax.scan` (with a `vmap` over clusters for 3-tier HFL).
      Batches for the whole round are staged up front
      (`FLTask.sample_round_batches`), so the only host<->device traffic
      per round is one params handle, the client-held optimizer states, and
      one stacked loss array.

  channel  (repro/comm/channels.py)
      Owns the *message*: the in-graph lossy transform (dense / QSGD /
      Top-K) and its `message_bits` accounting.  Compiled into the scan
      body, so adding a channel never touches a driver or the engine.
      Uplinks carry model deltas only — `LocalOpt` state (momentum, Adam
      moments) stays on the client and never traverses a channel.

A fourth, passive layer rides on the drivers' ledger entries:
`repro.netsim` replays the recorded per-message `CommEvent` stream through
link/compute models to price a run in wall-clock seconds — the paper's
§3.2 overhead model counts only bits, which is exactly what the event
metadata extends without changing (aggregate accounting is bit-identical).
`end_round` below is the uniform per-round bookkeeping hook every driver
calls once per round.

Round modes
-----------
* `grad_round`  — Eq. (5) literal: every in-cluster iteration uploads a
  gradient and the ES applies the gamma-weighted step (E=1, dense, plain
  SGD by definition).
* `cluster_round` — delta mode: clients run E local optimizer steps, upload
  channel-compressed model deltas, ES aggregates; scan over K/E
  interactions.  Per-client optimizer state enters and leaves the round as
  a stacked pytree (leading client axis) the driver holds between rounds.
* `multi_cluster_round` — the Hier-Local-QSGD round: the delta-mode
  interaction vmapped over all M clusters at once (ragged cluster sizes
  handled by padding + masking: padded client slots carry zero gamma
  weight and their deltas are masked to zero before compression), plus the
  ES->PS compress/aggregate/broadcast step, all inside one jit.

Memory & precision
------------------
Two orthogonal execution knobs on `RoundEngine` rescale the same round
computation from MLP toys to 0.6B-param LM clients on one host:

* `client_microbatch` — the delta/grad rounds above historically vmapped the
  E local steps over ALL n clients of the active cluster: n model replicas
  (plus n activation sets under AD) live simultaneously.  With
  `client_microbatch=mb` the engine scans over ceil(n/mb) client groups and
  accumulates the gamma-weighted aggregate in place
  (`_microbatched_cluster_step`), so peak memory is O(mb) replicas + the one
  master copy.  Grad mode stays BIT-identical (the per-step gradient stack
  feeds the unchanged einsum — `oracles.grad_phase`); delta modes match the
  vmapped aggregate to ≤1 ulp per interaction (exact at mb >= n) because
  only the reduction ORDER changes.
* `precision` — a `core.precision.Precision` policy: clients compute
  (forward/backward, local opt steps, raw deltas) in `precision.compute`
  (bf16 halves replica + activation bytes); the authoritative params the ES
  holds — the whole-run scan carry — and the delta accumulator stay in
  `precision.master`; dense wires travel at `precision.wire` width via
  `DenseChannel(wire_dtype=...)`, which the ledger prices exactly.  Casts
  are tagged ("precision_cast" / "master_accumulate") for
  roofline.attribution.  Grad mode — the paper-literal Eq. (5) arm —
  ignores the policy.

Both default to None, which traces the exact pre-knob graphs byte-for-byte
(same functools.cache entries, no inserted ops) — the default-path parity
contract in tests/test_engine_parity.py.  `scan_chunk_fn` additionally
donates the staged per-chunk xs on donation-capable backends, so a chunked
LM run's live set is master state + one chunk of batches + one microbatch
of activations.

Participation
-------------
Per-round participation (repro.part) flows into the rounds as masks riding
the same padded slots the vmapped HFL round already used: a dropped client's
slot carries zero gamma weight, its delta is zeroed before compression, its
loss is excluded from the average, and its `LocalOpt` state is frozen in
place (`_freeze_masked`).  `cluster_round(mask=...)` routes to a separate
compiled function so the default no-mask path stays byte-for-byte the
pre-participation computation; `multi_cluster_round`'s existing mask now
encodes padding AND dropouts, and a fully-dropped cluster degrades to a
zero-delta pass-through (its ES forwards the broadcast model unchanged).

Determinism
-----------
`split_chain(key, n)` reproduces n sequential `key, sub = split(key)`
draws as one fused scan, bit-identical to the eager chains the pre-engine
drivers used — so fixed-seed trajectories are preserved across the
refactor (see tests/test_engine_parity.py).  The default `PlainSGD` path
carries an empty opt-state pytree through the same scans the pre-FedTask
engine ran, so classifier trajectories are unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.channels import Channel, DenseChannel
from repro.core.ledger import CommLedger
from repro.core.oracles import grad_phase, local_opt_steps
from repro.core.precision import Precision, cast_floats, compute_cast, master_cast
from repro.models.fed import FedModel, as_fed_model
from repro.obs.taps import delta_taps, grad_taps, tree_client_norms
from repro.obs.trace import maybe_span
from repro.optim.local import LocalOpt, PlainSGD
from repro.utils import tree_add, tree_sub

PyTree = Any
Batch = Any  # pytree of arrays sharing the documented leading axes

_log = logging.getLogger(__name__)


def _jit_round(fn):
    """jit with donated params where the backend supports buffer donation
    (CPU does not; donating there only emits warnings)."""
    if jax.default_backend() in ("tpu", "gpu"):
        return jax.jit(fn, donate_argnums=(0,))
    return jax.jit(fn)


# --------------------------------------------------------------------------
# PRNG plumbing
# --------------------------------------------------------------------------


@functools.cache
def _split_chain_fn(n: int):
    def chain(key):
        def step(k, _):
            k2, sub = jax.random.split(k)
            return k2, sub

        return jax.lax.scan(step, key, None, length=n)

    return jax.jit(chain)


def split_chain(key: jax.Array, n: int) -> tuple[jax.Array, jax.Array]:
    """n sequential `key, sub = jax.random.split(key)` draws fused into one
    jitted scan. Returns (advanced key, subs (n, 2))."""
    if n == 0:
        return key, jnp.zeros((0, 2), jnp.uint32)
    return _split_chain_fn(n)(key)


def dummy_subs(*lead: int) -> jnp.ndarray:
    """Placeholder key array for non-stochastic channels (never consumed)."""
    return jnp.zeros(tuple(lead) + (2,), jnp.uint32)


# --------------------------------------------------------------------------
# compiled round functions, cached per (model, channel, opt) — shapes are
# handled by jit's own shape-keyed cache
# --------------------------------------------------------------------------


def compress_uplinks(channel: Channel, deltas: PyTree, sub: jax.Array,
                     slots: jax.Array | None = None) -> PyTree:
    """Compress a stacked uplink (leading sender axis on every leaf).

    `per_message` channels (every lossy channel: QSGD/sign-SGD encode each
    sender's message against its own per-leaf blocks; Top-K selection couples
    entries within one message) are vmapped over the sender axis with
    per-sender `fold_in(sub, slot)` keys.  fold_in — not `random.split` — is
    load-bearing: split(sub, n) changes *every* subkey when n changes, while
    fold_in keys slot i independently of how many slots the stacked uplink
    carries, so a run padded to n_max senders (the whole-run scan path) hands
    each real sender the exact key the unpadded looped path would.  Padded
    slots carry zero deltas, which every wire channel encodes to zero norms
    and decodes to exact zeros.  Dense transforms the stack directly.

    `slots` overrides the per-sender key indices: the microbatched client
    path compresses one GROUP of the stacked uplink at a time and passes the
    group's global slot ids, so client i's message is keyed identically
    whether its group holds 1, 2, or all n senders."""
    if getattr(channel, "per_message", False):
        if slots is None:
            n = jax.tree.leaves(deltas)[0].shape[0]
            slots = jnp.arange(n)
        return jax.vmap(
            lambda d, i: channel.compress(d, jax.random.fold_in(sub, i))
        )(deltas, slots)
    return channel.compress(deltas, sub)


@functools.cache
def _grad_round_fn(model: FedModel, taps: bool = False,
                   microbatch: int | None = None):
    """Eq. (5) literal (see `oracles.grad_phase`): batch leaves (K, n, B, ...),
    gammas (n,), lrs (K,). Returns (params, per-step gamma-weighted losses).
    With `taps`, additionally returns the grad-mode tele dict (obs/taps.py).
    Telemetry variants are SEPARATE cache entries: the taps=False graph is
    the exact pre-telemetry round, so the obs=None fast path costs nothing.
    `microbatch` bounds concurrent client forward/backward passes
    (`oracles.grad_phase`: exact at microbatch >= n, ≤1 ulp below it); grad
    mode is the paper-literal f32 path, so there is no precision knob here."""
    phase = grad_phase(model, microbatch)

    def round_fn(params, batch, gammas, lrs):
        with jax.named_scope("local_train"):
            new_params, losses = phase(params, batch, gammas, lrs)
        if taps:
            return new_params, losses, grad_taps(params, new_params, gammas)
        return new_params, losses

    return _jit_round(round_fn)


def _scan_and_tap_last(interaction, carry, xs, taps):
    """Scan `interaction` over a round's interactions; with `taps`, peel the
    FINAL interaction out of the scan and run it with `tap=True`, so the tap
    reductions trace exactly once per round and the tele dict is a
    final-interaction snapshot.  Alternatives measured worse on XLA:CPU
    inside the whole-run scan: a `lax.cond` on "is this the last
    interaction" copies its n×d operands through the conditional every
    interaction, and unconditional per-interaction taps re-run the
    reductions J times at memory speed.  The untapped path is the plain
    full-length scan — byte-for-byte the pre-telemetry graph.
    Returns (carry..., losses (J,)[, tele])."""
    if not taps:
        (a, b), losses = jax.lax.scan(interaction, carry, xs)
        return a, b, losses
    head = jax.tree.map(lambda x: x[:-1], xs)
    last = jax.tree.map(lambda x: x[-1], xs)
    carry, head_losses = jax.lax.scan(interaction, carry, head)
    (a, b), (last_loss, tele) = interaction(carry, last, tap=True)
    losses = jnp.concatenate([head_losses, last_loss[None]])
    return a, b, losses, tele


@functools.cache
def _delta_round_fn(model: FedModel, channel: Channel, opt: LocalOpt,
                    taps: bool = False, microbatch: int | None = None,
                    precision: Precision | None = None):
    """Delta mode: scan over J = K/E interactions; each interaction runs E
    local optimizer steps per client (vmapped), pushes channel-compressed
    deltas, and applies the gamma-weighted aggregate.
    batch leaves: (J, n, E, B, ...), opt_state leaves: (n, ...), lrs: (J, E),
    subs: (J, 2).
    Returns (params, opt_state, per-interaction mean losses (J,)); with
    `taps` also the per-round tele dict (a final-interaction snapshot — see
    `_scan_and_tap_last`).  The round phases are `jax.named_scope`-tagged
    (metadata only — numerics are untouched).

    `microbatch` routes the interaction through `_microbatched_cluster_step`
    (peak params/activations O(microbatch) instead of O(n) model copies;
    ≤1-ulp vs the vmapped aggregate — see the helper's docstring).
    `precision` is the mixed-precision policy (core/precision.py): compute
    runs in `precision.compute`, the carry params/aggregation stay in the
    master dtype.  Both default to None, which traces the exact
    pre-mixed-precision vmapped graph byte-for-byte."""
    if microbatch is not None:
        assert not taps, "telemetry taps are unsupported with client_microbatch"
        step = _microbatched_cluster_step(
            local_opt_steps(model, opt), channel, int(microbatch), precision)

        def mb_round_fn(params, opt_state, batch, gammas, lrs, subs):
            ones = jnp.ones_like(gammas)

            def interaction(carry, inp):
                p, s = carry
                b, lr, sub = inp
                new_p, new_s, losses = step(p, s, b, gammas, ones, lr, sub)
                return (new_p, new_s), jnp.mean(losses)

            (p, s), losses = jax.lax.scan(interaction, (params, opt_state),
                                          (batch, lrs, subs))
            return p, s, losses

        return _jit_round(mb_round_fn)

    multi_local = jax.vmap(local_opt_steps(model, opt), in_axes=(None, 0, 0, None))

    def round_fn(params, opt_state, batch, gammas, lrs, subs):
        def interaction(carry, inp, tap=False):
            p, s = carry
            b, lr, sub = inp
            p_c = compute_cast(p, precision)
            with jax.named_scope("local_train"):
                new_p, new_s, losses = multi_local(
                    p_c, s, compute_cast(b, precision), compute_cast(lr, precision))
            with jax.named_scope("uplink"):
                raw = jax.tree.map(lambda a, base: a - base[None], new_p, p_c)
                deltas = compress_uplinks(channel, raw, sub)
            deltas = master_cast(deltas, precision)
            with jax.named_scope("intra_agg"):
                agg = jax.tree.map(
                    lambda dl: jnp.einsum("n,n...->...", gammas.astype(dl.dtype), dl),
                    deltas)
                new_params = tree_add(p, agg)
            loss = jnp.mean(losses)
            out = (loss, delta_taps(raw, tree_sub(new_params, p),
                                    gammas)) if tap else loss
            return (new_params, new_s), out

        return _scan_and_tap_last(interaction, (params, opt_state),
                                  (batch, lrs, subs), taps)

    return _jit_round(round_fn)


def _freeze_masked(mask: jax.Array, new_state: PyTree, old_state: PyTree) -> PyTree:
    """Keep masked-out clients' opt state frozen in place: slots with
    mask == 0 leave the round carrying exactly the state they entered with
    (element-wise select, so kept slots are bit-identical to the unmasked
    update)."""
    return jax.tree.map(
        lambda ns, os: jnp.where(mask.reshape((-1,) + (1,) * (ns.ndim - 1)) > 0, ns, os),
        new_state,
        old_state,
    )


def _microbatched_cluster_step(local_fn, channel: Channel, mb: int,
                               precision: Precision | None):
    """One cluster interaction with at most `mb` concurrent client replicas.

    The memory-lean core of `client_microbatch`: instead of vmapping the E
    local steps over all n clients (n model copies + n activation sets live
    at once), clients are processed in ceil(n/mb) groups of `mb` by a
    `lax.scan` that accumulates the gamma-weighted aggregate in place — the
    live set is ONE master params tree + `mb` compute-dtype replicas.  The
    tail group is padded with slot-0 replicas carrying zero gamma AND zero
    mask, so pad work contributes exact zeros and pad opt-state/losses are
    sliced off before returning.

    Numerics contract (pinned by tests/test_engine_parity.py): per-client
    local trajectories compute the same math as the vmapped path, and group
    uplinks are keyed with the clients' GLOBAL slot ids
    (`compress_uplinks(slots=...)`), so each client draws the same dither.
    Two things change the summation order: XLA:CPU folds the vmapped
    clients into the shapes of the matmuls, so a GEMM of another width may
    sum in another order, and the aggregation becomes `acc +=
    einsum(gamma_group, delta_group)` instead of one full einsum.  So
    aggregated params match to ≤1 ulp per interaction, and exactly when
    mb >= n (a single group IS the full vmap and its einsum the full one).

    Under a `precision` policy the helper is also the mixed-precision hot
    path: params/batch/lr are cast to `precision.compute` once per
    interaction (tagged "precision_cast"), the group deltas are cast up
    (tagged "master_accumulate") into a master-dtype accumulator, and the
    returned params stay master-dtype — the ES never holds a compute-dtype
    authority copy.

    Returns ``step(params, opt_state, batch, gammas, mask, lrs, sub) ->
    (new_params, new_opt_state, per-client losses (n,))`` with batch leaves
    (n, E, B, ...), opt-state leaves (n, ...), gammas/mask (n,), lrs (E,).
    """
    multi_local = jax.vmap(local_fn, in_axes=(None, 0, 0, None))

    def step(p, s, b, gammas, mask, lrs, sub):
        n = gammas.shape[0]
        pad = (-n) % mb
        groups = (n + pad) // mb
        if pad:
            zeros = lambda v: jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
            rep = lambda a: jnp.concatenate(
                [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])])
            gammas, mask = zeros(gammas), zeros(mask)
            b = jax.tree.map(rep, b)
            s = jax.tree.map(rep, s)
        group = lambda a: a.reshape((groups, mb) + a.shape[1:])

        p_c = compute_cast(p, precision)
        lrs_c = compute_cast(lrs, precision)
        b = compute_cast(b, precision)
        acc0 = jax.tree.map(jnp.zeros_like, p)  # master-dtype accumulator

        def one_group(acc, inp):
            s_j, b_j, g_j, msk_j, slots_j = inp
            with jax.named_scope("local_train"):
                new_p, new_s, losses = multi_local(p_c, s_j, b_j, lrs_c)
                new_s = _freeze_masked(msk_j, new_s, s_j)
            with jax.named_scope("uplink"):
                raw = jax.tree.map(
                    lambda a: a * msk_j.astype(a.dtype).reshape((-1,) + (1,) * (a.ndim - 1)),
                    jax.tree.map(lambda a, base: a - base[None], new_p, p_c),
                )
                deltas = compress_uplinks(channel, raw, sub, slots=slots_j)
            deltas = master_cast(deltas, precision)
            with jax.named_scope("intra_agg"):
                acc = jax.tree.map(
                    lambda a, dl: a + jnp.einsum(
                        "n,n...->...", g_j.astype(a.dtype), dl.astype(a.dtype)),
                    acc, deltas)
            return acc, (new_s, losses)

        xs = (jax.tree.map(group, s), jax.tree.map(group, b), group(gammas),
              group(mask), group(jnp.arange(n + pad)))
        acc, (new_s, losses) = jax.lax.scan(one_group, acc0, xs)
        new_params = tree_add(p, acc)
        new_s = jax.tree.map(lambda a: a.reshape((n + pad,) + a.shape[2:])[:n], new_s)
        return new_params, new_s, losses.reshape(n + pad)[:n]

    return step


@functools.cache
def _masked_round_body(model: FedModel, channel: Channel, opt: LocalOpt,
                       taps: bool = False, microbatch: int | None = None,
                       precision: Precision | None = None):
    """The pure (unjitted) masked delta round — shared verbatim by the
    per-round compiled function (`_masked_delta_round_fn`) and the whole-run
    scan bodies below, so the looped and scanned paths trace the exact same
    computation.  With `taps` the round additionally returns the tele dict
    (mask-weighted, a final-interaction snapshot — see `_scan_and_tap_last`);
    taps=False is its own cache entry tracing the exact pre-telemetry
    graph.  `microbatch`/`precision` as in `_delta_round_fn` (the microbatch
    path routes through `_microbatched_cluster_step`; None/None traces the
    pre-mixed-precision graph byte-for-byte)."""
    if microbatch is not None:
        assert not taps, "telemetry taps are unsupported with client_microbatch"
        step = _microbatched_cluster_step(
            local_opt_steps(model, opt), channel, int(microbatch), precision)

        def mb_round_fn(params, opt_state, batch, gammas, mask, lrs, subs):
            def interaction(carry, inp):
                p, s = carry
                b, lr, sub = inp
                new_p, new_s, losses = step(p, s, b, gammas, mask, lr, sub)
                loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
                return (new_p, new_s), loss

            (p, s), losses = jax.lax.scan(interaction, (params, opt_state),
                                          (batch, lrs, subs))
            return p, s, losses

        return mb_round_fn

    multi_local = jax.vmap(local_opt_steps(model, opt), in_axes=(None, 0, 0, None))

    def round_fn(params, opt_state, batch, gammas, mask, lrs, subs):
        def interaction(carry, inp, tap=False):
            p, s = carry
            b, lr, sub = inp
            p_c = compute_cast(p, precision)
            with jax.named_scope("local_train"):
                new_p, new_s, losses = multi_local(
                    p_c, s, compute_cast(b, precision), compute_cast(lr, precision))
                new_s = _freeze_masked(mask, new_s, s)
            with jax.named_scope("uplink"):
                raw = jax.tree.map(
                    lambda a, base: (a - base[None])
                    * mask.astype(a.dtype).reshape((-1,) + (1,) * (a.ndim - 1)),
                    new_p,
                    p_c,
                )
                deltas = compress_uplinks(channel, raw, sub)
            deltas = master_cast(deltas, precision)
            with jax.named_scope("intra_agg"):
                agg = jax.tree.map(
                    lambda dl: jnp.einsum("n,n...->...", gammas.astype(dl.dtype), dl),
                    deltas)
                new_params = tree_add(p, agg)
            loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
            out = (loss, delta_taps(raw, tree_sub(new_params, p),
                                    gammas, mask)) if tap else loss
            return (new_params, new_s), out

        return _scan_and_tap_last(interaction, (params, opt_state),
                                  (batch, lrs, subs), taps)

    return round_fn


@functools.cache
def _masked_delta_round_fn(model: FedModel, channel: Channel, opt: LocalOpt,
                           taps: bool = False, microbatch: int | None = None,
                           precision: Precision | None = None):
    """Delta mode with a per-client participation mask (n,): masked-out
    clients contribute zero delta (their slot is zeroed before compression),
    are excluded from the loss average, and keep their `LocalOpt` state
    frozen in place.  `gammas` must already be renormalized over the
    participating set (zero on masked slots).  Otherwise identical to
    `_delta_round_fn`; the unmasked function stays untouched so the default
    full-participation path is bit-identical to the pre-participation stack.
    """
    return _jit_round(_masked_round_body(model, channel, opt, taps,
                                         microbatch, precision))


@functools.cache
def _multi_round_body(model: FedModel, channel: Channel, es_channel: Channel, opt: LocalOpt,
                      taps: bool = False, microbatch: int | None = None,
                      precision: Precision | None = None):
    """Pure (unjitted) 3-tier HFL global round, vmapped over all M clusters at
    once — shared by `_multi_round_fn` and the whole-run scan body.
    batch leaves: (J, M, n_max, E, B, ...), opt_state leaves: (M, n_max, ...),
    gammas/mask: (M, n_max), es_weights: (M,), lrs: (J, E), subs: (J, M, 2),
    es_subs: (M, 2).  Padded client slots (mask == 0) carry zero gamma
    weight and their deltas are zeroed before compression.
    Returns (params, opt_state, per-(interaction, cluster) losses (J, M));
    with `taps` also a per-cluster (M,) tele dict (a final-interaction
    snapshot — see `_scan_and_tap_last` — + "es_comp_err" for the ES->PS
    channel).  taps=False traces the exact pre-telemetry graph.
    `microbatch`/`precision` as in `_delta_round_fn`: the per-cluster
    interaction routes through `_microbatched_cluster_step` (the M-cluster
    vmap stays — peak is M * microbatch compute replicas), and cluster/PS
    params stay master-dtype."""
    if microbatch is not None:
        assert not taps, "telemetry taps are unsupported with client_microbatch"
        mb_step = _microbatched_cluster_step(
            local_opt_steps(model, opt), channel, int(microbatch), precision)
    multi_local = jax.vmap(local_opt_steps(model, opt), in_axes=(None, 0, 0, None))

    def round_fn(params, opt_state, batch, gammas, mask, es_weights, lrs, subs, es_subs):
        M = mask.shape[0]
        cparams0 = jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf[None], (M,) + leaf.shape), params
        )

        def interaction(carry, inp, tap=False):
            cp, s = carry
            b, lr, sub = inp

            def one_cluster_mb(p_m, s_m, b_m, g_m, msk_m, sub_m):
                new_pm, new_s, losses = mb_step(p_m, s_m, b_m, g_m, msk_m, lr, sub_m)
                loss = jnp.sum(losses * msk_m) / jnp.maximum(jnp.sum(msk_m), 1.0)
                return new_pm, new_s, loss

            def one_cluster(p_m, s_m, b_m, g_m, msk_m, sub_m):
                p_mc = compute_cast(p_m, precision)
                with jax.named_scope("local_train"):
                    new_p, new_s, losses = multi_local(
                        p_mc, s_m, compute_cast(b_m, precision),
                        compute_cast(lr, precision))
                    # masked slots (padding OR dropped-out clients) keep their opt
                    # state frozen; for real participating slots the select is a
                    # bit-exact identity, so default-path parity holds
                    new_s = _freeze_masked(msk_m, new_s, s_m)
                with jax.named_scope("uplink"):
                    raw = jax.tree.map(
                        lambda a, base: (a - base[None])
                        * msk_m.astype(a.dtype).reshape((-1,) + (1,) * (a.ndim - 1)),
                        new_p,
                        p_mc,
                    )
                    deltas = compress_uplinks(channel, raw, sub_m)
                deltas = master_cast(deltas, precision)
                with jax.named_scope("intra_agg"):
                    agg = jax.tree.map(
                        lambda dl: jnp.einsum("n,n...->...", g_m.astype(dl.dtype), dl),
                        deltas)
                    new_pm = tree_add(p_m, agg)
                # a fully-dropped cluster has sum(mask) == 0: its loss reads 0
                # and its params stay at the broadcast model (zero deltas)
                loss = jnp.sum(losses * msk_m) / jnp.maximum(jnp.sum(msk_m), 1.0)
                out = (loss, delta_taps(raw, tree_sub(new_pm, p_m),
                                        g_m, msk_m)) if tap else loss
                return new_pm, new_s, out

            cluster_fn = one_cluster_mb if microbatch is not None else one_cluster
            cp, s, ys = jax.vmap(cluster_fn)(cp, s, b, gammas, mask, sub)
            return (cp, s), ys

        out = _scan_and_tap_last(interaction, (cparams0, opt_state),
                                 (batch, lrs, subs), taps)
        cparams, opt_state = out[0], out[1]

        # ES -> PS: compressed cluster deltas, PS weighted-aggregates + broadcasts
        with jax.named_scope("es_hop"):
            if taps:
                raw_es = jax.vmap(lambda p_m: tree_sub(p_m, params))(cparams)
                es_deltas = jax.vmap(es_channel.compress)(raw_es, es_subs)
            else:
                es_deltas = jax.vmap(
                    lambda p_m, sub_m: es_channel.compress(tree_sub(p_m, params), sub_m)
                )(cparams, es_subs)
            agg = jax.tree.map(lambda x_: jnp.einsum("m,m...->...", es_weights, x_), es_deltas)
            new_params = tree_add(params, agg)
        if taps:
            losses, tele = out[2], dict(out[3])  # tele leaves: (M,)
            tele["es_comp_err"] = tree_client_norms(
                jax.tree.map(lambda c, r: c - r, es_deltas, raw_es))
            return new_params, opt_state, losses, tele
        return new_params, opt_state, out[2]

    return round_fn


@functools.cache
def _multi_round_fn(model: FedModel, channel: Channel, es_channel: Channel, opt: LocalOpt,
                    taps: bool = False, microbatch: int | None = None,
                    precision: Precision | None = None):
    """Compiled `_multi_round_body` (the per-round 3-tier HFL entry point)."""
    return _jit_round(_multi_round_body(model, channel, es_channel, opt, taps,
                                        microbatch, precision))


# --------------------------------------------------------------------------
# public facade
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RoundEngine:
    """Per-run facade over the cached compiled round functions.

    `model` may be a raw `Classifier` (wrapped to a `FedModel` on
    construction) or any `FedModel`.  `channel` compresses client -> ES
    uplinks; `es_channel` (3-tier HFL only) compresses ES -> PS uplinks and
    defaults to `channel`.  `local_opt` is the client-held local optimizer;
    the default `PlainSGD` is the seed-parity Eq. (5) step.

    `client_microbatch` bounds how many client replicas train concurrently
    inside a round (None = the historical all-clients vmap): peak memory
    drops from O(n) to O(microbatch) model copies — ≤1 ulp per interaction,
    exact at microbatch >= n (`_microbatched_cluster_step`).
    `precision` is the mixed-precision policy (core/precision.py): clients
    compute in `precision.compute` while the engine's authoritative params
    and delta aggregation stay in `precision.master`; grad mode (the
    paper-literal Eq. (5) path) ignores it.  Both default to None, which
    keeps every compiled graph byte-for-byte the pre-knob round.
    """

    model: FedModel
    channel: Channel = DenseChannel()
    es_channel: Channel | None = None
    local_opt: LocalOpt | None = None  # None -> PlainSGD()
    client_microbatch: int | None = None
    precision: Precision | None = None

    def __post_init__(self):
        object.__setattr__(self, "model", as_fed_model(self.model))
        if self.local_opt is None:
            object.__setattr__(self, "local_opt", PlainSGD())
        if self.client_microbatch is not None:
            assert self.client_microbatch >= 1

    def init_opt_state(self, params: PyTree, *lead: int) -> PyTree:
        """Fresh stacked per-client optimizer state with leading axes `lead`
        (e.g. `(n,)` for one cluster, `(M, n_max)` for 3-tier HFL).  Empty
        pytree (zero cost) for the default stateless SGD.  Under a
        `precision` policy the state is seeded from the COMPUTE-dtype params:
        client-held moments live at compute width (only the ES keeps f32
        state), matching the dtype the local steps update them at."""
        if self.precision is not None:
            params = cast_floats(params, self.precision.compute)
        state = self.local_opt.init(params)
        for n in reversed(lead):
            state = jax.tree.map(
                lambda leaf, n=n: jnp.broadcast_to(leaf[None], (n,) + leaf.shape), state
            )
        return state

    def grad_round(self, params, batch, gammas, lrs, *, taps=False):
        return _grad_round_fn(self.model, taps, self.client_microbatch)(
            params, batch, gammas, lrs)

    def cluster_round(self, params, batch, gammas, lrs, subs=None, opt_state=None,
                      mask=None, *, taps=False):
        """One delta-mode round.  `mask` (n,) is the optional per-client
        participation mask (repro.part): masked-out clients contribute zero
        delta, are excluded from the loss, and keep their opt state frozen.
        With `mask=None` the compiled function is the exact pre-participation
        round — the bit-identical full-participation path.  `taps=True`
        appends the per-round tele dict to the return tuple (a separately
        cached compiled variant; the default path's graph is untouched)."""
        J = jax.tree.leaves(batch)[0].shape[0]
        n = jax.tree.leaves(batch)[0].shape[1]
        if subs is None:
            subs = dummy_subs(J)
        if opt_state is None:
            opt_state = self.init_opt_state(params, n)
        if mask is None:
            fn = _delta_round_fn(self.model, self.channel, self.local_opt, taps,
                                 self.client_microbatch, self.precision)
            return fn(params, opt_state, batch, gammas, lrs, subs)
        fn = _masked_delta_round_fn(self.model, self.channel, self.local_opt, taps,
                                    self.client_microbatch, self.precision)
        return fn(params, opt_state, batch, gammas, jnp.asarray(mask), lrs, subs)

    def multi_cluster_round(
        self, params, batch, gammas, mask, es_weights, lrs,
        subs=None, es_subs=None, opt_state=None, *, taps=False,
    ):
        J, M = jax.tree.leaves(batch)[0].shape[:2]
        if subs is None:
            subs = dummy_subs(J, M)
        if es_subs is None:
            es_subs = dummy_subs(M)
        if opt_state is None:
            opt_state = self.init_opt_state(params, M, mask.shape[1])
        fn = _multi_round_fn(
            self.model, self.channel, self.es_channel or self.channel, self.local_opt,
            taps, self.client_microbatch, self.precision,
        )
        return fn(params, opt_state, batch, gammas, mask, es_weights, lrs, subs, es_subs)

    def end_round(self, ledger: CommLedger, round_idx: int) -> None:
        """Uniform end-of-round bookkeeping: snapshot the ledger.

        Every driver calls this exactly once per round (instead of each
        driver deciding its own snapshot cadence), so `bits_until` always
        sees a complete per-round history regardless of algorithm.
        """
        ledger.snapshot(round_idx)


# --------------------------------------------------------------------------
# whole-run execution: lax.scan over rounds
# --------------------------------------------------------------------------
#
# The looped drivers pay per-round host costs: one jit dispatch, per-round
# batch `jnp.asarray` transfers, scheduler advances, and ledger appends.
# `run_scan` removes all of them from the hot loop: the driver precomputes
# the whole run's schedule host-side (visit order, participation masks, PRNG
# subkeys), stages batches a *chunk* of rounds at a time, and executes each
# chunk as one jitted `lax.scan` over rounds.  The only host<->device traffic
# between eval points is the chunk's single explicit `device_put`; communica-
# tion accounting is deferred to `CommLedger.materialize` after the run.
#
# Rounds in which nothing trains (an all-dark cluster, a zero-reporter FedAvg
# round, a pass-through walk visit) are pure no-ops on the model state, so
# the scan simply *skips* them: it runs over the trained rounds only, and the
# host-side schedule maps eval/ledger bookkeeping back to global round
# indices.  That keeps the scan body mask-free of `trained` flags and means
# dark rounds consume neither data draws nor PRNG subkeys — exactly the
# looped drivers' behavior.
#
# Scan bodies close over the SAME cached pure round bodies the per-round
# compiled functions use (`_masked_round_body`, `_multi_round_body`,
# `oracles.grad_phase`), so looped and scanned runs trace identical per-round
# computations: model params are bit-identical at fixed seed (pinned by
# tests/test_engine_parity.py); only the *reported* loss scalars may differ
# by ~1 ulp from reduction fusion across the scan boundary.


@functools.cache
def scan_grad_body(model: FedModel, taps: bool = False,
                   microbatch: int | None = None):
    """Whole-run body, Eq. (5) grad mode.  carry: params.
    x: {"batch": (K, n_max, B, ...), "gammas": (n_max,), "lrs": (K,)} (padded
    client slots carry zero gamma weight — exact-zero contributions; the step
    sizes are staged per round so decaying schedules can track the GLOBAL
    round index, e.g. WRWGD's walk).  Emits the per-step gamma-weighted
    losses (K,); with `taps` the ys are (losses, tele) so the chunk runner
    can split the stacked telemetry off.  `microbatch` bounds concurrent
    client backward passes bit-identically (`oracles.grad_phase`)."""
    phase = grad_phase(model, microbatch)

    def body(params, x, consts):
        del consts
        with jax.named_scope("local_train"):
            new_params, losses = phase(params, x["batch"], x["gammas"], x["lrs"])
        if taps:
            return new_params, (losses, grad_taps(params, new_params, x["gammas"]))
        return new_params, losses

    return body


@functools.cache
def scan_delta_body(model: FedModel, channel: Channel, opt: LocalOpt,
                    taps: bool = False, microbatch: int | None = None,
                    precision: Precision | None = None):
    """Whole-run body, delta mode over one fixed client set (FedAvg).
    carry: (params, opt_state (n, ...)).  x: {"batch": (J, n, E, B, ...),
    "gammas"/"mask": (n,), "subs": (J, 2)}.  consts: {"lrs": (J, E)}.
    Emits per-interaction masked mean losses (J,); with `taps` the ys are
    (losses, tele).  `microbatch`/`precision` as in `_delta_round_fn`."""
    round_fn = _masked_round_body(model, channel, opt, taps, microbatch, precision)

    def body(carry, x, consts):
        params, opt_state = carry
        out = round_fn(
            params, opt_state, x["batch"], x["gammas"], x["mask"], consts["lrs"], x["subs"]
        )
        if taps:
            params, opt_state, losses, tele = out
            return (params, opt_state), (losses, tele)
        params, opt_state, losses = out
        return (params, opt_state), losses

    return body


@functools.cache
def scan_cluster_delta_body(model: FedModel, channel: Channel, opt: LocalOpt,
                            taps: bool = False, microbatch: int | None = None,
                            precision: Precision | None = None):
    """Whole-run body, delta mode with a per-round active cluster (Fed-CHS).
    carry: (params, opt_states (M, n_max, ...)) — the active cluster's rows
    are gathered/scattered by the scanned cluster index x["m"].
    x adds "m": () int32 to the `scan_delta_body` inputs (all padded to
    n_max width).  `microbatch`/`precision` as in `_delta_round_fn`."""
    round_fn = _masked_round_body(model, channel, opt, taps, microbatch, precision)

    def body(carry, x, consts):
        params, opt_all = carry
        m = x["m"]
        s_m = jax.tree.map(
            lambda leaf: jax.lax.dynamic_index_in_dim(leaf, m, 0, keepdims=False), opt_all
        )
        out = round_fn(
            params, s_m, x["batch"], x["gammas"], x["mask"], consts["lrs"], x["subs"]
        )
        if taps:
            params, new_s, losses, tele = out
        else:
            params, new_s, losses = out
        opt_all = jax.tree.map(
            lambda leaf, ns: jax.lax.dynamic_update_index_in_dim(leaf, ns, m, 0),
            opt_all,
            new_s,
        )
        if taps:
            return (params, opt_all), (losses, tele)
        return (params, opt_all), losses

    return body


@functools.cache
def scan_multi_body(model: FedModel, channel: Channel, es_channel: Channel, opt: LocalOpt,
                    taps: bool = False, microbatch: int | None = None,
                    precision: Precision | None = None):
    """Whole-run body, 3-tier HFL global rounds (Hier-Local-QSGD).
    carry: (params, opt_state (M, n_max, ...)).  x: {"batch": (J, M, n_max,
    E, B, ...), "gammas"/"mask": (M, n_max), "es_weights": (M,), "subs":
    (J, M, 2), "es_subs": (M, 2)}.  Emits losses (J, M); with `taps` the ys
    are (losses, tele) with per-cluster (M,) tele leaves.
    `microbatch`/`precision` as in `_multi_round_body`."""
    round_fn = _multi_round_body(model, channel, es_channel, opt, taps,
                                 microbatch, precision)

    def body(carry, x, consts):
        params, opt_state = carry
        out = round_fn(
            params, opt_state, x["batch"], x["gammas"], x["mask"], x["es_weights"],
            consts["lrs"], x["subs"], x["es_subs"],
        )
        if taps:
            params, opt_state, losses, tele = out
            return (params, opt_state), (losses, tele)
        params, opt_state, losses = out
        return (params, opt_state), losses

    return body


@functools.cache
def _chunk_of(body):
    """The pure chunk function: scan `body` over a stacked-rounds xs pytree.
    Signature: (carry, xs, consts) -> (carry, stacked per-round losses)."""

    def chunk(carry, xs, consts):
        return jax.lax.scan(lambda c, x: body(c, x, consts), carry, xs)

    return chunk


@functools.cache
def scan_chunk_fn(body):
    """jit(chunk) — the whole-run hot loop.  Where the backend supports
    buffer donation (tpu/gpu; CPU donation only warns), BOTH chunk inputs
    are donated:

      * the carry (argnum 0) — run-level: params/opt-state buffers are
        reused across chunks, so the master params exist once;
      * the staged xs (argnum 1) — chunk-level: `_run_chunks` stages a
        FRESH xs pytree per chunk via `device_put` and never touches it
        again, so donating hands its batch buffers back to the allocator
        as the scan consumes them.

    Together with `client_microbatch` this is what pins the LM run's live
    set at (master params + opt states) + one chunk of staged batches +
    one microbatch of activations.  `consts` (argnum 2) is deliberately NOT
    donated: it is reused by every chunk of the run."""
    fn = _chunk_of(body)
    if jax.default_backend() in ("tpu", "gpu"):
        return jax.jit(fn, donate_argnums=(0, 1))
    return jax.jit(fn)


@functools.cache
def sweep_chunk_fn(body):
    """`scan_chunk_fn` vmapped over a leading seed axis on carry and xs
    (consts are shared) — one dispatch advances every seed of a sweep."""
    return _jit_round(jax.vmap(_chunk_of(body), in_axes=(0, 0, None)))


def eval_rounds(rounds: int, eval_every: int) -> list[int]:
    """The rounds every driver logs at: t % eval_every == 0, plus the final
    round — the exact looped-driver cadence."""
    ev = [t for t in range(rounds) if t % eval_every == 0]
    if rounds - 1 not in ev:
        ev.append(rounds - 1)
    return ev


@dataclasses.dataclass
class ScanPlan:
    """A precomputed whole-run schedule for `run_scan`.

    `trained` marks the rounds that actually train (all of them under full
    participation); the scan runs over those only.  `stage(idxs)` returns the
    stacked per-round scan inputs (numpy leaves, leading axis len(idxs)) for
    the given ascending *global* round indices — it is the only host work
    left in the loop, and `run_scan` moves its output to the device with one
    explicit `device_put` per chunk.
    """

    body: Any                 # a scan_*_body (hashable: keys the jit cache)
    carry: PyTree
    consts: PyTree
    stage: Any                # (np.ndarray of round idxs) -> xs pytree
    trained: Any              # (rounds,) bool numpy array
    rounds: int
    eval_every: int
    chunk_rounds: int = 32
    obs: Any = None           # repro.obs.RunTelemetry | None; when its taps
    #                           flag is set, `body` must be the tapped variant
    #                           (ys = (losses, tele)) — plan builders pair them
    chunk_fn: Any = None      # compiled (carry, xs, consts) -> (carry, ys)
    #                           override; None -> scan_chunk_fn(body).  The
    #                           device-mesh path (repro.sharding.fed) installs
    #                           its shard_map-wrapped chunk here so run_scan
    #                           itself never branches on sharding.
    xs_put: Any = None        # staged-xs host->device transfer override; None
    #                           -> plain jax.device_put.  The mesh path uses a
    #                           per-leaf NamedSharding put (each device
    #                           receives only its shard slice — the global
    #                           stacked tensor never lands on one device).


def run_scan(plan: ScanPlan, record) -> PyTree:
    """Execute a whole run as chunked `lax.scan`s over its trained rounds.

    Chunks are cut at eval rounds (and at `chunk_rounds` to bound staged-
    batch memory), so between eval points the only host<->device traffic is
    the per-chunk staged-input `device_put`.  `record(t, carry, losses, t_l)`
    fires at every eval round t with the carry after round t, the last
    trained round's on-device loss row (None if nothing trained yet), and
    that round's global index t_l.  Returns the final carry.

    Compile cost: each DISTINCT chunk length compiles its own scan program
    (jit's shape-keyed cache).  With full participation the segmentation
    yields at most ~3 lengths (1, the eval_every/chunk_rounds period, and a
    remainder); participation churn can produce more (trained-round counts
    vary per segment, bounded by chunk_rounds).  The cache is per-process and
    keyed on the cached scan body, so repeated runs of the same shapes — the
    sweep/benchmark pattern — compile nothing after the first.  Padding
    chunks to one fixed length would cap this at a single compile but would
    require staging dummy batches for pad rounds, breaking the invariant
    that skipped rounds consume no data draws — we take the extra compiles.
    """
    assert plan.chunk_rounds >= 1
    chunk = plan.chunk_fn if plan.chunk_fn is not None else scan_chunk_fn(plan.body)
    return _run_chunks(chunk, plan.carry, plan.stage, plan,
                       record, last_slice=lambda leaf: leaf[-1])


def run_scan_sweep(plans: list[ScanPlan], record, *, mesh=None) -> PyTree:
    """Run several same-config, different-seed `ScanPlan`s as ONE vmapped
    scan over a leading seed axis.  All plans must share body/consts/trained
    schedule (same config, full participation); per-seed divergence lives in
    the stacked carries and staged inputs (visit orders, PRNG subkeys, data
    draws).  `record(t, carry, losses, t_l)` sees seed-stacked carry/losses.
    Returns the final stacked carry.

    `mesh` shards the leading seed axis across every device of the given
    mesh (pure GSPMD — the vmapped scan is compiled unchanged, only the
    input layouts change, so per-lane trajectories stay bit-exact).  The
    seed count must divide `mesh.size`; a non-divisible sweep logs a
    warning and runs unsharded rather than silently padding lanes.
    """
    p0 = plans[0]
    assert p0.obs is None, "telemetry is unsupported in vmapped sweeps"
    assert all(p.body is p0.body for p in plans), "sweep plans must share a body"
    assert all(np.array_equal(np.asarray(p.trained), np.asarray(p0.trained)) for p in plans), \
        "sweep plans must share the trained-round schedule (full participation)"
    assert p0.chunk_fn is None, \
        "mesh-sharded plans (sharding.fed.shard_plan) cannot be swept — the " \
        "client axes are already mapped to devices; shard the seed axis " \
        "instead via run_scan_sweep(mesh=...)"
    carry = jax.tree.map(lambda *ls: jnp.stack(ls), *[p.carry for p in plans])

    def stage(idxs):
        return jax.tree.map(lambda *ls: np.stack(ls), *[p.stage(idxs) for p in plans])

    if mesh is not None and len(plans) % mesh.size != 0:
        _log.warning(
            "sweep of %d seeds does not divide mesh of %d devices — "
            "running unsharded", len(plans), mesh.size,
        )
        mesh = None
    if mesh is not None:
        # GSPMD: lay the seed axis over all mesh devices; the compiler
        # partitions the vmapped scan lane-by-lane (per-lane bit-exact)
        seed_sh = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(tuple(mesh.axis_names))
        )
        carry = jax.device_put(carry, seed_sh)
        p0 = dataclasses.replace(
            p0,
            consts=jax.device_put(
                p0.consts, jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec())),
            xs_put=lambda xs: jax.device_put(xs, seed_sh),
        )

    return _run_chunks(sweep_chunk_fn(p0.body), carry, stage, p0,
                       record, last_slice=lambda leaf: leaf[:, -1])


def _run_chunks(chunk, carry, stage, plan: ScanPlan, record, *, last_slice) -> PyTree:
    """The shared chunked-execution loop behind `run_scan`/`run_scan_sweep`:
    segment the trained rounds at eval boundaries (capped at `chunk_rounds`),
    stage + `device_put` + execute each chunk, track the last trained round's
    on-device loss row (`last_slice` absorbs the sweep's leading seed axis),
    and fire `record` at every eval round.  With `plan.obs` set, each chunk's
    "stage" span holds a "draw" and a "device_put" span, and the chunk adds
    its staged bytes and rounds to the `staged_bytes`/`trained_rounds`
    counters."""
    obs = plan.obs
    tapped = obs is not None and obs.taps
    xs_put = plan.xs_put if plan.xs_put is not None else jax.device_put
    trained_idx = np.flatnonzero(np.asarray(plan.trained))
    last_losses, last_t = None, None
    pos = 0
    for t_e in eval_rounds(plan.rounds, plan.eval_every):
        n_t = int(np.searchsorted(trained_idx, t_e, side="right"))
        while pos < n_t:
            take = min(plan.chunk_rounds, n_t - pos)
            idxs = trained_idx[pos : pos + take]
            with maybe_span(obs, "stage"):
                with maybe_span(obs, "draw"):
                    staged = stage(idxs)
                if obs is not None:
                    obs.count("staged_bytes",
                              sum(leaf.nbytes for leaf in jax.tree.leaves(staged)))
                    obs.count("trained_rounds", len(idxs))
                with maybe_span(obs, "device_put"):
                    xs = xs_put(staged)
            with maybe_span(obs, "scan_chunk"):
                carry, ys = chunk(carry, xs, plan.consts)
                if tapped:
                    # hand the stacked tele to the recorder; by default it
                    # defers the host transfer (keeping this loop's async
                    # pipelining), while obs.sync_chunks blocks here so the
                    # span covers the chunk's real execution time
                    losses, tele = ys
                    obs.record_stacked(idxs.tolist(), tele)
                else:
                    losses = ys
            last_losses = jax.tree.map(last_slice, losses)
            last_t = int(idxs[-1])
            pos += take
        record(t_e, carry, last_losses, last_t)
    return carry

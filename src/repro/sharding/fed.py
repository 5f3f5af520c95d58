"""Population-scale federation: shard the engine's client/cluster axes.

The whole-run scan engine (`repro.core.engine`) stacks every per-client
quantity — batches, opt states, masks, PRNG subkeys — on leading
(clusters, clients) axes and vmaps over them.  That layout is exactly a
data-parallel device layout: this module partitions those stacked axes over
a ``("clusters", "clients")`` device mesh with `shard_map`, keeping the
Fed-CHS serial ES->ES chain a carried collective (the global params stay
replicated; cross-device communication happens only at aggregation points,
as `all_gather`s of the compressed uplinks).

Bit-parity contract
-------------------
A mesh run reproduces the single-device run of the same config exactly:
model params, eval metrics, and ledger aggregates are BIT-identical; the
per-round train-loss *log scalars* are bit-identical in grad mode and
within 1 ulp in delta modes (the lane-loss mean fuses with different
consumers under shard_map, the same reassociation the vmapped sweep
already documents in `core.sweep`; losses never feed back into training).
Pinned by tests/test_sharding_fed.py under forced 8 host devices.  One
backend caveat rides on top: XLA:CPU's batched-GEMM kernel choice can
depend on the vmap lane count for LARGE layers under the thread-starved
forced-host-device runtime (observed at 784x200, absent at <=128-wide
layers and absent under the default runtime), which perturbs local grads
at ~1e-7 before any of this module's collectives run.  A second one: in
the unsharded body XLA:CPU may fuse a channel's decode (QSGD's
dequantize) into the gamma-weighted dot as its operand, while here the
all_gather materializes the decoded stack first, so the two dots may sum
in different orders — FedAvg with QSGD(16) on 20 clients reads params
within ~1 ulp (3.7e-9 after 4 rounds), not bit-equal.  The machinery
itself is width-exact:

  * aggregation is NOT a `psum` of partial sums — that would reassociate
    the gamma-weighted reduction.  Each shard compresses its local senders'
    deltas, the shards `all_gather` the compressed messages (tiled, in
    axis-index order == global slot order), and every device applies the
    SAME full-width einsum the unsharded body runs.
  * per-sender compression keys are `fold_in(sub, slot)` with GLOBAL slot
    ids (`axis_index * n_loc + arange(n_loc)`), so sender i sees the exact
    key it gets in the unsharded stack (`engine.compress_uplinks`).
  * client/cluster axes are zero-padded up to mesh-divisible widths: padded
    slots carry exact-zero gamma/mask (zero deltas, which every channel
    encodes to zero norms and decodes to exact zeros), and padded batch
    slots replicate slot 0 so their (discarded) local training stays
    finite — the same padding discipline the scan path already pins for
    ragged clusters.
  * gathered stacks are sliced back to the TRUE (unsharded) width before
    every cross-client reduction — a wider zero-tailed einsum is equal in
    exact arithmetic but lets XLA group the sum differently, so the
    reductions must see exactly the unsharded operands.

The single-device path is byte-for-byte untouched: with ``mesh=None`` the
drivers never import a sharded body, and `ScanPlan.chunk_fn`/`xs_put`
default to the unsharded chunk and plain `device_put`.

Axis mapping
------------
  * FedAvg / Fed-CHS: ONE cluster trains per round, so the flat client axis
    shards over BOTH mesh axes — ``P(("clusters", "clients"))``.
  * Hier-Local-QSGD: independent clusters shard over ``"clusters"``,
    clients within an ES shard over ``"clients"``; the intra-cluster
    aggregate gathers over ``"clients"`` only, the ES->PS hop over
    ``"clusters"`` only.
  * WRWGD (n = 1): degrades gracefully — the walk's single client pads to
    mesh width with zero-gamma slots (replicated compute, exact result).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.engine import ScanPlan, _freeze_masked, _jit_round
from repro.core.oracles import local_opt_steps
from repro.data.sources import put_sharded
from repro.sharding.ctx import current_mesh
from repro.sharding.specs import FED_AXES, fed_engine_pspecs
from repro.utils import tree_add, tree_sub

PyTree = Any


def resolve_mesh(mesh: Mesh | None) -> Mesh | None:
    """The federation mesh a driver should shard over, or None.

    An explicit ``config.mesh`` wins; otherwise adopt the ambient
    `sharding.ctx.model_mesh` mesh IF it is a federation mesh (axis names
    exactly ``("clusters", "clients")`` — a tensor-parallel model mesh is
    never silently adopted).  A 1-device mesh resolves to None: sharding a
    singleton mesh only adds collective overhead."""
    if mesh is None:
        amb = current_mesh()
        if amb is not None and tuple(amb.axis_names) == FED_AXES:
            mesh = amb
    if mesh is None:
        return None
    assert tuple(mesh.axis_names) == FED_AXES, (
        f"federation mesh must have axes {FED_AXES}, got {tuple(mesh.axis_names)}"
    )
    return mesh if mesh.size > 1 else None


# --------------------------------------------------------------------------
# padding: client/cluster axes grow to mesh-divisible widths
# --------------------------------------------------------------------------


def _ceil_to(n: int, q: int) -> int:
    return -(-n // q) * q


def _pad_np(a: np.ndarray, axis: int, to: int, *, edge0: bool) -> np.ndarray:
    """Pad `a` to width `to` along `axis`: zeros (weights/masks) or copies of
    index 0 (batches/subkeys — padded slots must stay finite/valid)."""
    pad = to - a.shape[axis]
    if pad <= 0:
        return a
    if edge0:
        reps = np.take(a, np.zeros(pad, np.intp), axis=axis)
        return np.concatenate([a, reps], axis=axis)
    width = [(0, 0)] * a.ndim
    width[axis] = (0, pad)
    return np.pad(a, width)


def _pad_leaf(a, axis: int, to: int):
    """Device-array edge0 pad (opt-state leaves; masked slots stay frozen)."""
    pad = to - a.shape[axis]
    if pad <= 0:
        return a
    reps = jnp.take(a, jnp.zeros(pad, jnp.int32), axis=axis)
    return jnp.concatenate([a, reps], axis=axis)


# --------------------------------------------------------------------------
# sharded scan bodies — the mesh twins of engine.scan_*_body.  Same
# (carry, x, consts) signatures, same per-slot computation; the only
# difference is WHERE each slot lives and the all_gather at each
# aggregation point.
# --------------------------------------------------------------------------


def _compress_shard(channel, deltas: PyTree, sub, slots):
    """`engine.compress_uplinks` for one shard of the stacked uplink:
    per-message channels key each local sender by its GLOBAL slot id, so the
    gathered stack carries exactly the keys the unsharded vmap hands out."""
    if getattr(channel, "per_message", False):
        return jax.vmap(
            lambda d, i: channel.compress(d, jax.random.fold_in(sub, i))
        )(deltas, slots)
    return channel.compress(deltas, sub)


def _gather(tree: PyTree, axes, axis: int = 0) -> PyTree:
    """Tiled all_gather in axis-index order — global slot order, so the
    downstream full-width einsum sees the unsharded operand layout."""
    return jax.tree.map(
        lambda leaf: jax.lax.all_gather(leaf, axes, axis=axis, tiled=True), tree
    )


@functools.cache
def sharded_grad_body(model, n: int):
    """Mesh twin of `scan_grad_body` (untapped): local per-step grads,
    all-gathered and sliced back to the true width `n`, then the SAME gamma
    einsum + SGD step on every device.  x["batch"] local leaves
    (K, n_loc, B, ...); gammas arrive padded full-width replicated."""
    grad_fn = jax.vmap(jax.value_and_grad(model.loss), in_axes=(None, 0))

    def body(params, x, consts):
        gammas = x["gammas"][:n]

        def step(p, inp):
            b_k, lr_k = inp
            losses, grads = grad_fn(p, b_k)
            grads = _gather(grads, FED_AXES)
            losses = jax.lax.all_gather(losses, FED_AXES, axis=0, tiled=True)[:n]
            agg = jax.tree.map(
                lambda g: jnp.einsum("n,n...->...", gammas, g[:n]), grads
            )
            p = jax.tree.map(lambda w, g: w - lr_k * g, p, agg)
            return p, jnp.dot(gammas, losses)

        return jax.lax.scan(step, params, (x["batch"], x["lrs"]))

    return body


def _sharded_masked_round(model, channel, opt, n: int):
    """Mesh twin of `engine._masked_round_body` (untapped): the flat client
    axis is sharded over the whole mesh; gammas/mask arrive padded full-width
    replicated, the body slices its local padded window, and every gathered
    stack is cut back to the true width `n` before reducing."""
    multi_local = jax.vmap(local_opt_steps(model, opt), in_axes=(None, 0, 0, None))

    def round_fn(params, opt_state, batch, gammas, mask, lrs, subs):
        n_loc = jax.tree.leaves(batch)[0].shape[1]
        start = jax.lax.axis_index(FED_AXES) * n_loc
        slots = start + jnp.arange(n_loc)
        mask_loc = jax.lax.dynamic_slice_in_dim(mask, start, n_loc)
        gammas_t, mask_t = gammas[:n], mask[:n]

        def interaction(carry, inp):
            p, s = carry
            b, lr, sub = inp
            new_p, new_s, losses = multi_local(p, s, b, lr)
            new_s = _freeze_masked(mask_loc, new_s, s)
            raw = jax.tree.map(
                lambda a, base: (a - base[None])
                * mask_loc.reshape((-1,) + (1,) * (a.ndim - 1)),
                new_p,
                p,
            )
            deltas = _gather(_compress_shard(channel, raw, sub, slots), FED_AXES)
            agg = jax.tree.map(
                lambda dl: jnp.einsum("n,n...->...", gammas_t, dl[:n]), deltas
            )
            new_params = tree_add(p, agg)
            g_losses = jax.lax.all_gather(losses, FED_AXES, axis=0, tiled=True)[:n]
            loss = jnp.sum(g_losses * mask_t) / jnp.maximum(jnp.sum(mask_t), 1.0)
            return (new_params, new_s), loss

        (p, s), losses = jax.lax.scan(
            interaction, (params, opt_state), (batch, lrs, subs)
        )
        return p, s, losses

    return round_fn


@functools.cache
def sharded_delta_body(model, channel, opt, n: int):
    """Mesh twin of `scan_delta_body` (FedAvg)."""
    round_fn = _sharded_masked_round(model, channel, opt, n)

    def body(carry, x, consts):
        params, opt_state = carry
        params, opt_state, losses = round_fn(
            params, opt_state, x["batch"], x["gammas"], x["mask"], consts["lrs"],
            x["subs"],
        )
        return (params, opt_state), losses

    return body


@functools.cache
def sharded_cluster_delta_body(model, channel, opt, n: int):
    """Mesh twin of `scan_cluster_delta_body` (Fed-CHS): the per-round active
    cluster's opt rows are gathered/scattered by x["m"] exactly as on one
    device — the cluster axis of the opt stack is NOT sharded (only one
    cluster trains per round); the client axis within it is."""
    round_fn = _sharded_masked_round(model, channel, opt, n)

    def body(carry, x, consts):
        params, opt_all = carry
        m = x["m"]
        s_m = jax.tree.map(
            lambda leaf: jax.lax.dynamic_index_in_dim(leaf, m, 0, keepdims=False),
            opt_all,
        )
        params, new_s, losses = round_fn(
            params, s_m, x["batch"], x["gammas"], x["mask"], consts["lrs"], x["subs"]
        )
        opt_all = jax.tree.map(
            lambda leaf, ns: jax.lax.dynamic_update_index_in_dim(leaf, ns, m, 0),
            opt_all,
            new_s,
        )
        return (params, opt_all), losses

    return body


@functools.cache
def sharded_multi_body(model, channel, es_channel, opt, M: int, n: int):
    """Mesh twin of `scan_multi_body` (Hier-Local-QSGD): clusters shard over
    "clusters", clients within each over "clients".  Intra-cluster
    aggregation gathers over "clients" only; the ES->PS hop gathers the
    compressed cluster deltas over "clusters" and applies the true-width
    (`M`, `n` — padding sliced off) weighted aggregate on every device."""
    multi_local = jax.vmap(local_opt_steps(model, opt), in_axes=(None, 0, 0, None))

    def body(carry, x, consts):
        params, opt_state = carry
        batch, gammas, mask = x["batch"], x["gammas"], x["mask"]
        lead = jax.tree.leaves(batch)[0].shape
        M_loc, n_loc = lead[1], lead[2]
        c_start = jax.lax.axis_index("clusters") * M_loc
        r_start = jax.lax.axis_index("clients") * n_loc
        slots = r_start + jnp.arange(n_loc)  # global client slot within a cluster

        # local windows of the replicated full-width schedule rows
        gam_c = jax.lax.dynamic_slice_in_dim(gammas, c_start, M_loc)
        mask_c = jax.lax.dynamic_slice_in_dim(mask, c_start, M_loc)
        mask_loc = jax.lax.dynamic_slice_in_dim(mask_c, r_start, n_loc, axis=1)
        subs_c = jax.lax.dynamic_slice_in_dim(x["subs"], c_start, M_loc, axis=1)
        es_subs_c = jax.lax.dynamic_slice_in_dim(x["es_subs"], c_start, M_loc)

        cparams0 = jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf[None], (M_loc,) + leaf.shape), params
        )

        def interaction(carry, inp):
            cp, s = carry
            b, lr, sub = inp

            def one_cluster(p_m, s_m, b_m, g_m, msk_m, mskloc_m, sub_m):
                new_p, new_s, losses = multi_local(p_m, s_m, b_m, lr)
                new_s = _freeze_masked(mskloc_m, new_s, s_m)
                raw = jax.tree.map(
                    lambda a, base: (a - base[None])
                    * mskloc_m.reshape((-1,) + (1,) * (a.ndim - 1)),
                    new_p,
                    p_m,
                )
                deltas = _gather(
                    _compress_shard(channel, raw, sub_m, slots), "clients"
                )
                agg = jax.tree.map(
                    lambda dl: jnp.einsum("n,n...->...", g_m[:n], dl[:n]), deltas
                )
                new_pm = tree_add(p_m, agg)
                g_losses = jax.lax.all_gather(
                    losses, "clients", axis=0, tiled=True
                )[:n]
                loss = (jnp.sum(g_losses * msk_m[:n])
                        / jnp.maximum(jnp.sum(msk_m[:n]), 1.0))
                return new_pm, new_s, loss

            cp, s, ys = jax.vmap(one_cluster)(cp, s, b, gam_c, mask_c, mask_loc, sub)
            return (cp, s), ys

        (cparams, opt_state), losses = jax.lax.scan(
            interaction, (cparams0, opt_state), (batch, consts["lrs"], subs_c)
        )

        # ES -> PS: compressed local-cluster deltas, gathered over "clusters",
        # true-width weighted aggregate + broadcast (replicated result)
        es_deltas = jax.vmap(
            lambda p_m, sub_m: es_channel.compress(tree_sub(p_m, params), sub_m)
        )(cparams, es_subs_c)
        es_deltas = _gather(es_deltas, "clusters")
        agg = jax.tree.map(
            lambda x_: jnp.einsum("m,m...->...", x["es_weights"][:M], x_[:M]),
            es_deltas,
        )
        new_params = tree_add(params, agg)
        losses = jax.lax.all_gather(losses, "clusters", axis=1, tiled=True)[:, :M]
        return (new_params, opt_state), losses

    return body


_BODY_OF = {
    "grad": lambda model, channel, es_channel, opt, M, n:
        sharded_grad_body(model, n),
    "delta": lambda model, channel, es_channel, opt, M, n:
        sharded_delta_body(model, channel, opt, n),
    "cluster_delta": lambda model, channel, es_channel, opt, M, n:
        sharded_cluster_delta_body(model, channel, opt, n),
    "multi": lambda model, channel, es_channel, opt, M, n:
        sharded_multi_body(model, channel, es_channel, opt, M, n),
}


# --------------------------------------------------------------------------
# the shard_map-wrapped chunk + plan rewriting
# --------------------------------------------------------------------------


@functools.cache
def sharded_chunk_fn(kind: str, model, channel, es_channel, opt, mesh: Mesh,
                     clusters: int | None, clients: int):
    """jit(shard_map(scan-over-rounds)) for one (body, mesh) pair — the
    sharded hot loop `run_scan` drives through `ScanPlan.chunk_fn`.  Cached
    so repeated runs of the same config/mesh (parity tests, sweeps of
    configs) compile once, exactly like `engine.scan_chunk_fn`.
    `clusters`/`clients` are the TRUE stacked widths the reductions slice
    gathered stacks back to (see the module docstring)."""
    body = _BODY_OF[kind](model, channel, es_channel, opt, clusters, clients)
    specs = fed_engine_pspecs(kind)
    # the chunk's xs stack the body's x under a leading rounds axis
    xs_specs = dict(specs["xs"])
    xs_specs["batch"] = P(None, *xs_specs["batch"])

    def chunk(carry, xs, consts):
        return jax.lax.scan(lambda c, x: body(c, x, consts), carry, xs)

    # replication checking is off: the bodies return all-gathered (hence
    # replicated) values the checker cannot prove replicated across the
    # un-gathered axis
    return _jit_round(
        jax.shard_map(
            chunk,
            mesh=mesh,
            in_specs=(specs["carry"], xs_specs, P()),
            out_specs=(specs["carry"], specs["ys"]),
            check_vma=False,
        )
    )


def _xs_shardings(xs: PyTree, kind: str, mesh: Mesh) -> PyTree:
    """NamedShardings mirroring one staged-xs pytree: batch leaves sharded on
    their client/cluster axes, schedule rows (gammas/mask/weights/subkeys)
    replicated."""
    batch_spec = fed_engine_pspecs(kind)["xs"]["batch"]
    chunk_batch = NamedSharding(mesh, P(None, *batch_spec))  # + leading chunk axis
    repl = NamedSharding(mesh, P())
    return {
        k: jax.tree.map(lambda _: chunk_batch if k == "batch" else repl, v)
        for k, v in xs.items()
    }


def shard_plan(plan: ScanPlan, mesh: Mesh, kind: str, *, model,
               channel=None, es_channel=None, opt=None,
               clients: int, clusters: int | None = None) -> ScanPlan:
    """Rewrite a single-device `ScanPlan` to execute on `mesh`.

    Pads the client (and, for "multi", cluster) axes of the staged inputs
    and the carry to mesh-divisible widths, installs the shard_map-wrapped
    chunk (`chunk_fn`) and the per-shard `device_put` (`xs_put`), and leaves
    everything else — schedule, recording, ledger glue — untouched.  The
    result is bit-identical to running `plan` unsharded (module docstring).
    """
    assert plan.obs is None, "telemetry is per-host state — unsupported on a mesh"
    assert kind in _BODY_OF, kind
    n_cl, n_ci = mesh.shape["clusters"], mesh.shape["clients"]

    if kind == "multi":
        assert clusters is not None
        M_pad = _ceil_to(clusters, n_cl)
        n_pad = _ceil_to(clients, n_ci)
    else:
        M_pad = None
        n_pad = _ceil_to(clients, n_cl * n_ci)

    stage0 = plan.stage

    def stage(idxs):
        xs = stage0(idxs)
        out = dict(xs)
        if kind == "multi":
            out["batch"] = jax.tree.map(
                lambda b: _pad_np(_pad_np(b, 3, n_pad, edge0=True),
                                  2, M_pad, edge0=True),
                xs["batch"],
            )
            for k in ("gammas", "mask"):
                out[k] = _pad_np(_pad_np(xs[k], 2, n_pad, edge0=False),
                                 1, M_pad, edge0=False)
            out["es_weights"] = _pad_np(xs["es_weights"], 1, M_pad, edge0=False)
            out["subs"] = _pad_np(xs["subs"], 2, M_pad, edge0=True)
            out["es_subs"] = _pad_np(xs["es_subs"], 1, M_pad, edge0=True)
        else:
            out["batch"] = jax.tree.map(
                lambda b: _pad_np(b, 2, n_pad, edge0=True), xs["batch"]
            )
            out["gammas"] = _pad_np(xs["gammas"], 1, n_pad, edge0=False)
            if "mask" in xs:
                out["mask"] = _pad_np(xs["mask"], 1, n_pad, edge0=False)
        return out

    # carry: params replicated; opt-state leaves sharded on their
    # client/cluster axes (padded slots replicate slot 0 — frozen by mask)
    specs = fed_engine_pspecs(kind)
    repl = NamedSharding(mesh, P())
    if kind == "grad":
        carry = jax.device_put(plan.carry, jax.tree.map(lambda _: repl, plan.carry))
    else:
        params, opt_state = plan.carry
        axis = 0 if kind == "delta" else 1  # client axis of the opt stack
        opt_state = jax.tree.map(lambda leaf: _pad_leaf(leaf, axis, n_pad), opt_state)
        if kind == "multi":
            opt_state = jax.tree.map(lambda leaf: _pad_leaf(leaf, 0, M_pad), opt_state)
        opt_ns = NamedSharding(mesh, specs["carry"][1])
        carry = (
            jax.device_put(params, jax.tree.map(lambda _: repl, params)),
            jax.device_put(opt_state, jax.tree.map(lambda _: opt_ns, opt_state)),
        )

    consts = jax.device_put(plan.consts, jax.tree.map(lambda _: repl, plan.consts))

    chunk_fn = sharded_chunk_fn(kind, model, channel, es_channel, opt, mesh,
                                clusters, clients)

    def xs_put(xs):
        return put_sharded(xs, _xs_shardings(xs, kind, mesh))

    return dataclasses.replace(
        plan, stage=stage, carry=carry, consts=consts,
        chunk_fn=chunk_fn, xs_put=xs_put,
    )

"""shard_map MoE interior: provably-local expert dispatch/combine.

Why this exists (EXPERIMENTS.md §Perf pair 1): under GSPMD the expert-choice
combine is a vmapped scatter-add whose locality XLA cannot prove, so it
resolves it as operand-replicated scatter + an all-reduce of the FULL
(N, d) activation over every mesh axis — ~2 TB/device/step at deepseek-v3
scale. Writing the interior with `jax.shard_map` makes the layout explicit:

  * tokens stay on their `data` shard end-to-end (gather and scatter-add are
    ordinary local ops on the shard's (n_loc, d) block);
  * each `model` shard owns E/n_model experts and runs expert-choice over its
    *local* tokens (shard-granular group-limited routing — the same
    approximation `moe_groups` makes, at G = n_data instead of G = B);
  * the ONLY communication is one psum over `model` of the (n_loc, d)
    partial outputs + the (n_loc,) gate mass — the Megatron-style row-sum,
    ~n_loc*d bytes/layer instead of the full-activation all-reduce.

Semantics match `ffn.moe_forward(method="expert_choice")` with batch-row
groups when each data shard holds exactly one group (tested in
tests/test_moe_shardmap.py at mesh (2,2)); at mesh (1,1) it is bit-identical
to global expert choice.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.common import activation


def shardmap_supported(cfg: ArchConfig, mesh, batch: int) -> bool:
    """Routed-expert shard_map needs divisible shards and a (data, model) mesh."""
    if mesh is None or "data" not in mesh.axis_names or "model" not in mesh.axis_names:
        return False
    n_data, n_model = mesh.shape["data"], mesh.shape["model"]
    return (
        cfg.num_experts > 0
        and cfg.num_experts % n_model == 0
        and batch % n_data == 0
    )


def moe_routed_shardmap(cfg: ArchConfig, p: dict, x, mesh, *,
                        capacity_factor: float = 1.0):
    """Routed-experts-only forward. x (B, T, d) -> (y (B, T, d), aux scalar).

    Shared experts / aux-coef scaling are applied by the caller
    (ffn.moe_forward) exactly as for the GSPMD paths.
    """
    B, T, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    n_model = mesh.shape["model"]
    E_loc = E // n_model
    act = activation(cfg.act)
    use_sigmoid = E > 32

    def interior(xb, router, w_gate, w_in, w_out):
        # xb (B_loc, T, d); router (d, E); w_* (E_loc, d, f) — local blocks.
        B_loc = xb.shape[0]
        n_loc = B_loc * T
        xf = xb.reshape(n_loc, d)
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.sigmoid(logits) if use_sigmoid else jax.nn.softmax(logits, -1)

        # load-balance aux: global mean prob per expert (psum over data shards)
        me = jax.lax.psum(jnp.sum(probs, axis=0), "data") / (
            n_loc * mesh.shape["data"]
        )
        aux = E * jnp.sum(me * me)

        # local expert-choice: this shard's E_loc experts pick their top-C
        # tokens among the shard's n_loc tokens.
        cap = max(1, int(n_loc * k * capacity_factor) // E)
        e0 = jax.lax.axis_index("model") * E_loc
        scores = jax.lax.dynamic_slice(
            probs, (0, e0), (n_loc, E_loc)
        ).T  # (E_loc, n_loc)
        g, idx = jax.lax.top_k(scores, cap)  # (E_loc, C)
        xe = jnp.take(xf, idx.reshape(-1), axis=0).reshape(E_loc, cap, d)

        h = jnp.einsum("ecd,edf->ecf", xe, w_gate)
        u = jnp.einsum("ecd,edf->ecf", xe, w_in)
        ye = jnp.einsum("ecf,efd->ecd", act(h) * u, w_out)
        ye = ye * g[..., None].astype(x.dtype)

        # local combine + the one collective: row-sum over the model axis
        y = jnp.zeros((n_loc, d), x.dtype).at[idx.reshape(-1)].add(
            ye.reshape(-1, d)
        )
        mass = jnp.zeros((n_loc,), jnp.float32).at[idx.reshape(-1)].add(
            g.reshape(-1)
        )
        y = jax.lax.psum(y, "model")
        mass = jax.lax.psum(mass, "model")
        y = y / jnp.maximum(mass, 1e-9)[:, None].astype(x.dtype)
        return y.reshape(B_loc, T, d), aux

    axes = tuple(mesh.axis_names)  # may include "pod"; unmentioned axes replicate

    def rep(*spec):
        # pad a spec to full rank with Nones on unmentioned (leading) axes
        return P(*spec)

    y, aux = jax.shard_map(
        interior,
        mesh=mesh,
        in_specs=(
            rep("data", None, None),     # x: batch over data, repl. over model
            rep(None, None),             # router replicated
            rep("model", None, None),    # expert weights: E over model
            rep("model", None, None),
            rep("model", None, None),
        ),
        out_specs=(rep("data", None, None), rep()),
        check_vma=False,  # aux is replicated by construction (psum over data)
    )(x, p["router"], p["w_gate"], p["w_in"], p["w_out"])
    return y, aux

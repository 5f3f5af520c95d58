"""In-graph telemetry taps: pure-JAX training-health reductions.

These run *inside* the jitted round bodies (and inside `lax.scan`), so they
must be pure functions of tensors already present in the round — they add
new reduction ops that read existing values but never feed back into the
parameter/optimizer path, keeping the tapped graph's training outputs
bit-identical to the untapped one (pinned by tests/test_engine_parity.py).

Conventions: every tap returns a dict of f32 scalars (or (M,) per-cluster
vectors in multi-cluster mode) with keys

  update_norm — mean per-client L2 norm of the local update Δ_n
  drift       — client-drift dispersion, mean_n ‖Δ_n − Δ̄‖, where Δ̄ is
                the round's applied per-unit-weight aggregate when the
                engine provides it (see delta_taps) and the mean raw delta
                otherwise; the non-IID divergence signal Fed-CHS's
                sequential ES→ES pass is meant to tame
  comp_err    — L2 error the uplink channel injects into the APPLIED
                aggregate, ‖Σ_n γ_n (C(Δ_n) − Δ_n)‖ (0 for DenseChannel)
  mass        — effective participation mass: number of clients whose
                aggregation weight is nonzero this round
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def tree_sq_norms(tree) -> jax.Array:
    """Per-client squared L2 norms: leaves have a leading client axis N."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(
        jnp.sum(jnp.reshape(x.astype(jnp.float32) ** 2, (x.shape[0], -1)), axis=1)
        for x in leaves
    )


def tree_client_norms(tree) -> jax.Array:
    """Per-client L2 norms over a stacked update pytree -> (N,)."""
    return jnp.sqrt(tree_sq_norms(tree))


def _flat_clients(tree) -> list[jax.Array]:
    """Leaves as f32 (n, d_leaf) matrices (leading client axis kept)."""
    return [jnp.reshape(x.astype(jnp.float32), (x.shape[0], -1))
            for x in jax.tree_util.tree_leaves(tree)]


def delta_taps(raw, applied, gammas, mask=None) -> dict[str, jax.Array]:
    """Taps for a delta-mode interaction: raw per-client deltas Δ_n, the
    interaction's APPLIED net update `applied` = new_params − params
    (= Σγ C(Δ_n) recovered from the scan carry, param-shaped, no client
    axis), and the aggregation weights γ_n (zero for non-participants).
    `mask` (n,) excludes padded / dropped-out slots from the means (their
    deltas are already exact zeros — without the mask they would dilute
    the health signals toward 0).

    The taps run inside the scanned hot loop, so both the tensors they
    read and every extra pass over the n×d client deltas cost round time
    (not measured on the chip):

    - the only per-client tree read is `raw` (materialised in the round
      regardless).  The channel output C(Δ_n) is deliberately NOT an
      input: reading it would force its dequantised form to materialise
      per interaction instead of fusing into the aggregation einsum, and
      reading the aggregate Σγ C(Δ_n) itself adds a consumer to the
      parameter-path einsum that shifts XLA's fusion choices by ~1 ulp,
      breaking the tapped==untapped bit-identity contract
      (tests/test_engine_parity.py).  `new_params − params` touches only
      scan-carry tensors, which are materialisation points already;
    - per-client squared norms and the γ-weighted raw sum R = Σγ Δ_n are
      elementwise sweeps (the client axis of R is a short unrolled FMA
      chain, not a reduction op) that fuse together;
    - drift centres on the applied per-unit-weight update Δ̄ = A / Σγ —
      arguably the more meaningful reference than the plain mean (how far
      do raw client updates disperse around the update the server
      actually applied) — via
      ‖Δ_n − Δ̄‖² = ‖Δ_n‖² − 2⟨Δ_n, A⟩/Σγ + ‖A‖²/Σγ², where the
      per-client inner products are `nd,d->n` matrix–vector einsums, the
      one contraction shape XLA:CPU lowers to a fast GEMV (batched
      `nd,nd->n` dots, `n×n` Gram matmuls, and the transposed `n,nd->d`
      weighted mean all lower to loops ~8× slower here, and a
      materialised centred copy of the deltas is worse still);
    - comp_err = ‖A − R‖ compares two d-sized vectors instead of taking a
      per-client mean over a materialised error tree.  Because A rides
      through the params carry, a lossless channel reads as a small
      floating-point residual (~ulp(params)) rather than an exact 0.

    Masked slots get garbage drift values (clamped at 0) but carry
    w_n = 0, so they never reach the output."""
    flat = _flat_clients(raw)
    n = flat[0].shape[0]
    sq = sum(jnp.sum(m * m, axis=1) for m in flat)
    if mask is None:
        mask = jnp.ones(sq.shape, sq.dtype)
    w = mask / jnp.maximum(jnp.sum(mask), 1.0)
    raw_agg = [sum(gammas[k] * m[k] for k in range(n)) for m in flat]
    agg_flat = [jnp.reshape(a.astype(jnp.float32), (-1,))
                for a in jax.tree_util.tree_leaves(applied)]
    ip_agg = sum(jnp.einsum("nd,d->n", m, a) for m, a in zip(flat, agg_flat))
    agg_sq = sum(jnp.einsum("d,d->", a, a) for a in agg_flat)
    denom = jnp.maximum(jnp.sum(gammas), jnp.finfo(jnp.float32).tiny)
    drift_sq = jnp.maximum(
        sq - 2.0 * ip_agg / denom + agg_sq / (denom * denom), 0.0)
    err_sq = sum(jnp.sum((a - r) ** 2)
                 for a, r in zip(agg_flat, raw_agg))
    return {
        "update_norm": jnp.sum(jnp.sqrt(sq) * w),
        "drift": jnp.sum(jnp.sqrt(drift_sq) * w),
        "comp_err": jnp.sqrt(err_sq),
        "mass": jnp.sum((gammas > 0).astype(jnp.float32)),
    }


def grad_taps(params, new_params, gammas) -> dict[str, jax.Array]:
    """Taps for grad-mode rounds (one SGD step, dense wire): the update is
    the whole-round parameter motion; there is no per-client delta or
    channel, so drift/comp_err are structurally zero."""
    step = jax.tree.map(lambda a, b: a - b, new_params, params)
    norm = jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                        for x in jax.tree_util.tree_leaves(step)))
    zero = jnp.zeros((), jnp.float32)
    return {
        "update_norm": norm,
        "drift": zero,
        "comp_err": zero,
        "mass": jnp.sum((gammas > 0).astype(jnp.float32)),
    }

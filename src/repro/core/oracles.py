"""Seed-parity local-update oracles — the ONE implementation of local SGD.

Before this module the repo carried three near-identical copies of the local
update loop (`engine._local_sgd`, `simulation._local_sgd_fn` /
`_multi_client_local_sgd_fn`, and the Eq.-(5) phase `_cluster_sgd_fn`).  They
now all live here, built from two generic factories:

  * `local_opt_steps(model, opt)` — E local optimizer steps for ONE client
    over a batch *pytree* (leaves ``(E, B, ...)``), threading the client-held
    `LocalOpt` state through the scan.  With the default `PlainSGD` the scan
    body is the exact ``w - lr * g`` expression the seed drivers ran, which
    is what keeps fixed-seed trajectories bit-identical (the contract in
    tests/test_engine_parity.py).
  * `grad_phase(model)` — the Eq. (5) literal: scan over K joint steps of
    ``w <- w - eta_k * sum_n gamma_n grad_n(w, xi_{n,k})``.

The jitted classifier-signature wrappers below (`local_sgd`,
`multi_client_local_sgd`, `cluster_sgd`) keep the historical
``(params, xs, ys, lrs)`` calling convention for the parity tests' reference
loops.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.classifier import Classifier
from repro.models.fed import FedModel, as_fed_model
from repro.optim.local import LocalOpt, PlainSGD

PyTree = Any


def local_opt_steps(model: FedModel, opt: LocalOpt):
    """E local steps for one client: batch leaves (E, B, ...), lrs (E,).

    Returns ``run(params, opt_state, batch, lrs) -> (params, opt_state,
    mean_loss)``; the opt state is the client's private carry — it never
    appears in the uplink deltas the engine computes from the params."""
    grad_fn = jax.value_and_grad(model.loss)

    def run(params, opt_state, batch, lrs):
        def step(carry, inp):
            p, s = carry
            b, lr = inp
            loss, g = grad_fn(p, b)
            p, s = opt.step(p, s, g, lr)
            return (p, s), loss

        (params, opt_state), losses = jax.lax.scan(step, (params, opt_state), (batch, lrs))
        return params, opt_state, jnp.mean(losses)

    return run


def grad_phase(model: FedModel, microbatch: int | None = None):
    """Eq. (5) literal: scan over K steps of
    w <- w - eta_k * sum_n gamma_n grad_n(w, xi_{n,k}).
    batch leaves: (K, n, B, ...); gammas: (n,); lrs: (K,).
    Returns (params, per-step gamma-weighted losses (K,)).

    `microbatch` bounds how many clients' forward/backward passes are live at
    once: the all-clients vmap becomes a `lax.scan` over ceil(n/microbatch)
    client groups (tail group padded with client-0 replicas, sliced off before
    aggregation).  The per-step gradient STACK (n, ...) is still materialized
    — Eq. (5) aggregates all n gradients jointly, and the stack feeds the
    very same einsum — but activation memory drops from O(n) to
    O(microbatch) model evaluations, which is the dominant term for LMs.
    The per-client grads are not bit-invariant to the group width: XLA:CPU
    folds the vmapped clients into the shapes of the matmuls, and a GEMM of
    another shape may sum in another order (~1e-7 in a grad at width 1).
    So params match the vmapped path to ≤1 ulp per step, and exactly at
    microbatch >= n (pinned by tests/test_engine_parity.py)."""
    grad_fn = jax.vmap(jax.value_and_grad(model.loss), in_axes=(None, 0))

    if microbatch is None:
        per_step = grad_fn
    else:
        mb = int(microbatch)
        assert mb >= 1

        def per_step(p, b_k):
            n = jax.tree.leaves(b_k)[0].shape[0]
            pad = (-n) % mb
            if pad:
                b_k = jax.tree.map(
                    lambda a: jnp.concatenate(
                        [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])]
                    ),
                    b_k,
                )
            b_g = jax.tree.map(
                lambda a: a.reshape(((n + pad) // mb, mb) + a.shape[1:]), b_k
            )

            def group(_, b_j):
                return None, grad_fn(p, b_j)

            _, (losses, grads) = jax.lax.scan(group, None, b_g)
            losses = losses.reshape(n + pad)[:n]
            grads = jax.tree.map(
                lambda a: a.reshape((n + pad,) + a.shape[2:])[:n], grads
            )
            return losses, grads

    def phase(params, batch, gammas, lrs):
        def step(p, inp):
            b_k, lr_k = inp
            losses, grads = per_step(p, b_k)
            agg = jax.tree.map(lambda g: jnp.einsum("n,n...->...", gammas, g), grads)
            p = jax.tree.map(lambda w, g: w - lr_k * g, p, agg)
            return p, jnp.dot(gammas, losses)

        return jax.lax.scan(step, params, (batch, lrs))

    return phase


# --------------------------------------------------------------------------
# jitted classifier-signature oracles (seed parity tests + benchmarks)
# --------------------------------------------------------------------------


def _classifier_local(model: Classifier):
    run = local_opt_steps(as_fed_model(model), PlainSGD())

    def fn(params, xs, ys, lrs):
        p, _, loss = run(params, (), {"x": xs, "y": ys}, lrs)
        return p, loss

    return fn


@functools.cache
def local_sgd(model: Classifier):
    """E plain local SGD steps for ONE client: xs (E, B, ...), ys (E, B), lrs (E,)."""
    return jax.jit(_classifier_local(model))


@functools.cache
def multi_client_local_sgd(model: Classifier):
    """`local_sgd` vmapped over a leading client axis (same E, B)."""
    return jax.jit(jax.vmap(_classifier_local(model), in_axes=(None, 0, 0, None)))


@functools.cache
def cluster_sgd(model: Classifier):
    """One Eq.(5) in-cluster phase: xs (K, n, B, ...), ys (K, n, B),
    gammas (n,), lrs (K,). Returns (params, mean loss over steps/clients)."""
    phase = grad_phase(as_fed_model(model))

    def fn(params, xs, ys, gammas, lrs):
        p, losses = phase(params, {"x": xs, "y": ys}, gammas, lrs)
        return p, jnp.mean(losses)

    return jax.jit(fn)

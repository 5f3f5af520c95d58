"""Small shared utilities: pytree arithmetic, rng splitting, size accounting."""
from __future__ import annotations

import functools
import os
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

# the checkout root (src/repro/utils.py -> ../..): a fixed cache path, since
# the path is part of every cache key and a moving directory never hits
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first compile.

    `JAX_COMPILATION_CACHE_DIR`, when set, is the cache (JAX reads it itself
    and nothing is set here); otherwise the cache lives in `.jax_cache` at the
    root of the checkout.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def tree_zeros_like(tree: PyTree) -> PyTree:
    return jax.tree.map(jnp.zeros_like, tree)


def tree_add(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.add, a, b)


def tree_sub(a: PyTree, b: PyTree) -> PyTree:
    return jax.tree.map(jnp.subtract, a, b)


def tree_scale(tree: PyTree, s) -> PyTree:
    return jax.tree.map(lambda x: x * s, tree)


def tree_axpy(alpha, x: PyTree, y: PyTree) -> PyTree:
    """alpha * x + y."""
    return jax.tree.map(lambda xi, yi: alpha * xi + yi, x, y)


def tree_weighted_sum(trees: list[PyTree], weights) -> PyTree:
    """sum_i weights[i] * trees[i] — the ES aggregation primitive (Eq. 5)."""
    assert len(trees) == len(weights) and trees, "empty aggregation"
    acc = tree_scale(trees[0], weights[0])
    for t, w in zip(trees[1:], weights[1:]):
        acc = tree_axpy(w, t, acc)
    return acc


def tree_dot(a: PyTree, b: PyTree):
    leaves = jax.tree.map(lambda x, y: jnp.vdot(x, y), a, b)
    return jax.tree.reduce(jnp.add, leaves)


def tree_sq_norm(tree: PyTree):
    return tree_dot(tree, tree)


def tree_num_params(tree: PyTree) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(tree)))


def tree_num_bytes(tree: PyTree) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize for x in jax.tree.leaves(tree)))


def tree_any_nan(tree: PyTree) -> bool:
    return bool(any(bool(jnp.isnan(x).any()) for x in jax.tree.leaves(tree)))


def split_like(key: jax.Array, tree: PyTree) -> PyTree:
    """One PRNG key per leaf, same structure."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, list(keys))


def cached_jit(fn: Callable, **jit_kwargs) -> Callable:
    return functools.lru_cache(maxsize=None)(lambda: jax.jit(fn, **jit_kwargs))

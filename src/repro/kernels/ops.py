"""jit'd public wrappers around the Pallas kernels: flat-vector / pytree QSGD.

These handle padding to whole tiles, flattening, and pytree mapping; the
kernels themselves (qsgd.py) only see dense (n_blocks, block) tiles.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.kernels.qsgd import (
    ROWS_PER_TILE,
    _pack_words,
    _unpack_words,
    qsgd_dequantize_blocks,
    qsgd_quantize_blocks,
    qsgd_quantize_pack_blocks,
    qsgd_unpack_dequantize_blocks,
)
from repro.kernels.ref import (
    qsgd_code_bits,
    qsgd_dequantize_blocks_ref,
    qsgd_dequantize_codes_ref,
    qsgd_quantize_blocks_ref,
    qsgd_quantize_codes_ref,
    signsgd_dequantize_codes_ref,
    signsgd_quantize_codes_ref,
)

PyTree = Any
DEFAULT_BLOCK = 1024


def _use_pallas() -> bool:
    # Off-TPU the Pallas kernels run in interpret mode (a grid-step loop of
    # dynamic slices — orders of magnitude slower than fused XLA, and worse
    # still under vmap). The pure-jnp oracle is bit-identical (enforced by
    # tests/test_kernels_qsgd.py), so route through it everywhere but TPU.
    return jax.default_backend() == "tpu"


def _pad_to_blocks(v: jnp.ndarray, block: int, rows_per_tile: int):
    n = v.size
    per_tile = block * rows_per_tile
    flat = v.reshape(-1).astype(jnp.float32)
    if n % per_tile:  # whole-tile sizes skip the pad copy entirely
        padded = ((n + per_tile - 1) // per_tile) * per_tile
        flat = jnp.zeros((padded,), jnp.float32).at[:n].set(flat)
    return flat.reshape(-1, block), n


def _cheap_uniform(key: jax.Array, shape: tuple) -> jnp.ndarray:
    """Stochastic-rounding dither: uniform on the 16-bit grid {k / 65536}.

    `jax.random.uniform` (threefry2x32: 20 mixing rounds per 4 output words)
    was ~95% of qsgd_quantize's CPU runtime at n=1M.  The dither only needs to
    be (a) deterministic in `key` and position, (b) uniform, (c) decorrelated
    across positions and across nearby keys — a keyed murmur3-fmix32 counter
    hash (two avalanche rounds, 12 int ops per word) delivers that at ~6x the
    throughput, and every 32-bit word yields TWO 16-bit dither samples.
    u = half / 65536 quantizes the rounding probability to 2^-16 — far below
    QSGD's own quantization variance, so unbiasedness tests are unaffected.
    Depends only on (key, size): scale-invariance of Q(v) is preserved.  NOT
    a general-purpose RNG — use only where the consumer is floor(p + u).
    """
    n = math.prod(shape)
    nw = (n + 1) // 2
    kd = key if key.dtype == jnp.uint32 else jax.random.key_data(key)
    x = jax.lax.iota(jnp.uint32, nw) ^ kd[0]
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    # second keyed avalanche: PRNGKey(i) streams differ only in kd[1], and one
    # fmix round after the xor is what decorrelates those streams
    x = x ^ (x >> 16) ^ kd[1]
    x = (x ^ (x >> 16)) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> 13)) * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    # interleave the halves with stack+reshape: XLA:CPU fuses it into the
    # elementwise chain, where a concatenate materializes both operands (~4x)
    halves = jnp.stack([x & jnp.uint32(0xFFFF), x >> 16], axis=1).reshape(-1)[:n]
    return (halves.astype(jnp.float32) * (1.0 / 65536.0)).reshape(shape)


@functools.partial(jax.jit, static_argnames=("s", "block"))
def qsgd_quantize(v: jnp.ndarray, key: jax.Array, *, s: int = 16, block: int = DEFAULT_BLOCK):
    """Quantize an arbitrary-shape f32 array. Returns (q, norms, orig_size)."""
    blocks, n = _pad_to_blocks(v, block, ROWS_PER_TILE)
    u = _cheap_uniform(key, blocks.shape)
    if _use_pallas():
        q, norms = qsgd_quantize_blocks(blocks, u, s=s)
    else:
        q, norms = qsgd_quantize_blocks_ref(blocks, u, s)
    return q, norms, n


@functools.partial(jax.jit, static_argnames=("s", "shape", "block"))
def qsgd_dequantize(q, norms, *, s: int = 16, shape: tuple = (), block: int = DEFAULT_BLOCK):
    import numpy as np

    if _use_pallas():
        flat = qsgd_dequantize_blocks(q, norms, s=s).reshape(-1)
    else:
        flat = qsgd_dequantize_blocks_ref(q, norms, s).reshape(-1)
    n = int(np.prod(shape)) if shape else flat.size
    return flat[:n].reshape(shape)


def qsgd_roundtrip(v: jnp.ndarray, key: jax.Array, *, s: int = 16, block: int = DEFAULT_BLOCK):
    """quantize -> dequantize (the lossy channel a message actually traverses)."""
    q, norms, _ = qsgd_quantize(v, key, s=s, block=block)
    return qsgd_dequantize(q, norms, s=s, shape=tuple(v.shape), block=block)


# --------------------------------------------------------------------------
# packed wire format: fused quantize→pack / unpack→dequantize
# --------------------------------------------------------------------------
# On TPU the fused Pallas kernels run; elsewhere the fallback is the same
# *vectorized* jnp pack/unpack the kernels use internally (`_pack_words` /
# `_unpack_words`: one iota + per-plane reduction, no python-per-bit index
# loops) composed with the oracle's vectorized quantize math — bit-identical
# to the naive `ref.pack_codes_ref` oracle (pinned by tests) but XLA-fusable.


def _leaf_blocks(n: int, block: int) -> int:
    return max(1, math.ceil(n / block))


@functools.partial(jax.jit, static_argnames=("s", "block"))
def qsgd_encode(v: jnp.ndarray, key: jax.Array, *, s: int = 16, block: int = DEFAULT_BLOCK):
    """Encode one leaf to its wire form: {'payload': uint32 (nb, b*block/32),
    'norms': f32 (nb,)} with nb = ceil(v.size / block) blocks *per leaf* —
    block boundaries never depend on anything outside this leaf, so stacking,
    padding, or concatenating messages cannot shift them.
    """
    # named_scope tags every op with op_name=".../qsgd_encode/..." so a
    # device trace attributes the quantize+pack cost to the encode
    with jax.named_scope("qsgd_encode"):
        n = v.size
        nb = _leaf_blocks(n, block)
        flat = v.reshape(-1).astype(jnp.float32)
        if n != nb * block:
            flat = jnp.zeros((nb * block,), jnp.float32).at[:n].set(flat)
        blocks = flat.reshape(nb, block)
        u = _cheap_uniform(key, blocks.shape)
        if _use_pallas():
            payload, norms = qsgd_quantize_pack_blocks(blocks, u, s=s)
        else:
            codes, norms = qsgd_quantize_codes_ref(blocks, u, s)
            payload = _pack_words(codes, qsgd_code_bits(s))
        return {"payload": payload, "norms": norms}


@functools.partial(jax.jit, static_argnames=("s", "shape", "block"))
def qsgd_decode(wire, *, s: int = 16, shape: tuple = (), block: int = DEFAULT_BLOCK):
    """Receiver side: unpack + dequantize a wire dict back to a (shape) f32 leaf."""
    with jax.named_scope("qsgd_decode"):
        payload, norms = wire["payload"], wire["norms"]
        if _use_pallas():
            blocks = qsgd_unpack_dequantize_blocks(payload, norms, s=s, block=block)
        else:
            codes = _unpack_words(payload, qsgd_code_bits(s))
            blocks = qsgd_dequantize_codes_ref(codes, norms, s)
        n = math.prod(shape) if shape else blocks.size
        return blocks.reshape(-1)[:n].reshape(shape)


def qsgd_encode_tree(tree: PyTree, key: jax.Array, *, s: int = 16,
                     block: int = DEFAULT_BLOCK) -> list:
    """Encode every leaf of a message pytree; returns wire dicts in leaf order
    (the packed payloads + norm sidecars are the values that cross a channel)."""
    leaves, _ = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return [qsgd_encode(leaf, k, s=s, block=block) for leaf, k in zip(leaves, keys)]


def qsgd_decode_tree(wires: list, like: PyTree, *, s: int = 16,
                     block: int = DEFAULT_BLOCK) -> PyTree:
    """Decode wire dicts (leaf order) back into the structure/dtypes of `like`."""
    leaves, treedef = jax.tree.flatten(like)
    out = [
        qsgd_decode(w, s=s, shape=tuple(leaf.shape), block=block).astype(leaf.dtype)
        for w, leaf in zip(wires, leaves)
    ]
    return jax.tree.unflatten(treedef, out)


def qsgd_compress_tree(tree: PyTree, key: jax.Array, *, s: int = 16,
                       block: int = DEFAULT_BLOCK) -> PyTree:
    """The QSGD channel roundtrip: encode to the packed wire format, decode at
    the receiver. Leaf-wise with per-leaf PRNG keys."""
    return qsgd_decode_tree(qsgd_encode_tree(tree, key, s=s, block=block), tree,
                            s=s, block=block)


# -- sign-SGD (1-bit) ---------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block",))
def signsgd_encode(v: jnp.ndarray, *, block: int = DEFAULT_BLOCK):
    """1-bit sign codes + per-block mean-|v| scale. Deterministic (no key)."""
    with jax.named_scope("signsgd_encode"):
        n = v.size
        nb = _leaf_blocks(n, block)
        flat = v.reshape(-1).astype(jnp.float32)
        if n != nb * block:
            flat = jnp.zeros((nb * block,), jnp.float32).at[:n].set(flat)
        blocks = flat.reshape(nb, block)
        codes, scales = signsgd_quantize_codes_ref(blocks)
        return {"payload": _pack_words(codes, 1), "norms": scales}


@functools.partial(jax.jit, static_argnames=("shape", "block"))
def signsgd_decode(wire, *, shape: tuple = (), block: int = DEFAULT_BLOCK):
    with jax.named_scope("signsgd_decode"):
        codes = _unpack_words(wire["payload"], 1)
        blocks = signsgd_dequantize_codes_ref(codes, wire["norms"])
        n = math.prod(shape) if shape else blocks.size
        return blocks.reshape(-1)[:n].reshape(shape)


def signsgd_compress_tree(tree: PyTree, *, block: int = DEFAULT_BLOCK) -> PyTree:
    """Sign-SGD channel roundtrip, leaf-wise. Note the *padding* subtlety: the
    tail block's zero padding decodes to +scale like any non-negative entry,
    but those slots are sliced off before the leaf is rebuilt — and an all-zero
    leaf (e.g. a masked-out sender's delta) has scale 0 everywhere, so it
    decodes to exact zeros."""
    leaves, treedef = jax.tree.flatten(tree)
    out = [
        signsgd_decode(signsgd_encode(leaf, block=block), shape=tuple(leaf.shape),
                       block=block).astype(leaf.dtype)
        for leaf in leaves
    ]
    return jax.tree.unflatten(treedef, out)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_sparsify(v: jnp.ndarray, *, k: int) -> jnp.ndarray:
    """Keep the k largest-magnitude entries of v (any shape), zero the rest.

    The deterministic sparsification half of a Top-K channel: the receiver
    reconstructs the dense tensor from (value, index) pairs, so the lossy
    roundtrip is exactly this masking."""
    flat = v.reshape(-1)
    k = min(k, flat.size)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    mask = jnp.zeros_like(flat).at[idx].set(1.0)
    return (flat * mask).reshape(v.shape)


def topk_sparsify_tree(tree: PyTree, *, fraction: float) -> PyTree:
    """Whole-message Top-K: keep the ceil(fraction * total_size) largest-magnitude
    entries across ALL leaves of the pytree (one message = one flat vector), so
    the encoded size is exactly k (index, value) pairs over the full dimension."""
    import math

    leaves, treedef = jax.tree.flatten(tree)
    flat = jnp.concatenate([leaf.reshape(-1).astype(jnp.float32) for leaf in leaves])
    sparse = topk_sparsify(flat, k=max(1, math.ceil(fraction * flat.size)))
    out, off = [], 0
    for leaf in leaves:
        out.append(sparse[off : off + leaf.size].reshape(leaf.shape).astype(leaf.dtype))
        off += leaf.size
    return jax.tree.unflatten(treedef, out)

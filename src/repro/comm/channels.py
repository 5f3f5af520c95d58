"""Pluggable lossy communication channels.

A `Channel` owns BOTH sides of a message's cost model:
  * `compress(tree, key)` — the in-graph lossy transform a pytree message
    traverses (traceable under jit/vmap/scan, so the round engine can fuse it
    into the per-interaction loop);
  * `message_bits(num_params)` — the encoded size of one message, which is
    what `CommLedger` records.  Drivers never re-derive bit formulas.

Wire channels (QSGD, sign-SGD) additionally expose the split halves:
  * `encode(tree, key)` — sender side: per-leaf wire dicts
    `{"payload": uint32 (n_blocks, bits*block/32), "norms": f32 (n_blocks,)}`
    in leaf order.  The payload IS the cross-device value: its byte size is
    exactly `wire_bits(leaf_sizes) / 8`;
  * `decode(wires, like)` — receiver side, rebuilding `like`'s structure;
  * `wire_bits(leaf_sizes)` — the exact multi-leaf message size (blocks are
    per-leaf, so each leaf rounds up to whole blocks independently).
`compress` is exactly `decode ∘ encode` for these channels.

Channels are frozen dataclasses: hashable, so the engine can cache one
compiled round function per (model, channel) pair, and all quantization
hyper-parameters are static under jit.

`stochastic` tells the engine whether the channel consumes PRNG keys — the
drivers only advance their key chains for stochastic channels, which keeps
fixed-seed trajectories identical to the pre-engine implementations.

`per_message` declares how the channel treats a *stacked* uplink (the engine
hands it client deltas with a leading sender axis on every leaf): True means
each sender's message must be transformed independently (the engine vmaps
`compress` over that axis with per-sender `fold_in` keys).  Every lossy
channel here is per-message: QSGD/sign-SGD block boundaries are computed
per-leaf *within* one sender's message, so a sender's encoding can never
depend on how many other senders ride the same stacked uplink — that padding
invariance is what lets Fed-CHS+QSGD run under the whole-run scan on ragged
clusters.

Stochastic channels split their key per leaf internally (see
`qsgd_compress_tree`), so the historical bug class of reusing one subkey
across every layer of the model cannot reappear in a driver.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.comm.bits import (
    dense_message_bits,
    dtype_bits,
    packed_wire_bits,
    qsgd_code_bits,
    qsgd_message_bits,
    signsgd_message_bits,
    topk_message_bits,
)
from repro.kernels.ops import (
    DEFAULT_BLOCK,
    qsgd_compress_tree,
    qsgd_decode_tree,
    qsgd_encode_tree,
    signsgd_compress_tree,
    signsgd_decode,
    signsgd_encode,
    topk_sparsify_tree,
)

PyTree = Any


@runtime_checkable
class Channel(Protocol):
    """Lossy uplink abstraction: in-graph transform + bit accounting."""

    stochastic: bool
    per_message: bool

    def compress(self, tree: PyTree, key: jax.Array) -> PyTree:
        """Apply the lossy roundtrip (what the receiver decodes). Traceable."""
        ...

    def message_bits(self, num_params: int) -> int:
        """Encoded size in bits of one message of `num_params` parameters."""
        ...


def channel_wire_bits(channel: Channel, num_params: int, leaf_sizes=None) -> int:
    """The exact per-message bits a driver should put in the ledger: wire
    channels price the real multi-leaf payload (`wire_bits`); anything else
    falls back to the flat `message_bits` formula."""
    if leaf_sizes is not None and hasattr(channel, "wire_bits"):
        return channel.wire_bits(tuple(leaf_sizes))
    return channel.message_bits(num_params)


@dataclasses.dataclass(frozen=True)
class DenseChannel:
    """Uncompressed float transport.

    With the default ``wire_dtype=None`` the transform is the identity and
    `message_bits` prices `bits_per_param` per entry — byte-for-byte the
    historical dense channel.  Setting ``wire_dtype`` (e.g. ``"bfloat16"``
    from a `core.precision.Precision` policy) makes the wire real: `compress`
    round-trips every leaf through that dtype IN-GRAPH (so the lossy cast is
    part of the compiled round and moves the narrow tensors),
    `encode`/`decode`/`wire_bits` expose the exact payload the honesty test
    measures, and `bits_per_param` is overridden to the dtype's width — the
    ledger prices what actually travels, so a bf16 wire halves every dense
    message exactly."""

    bits_per_param: int = 32
    wire_dtype: str | None = None
    stochastic: bool = dataclasses.field(default=False, init=False)
    per_message: bool = dataclasses.field(default=False, init=False)

    def __post_init__(self):
        if self.wire_dtype is not None:
            # pricing follows the wire: the declared width is the dtype's
            object.__setattr__(self, "bits_per_param", dtype_bits(self.wire_dtype))

    def compress(self, tree: PyTree, key: jax.Array) -> PyTree:
        if self.wire_dtype is None:
            return tree
        wire = jnp.dtype(self.wire_dtype)
        with jax.named_scope("wire_cast"):
            return jax.tree.map(lambda a: a.astype(wire).astype(a.dtype), tree)

    def message_bits(self, num_params: int) -> int:
        return dense_message_bits(num_params, self.bits_per_param)

    # -- wire-channel surface (only meaningful with a wire_dtype; the f32
    # default is its own wire: encode is then a per-leaf identity) ----------

    def encode(self, tree: PyTree, key: jax.Array = None) -> list:
        wire = jnp.dtype(self.wire_dtype or "float32")
        with jax.named_scope("wire_encode"):
            return [{"payload": leaf.astype(wire)} for leaf in jax.tree.leaves(tree)]

    def decode(self, wires: list, like: PyTree) -> PyTree:
        leaves, treedef = jax.tree.flatten(like)
        with jax.named_scope("wire_decode"):
            return jax.tree.unflatten(
                treedef,
                [w["payload"].astype(leaf.dtype) for w, leaf in zip(wires, leaves)],
            )

    def wire_bits(self, leaf_sizes) -> int:
        return sum(n * self.bits_per_param for n in leaf_sizes)


@dataclasses.dataclass(frozen=True)
class QSGDChannel:
    """QSGD stochastic quantization (Alistarh et al., 2017), Pallas-backed,
    carrying the packed integer wire format in-graph.

    `levels` is the number of quantization levels s; `encode` emits, per leaf,
    a dense uint32 payload of ceil(log2(2s+1))-bit sign-folded codes plus a
    per-block f32 norm sidecar (fused quantize→pack kernel on TPU, vectorized
    jnp elsewhere).  levels=7 is the 4-bit variant, levels=1 the 2-bit
    (ternary) variant — see `low_bit_channel`.
    """

    levels: int = 16
    block: int = DEFAULT_BLOCK
    stochastic: bool = dataclasses.field(default=True, init=False)
    per_message: bool = dataclasses.field(default=True, init=False)

    def encode(self, tree: PyTree, key: jax.Array) -> list:
        return qsgd_encode_tree(tree, key, s=self.levels, block=self.block)

    def decode(self, wires: list, like: PyTree) -> PyTree:
        return qsgd_decode_tree(wires, like, s=self.levels, block=self.block)

    def compress(self, tree: PyTree, key: jax.Array) -> PyTree:
        return qsgd_compress_tree(tree, key, s=self.levels, block=self.block)

    def message_bits(self, num_params: int) -> int:
        return qsgd_message_bits(num_params, self.levels, self.block)

    def wire_bits(self, leaf_sizes) -> int:
        return packed_wire_bits(leaf_sizes, qsgd_code_bits(self.levels), self.block)


@dataclasses.dataclass(frozen=True)
class SignSGDChannel:
    """1-bit sign-SGD with per-block norm scaling (Bernstein et al., 2018):
    each entry travels as its sign bit, decoded as ±(mean |v| of its block).
    Deterministic — no PRNG — and per-message like QSGD; the payload packs 32
    entries per uint32 word with an f32 scale sidecar per block."""

    block: int = DEFAULT_BLOCK
    stochastic: bool = dataclasses.field(default=False, init=False)
    per_message: bool = dataclasses.field(default=True, init=False)

    def encode(self, tree: PyTree, key: jax.Array = None) -> list:
        leaves, _ = jax.tree.flatten(tree)
        return [signsgd_encode(leaf, block=self.block) for leaf in leaves]

    def decode(self, wires: list, like: PyTree) -> PyTree:
        leaves, treedef = jax.tree.flatten(like)
        out = [
            signsgd_decode(w, shape=tuple(leaf.shape), block=self.block).astype(leaf.dtype)
            for w, leaf in zip(wires, leaves)
        ]
        return jax.tree.unflatten(treedef, out)

    def compress(self, tree: PyTree, key: jax.Array) -> PyTree:
        return signsgd_compress_tree(tree, block=self.block)

    def message_bits(self, num_params: int) -> int:
        return signsgd_message_bits(num_params, self.block)

    def wire_bits(self, leaf_sizes) -> int:
        return packed_wire_bits(leaf_sizes, 1, self.block)


@dataclasses.dataclass(frozen=True)
class TopKChannel:
    """Deterministic magnitude Top-K sparsification.

    Keeps the ceil(fraction * d) largest-magnitude entries of the WHOLE
    message (all leaves flattened as one d-vector); the encoding is exactly k
    (index, value) pairs of ceil(log2(d)) + bits_per_param bits each, so
    `message_bits` is exact. Top-K selection couples entries, so the channel
    is `per_message`: the engine applies it to each sender's delta
    independently. Proof that the channel stack extends beyond the paper's
    QSGD arm.
    """

    fraction: float = 0.01
    bits_per_param: int = 32
    stochastic: bool = dataclasses.field(default=False, init=False)
    per_message: bool = dataclasses.field(default=True, init=False)

    def compress(self, tree: PyTree, key: jax.Array) -> PyTree:
        return topk_sparsify_tree(tree, fraction=self.fraction)

    def message_bits(self, num_params: int) -> int:
        return topk_message_bits(num_params, self.fraction, self.bits_per_param)


def make_channel(qsgd_levels: int | None, bits_per_param: int = 32) -> Channel:
    """Back-compat shim: the (qsgd_levels, bits_per_param) config pair every
    algorithm historically exposed, as a Channel."""
    if qsgd_levels is None:
        return DenseChannel(bits_per_param)
    return QSGDChannel(qsgd_levels)


def low_bit_channel(bits: int) -> Channel:
    """The low-bit channel family by wire width: 8/4/2-bit packed QSGD
    (s = 127 / 7 / 1 — the largest s whose sign-folded code fits) or the
    1-bit sign-SGD channel."""
    try:
        return {8: QSGDChannel(127), 4: QSGDChannel(7), 2: QSGDChannel(1),
                1: SignSGDChannel()}[bits]
    except KeyError:
        raise ValueError(f"no {bits}-bit channel (choose 1, 2, 4, or 8)") from None

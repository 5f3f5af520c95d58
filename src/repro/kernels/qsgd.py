"""Pallas TPU kernels for QSGD stochastic quantization / dequantization.

TPU adaptation notes (vs the CPU/GPU reference implementations of QSGD):
  * the quantizer is memory-bound (one read of v, one write of q) — the kernel
    tiles the (n_blocks, block) layout into VMEM tiles of ROWS_PER_TILE x block
    so each grid step streams a contiguous HBM slab through VMEM once;
  * block = 1024 keeps the lane dimension a multiple of 128 (VPU lane width)
    and the per-row reduction (the block L2 norm) a single-lane-axis reduce;
  * stochastic rounding consumes an explicit uniform tensor (generated
    outside — see `ops._cheap_uniform`) instead of on-chip RNG — keeps the kernel a pure
    function, bit-identical to ref.py, and validated under interpret=True.

The fused quantize→pack / unpack→dequantize pair emits/consumes the packed
uint32 wire format defined (bit-for-bit) by `ref.pack_codes_ref`: sign-folded
codes, bit-plane packed, b = ceil(log2(2s+1)) bits per entry.  The kernels
never split or merge the lane axis: the wrappers hand them (rows, 32, W)
views of the blocks and (rows, b, W) views of the payload (free row-major
reshapes outside the kernel), so the pack is a reduction over the *sublane*
axis — every word sums 32 single-bit terms at distinct bit positions, so an
integer add is an exact bitwise OR.  Mosaic has no unsigned reductions and
no float→uint32 cast, so all code/word math runs in int32 (two's complement
makes the bit patterns identical) and the wrappers bitcast to the uint32
wire dtype.  Block norms are computed by the wrapper with the oracle's own
jnp expression and enter the quantize kernel as (rows, 1, 1) blocks, so the
norms sidecar equals `ref.py` bit for bit on every backend.  `s` and `bits`
are static closure args (functools.partial), not scalar operands, so the
per-bit loop unrolls at trace time.

All wrappers accept any n_blocks: tail tiles are handled by host-side
pad-to-ROWS_PER_TILE + slice (padding rows are all-zero -> zero norms -> the
kernel's zero-norm guard makes them inert), so arbitrary model dims never
trip a grid assert.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import qsgd_code_bits

ROWS_PER_TILE = 8  # 8 x 1024 f32 = 32 KiB per input tile; 4 tensors in flight << 16 MiB VMEM


def _auto_rows(n_blocks: int) -> int:
    """Tile height when the caller doesn't pin one: 8 rows (32 KiB tiles)
    keeps the tail-pad waste small for the many-small-leaves case; from 256
    blocks (1 MiB of input) up, 64-row tiles amortize the per-grid-step
    dispatch 8x while 4 tensors in flight still sit far under VMEM."""
    return 64 if n_blocks >= 256 else ROWS_PER_TILE


def _pad_rows(arrs, n_blocks: int, rows_per_tile: int):
    """Host-side tail-tile fix: zero-pad the leading (block-row) axis of every
    array to a multiple of rows_per_tile. Returns (padded arrays, padded rows)."""
    padded = ((n_blocks + rows_per_tile - 1) // rows_per_tile) * rows_per_tile
    if padded == n_blocks:
        return arrs, n_blocks
    out = [
        jnp.zeros((padded,) + a.shape[1:], a.dtype).at[:n_blocks].set(a) for a in arrs
    ]
    return out, padded


def _quantize_kernel(v_ref, u_ref, s_ref, q_ref, n_ref):
    v = v_ref[...]  # (rows, block) f32
    u = u_ref[...]
    s = s_ref[0]  # scalar f32 (levels)
    norms = jnp.sqrt(jnp.sum(v * v, axis=1, keepdims=True))  # (rows, 1)
    safe = jnp.where(norms > 0, norms, 1.0)
    p = jnp.abs(v) / safe * s
    q = jnp.clip(jnp.floor(p + u), 0.0, s)
    q = jnp.where(norms > 0, q, 0.0)
    q_ref[...] = (jnp.sign(v) * q).astype(jnp.int8)
    n_ref[...] = norms


def _dequantize_kernel(q_ref, n_ref, s_ref, v_ref):
    q = q_ref[...].astype(jnp.float32)
    norms = n_ref[...]
    s = s_ref[0]
    v_ref[...] = q * (norms / s)  # norms (rows, 1)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("s", "rows_per_tile"))
def qsgd_quantize_blocks(
    v: jnp.ndarray, u: jnp.ndarray, *, s: int, rows_per_tile: int | None = None
):
    """v, u: (n_blocks, block) f32 -> (q int8, norms f32). Any n_blocks."""
    n_blocks, block = v.shape
    rows_per_tile = rows_per_tile or _auto_rows(n_blocks)
    (v, u), padded = _pad_rows([v, u], n_blocks, rows_per_tile)
    grid = (padded // rows_per_tile,)
    s_arr = jnp.full((1,), float(s), jnp.float32)
    q, norms = pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_tile, block), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_tile, block), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((rows_per_tile, block), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_tile, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((padded, block), jnp.int8),
            jax.ShapeDtypeStruct((padded, 1), jnp.float32),
        ],
        interpret=_interpret(),
    )(v, u, s_arr)
    return q[:n_blocks], norms[:n_blocks, 0]


@functools.partial(jax.jit, static_argnames=("s", "rows_per_tile"))
def qsgd_dequantize_blocks(
    q: jnp.ndarray, norms: jnp.ndarray, *, s: int, rows_per_tile: int | None = None
):
    n_blocks, block = q.shape
    rows_per_tile = rows_per_tile or _auto_rows(n_blocks)
    (q, norms), padded = _pad_rows([q, norms], n_blocks, rows_per_tile)
    grid = (padded // rows_per_tile,)
    s_arr = jnp.full((1,), float(s), jnp.float32)
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_tile, block), lambda i: (i, 0)),
            pl.BlockSpec((rows_per_tile, 1), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((rows_per_tile, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, block), jnp.float32),
        interpret=_interpret(),
    )(q, norms[:, None], s_arr)
    return out[:n_blocks]


# --------------------------------------------------------------------------
# fused quantize→bit-pack / unpack→dequantize (the packed wire format)
# --------------------------------------------------------------------------


def _pack_planes(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(rows, 32, W) int32 codes -> (rows, bits, W) int32 words.

    Layout defined by `ref.pack_codes_ref`: the 32 codes of a word sit on the
    sublane axis; plane word j sums bit j of each at its own position (an
    exact OR; bit 31 wraps to the sign bit, the same 32-bit pattern)."""
    pos = jax.lax.broadcasted_iota(jnp.int32, codes.shape, 1)
    return jnp.concatenate(
        [jnp.sum(((codes >> j) & 1) << pos, axis=1, keepdims=True)
         for j in range(bits)], axis=1)


def _unpack_planes(words: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Exact inverse of `_pack_planes`: (rows, bits, W) -> (rows, 32, W)."""
    rows, _, w_per_plane = words.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, 32, w_per_plane), 1)
    c = jnp.zeros((rows, 32, w_per_plane), jnp.int32)
    for j in range(bits):
        c = c | (((words[:, j:j + 1, :] >> pos) & 1) << j)
    return c


def _pack_words(codes: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(rows, block) uint32 codes -> (rows, bits * block/32) uint32 payload."""
    rows, block = codes.shape
    planes = _pack_planes(codes.astype(jnp.int32).reshape(rows, 32, block // 32), bits)
    return jax.lax.bitcast_convert_type(planes, jnp.uint32).reshape(rows, -1)


def _unpack_words(payload: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Exact inverse of `_pack_words`: (rows, bits*W) uint32 -> (rows, 32*W)."""
    rows = payload.shape[0]
    words = jax.lax.bitcast_convert_type(payload, jnp.int32).reshape(rows, bits, -1)
    return _unpack_planes(words, bits).astype(jnp.uint32).reshape(rows, -1)


def _quantize_pack_kernel(v_ref, u_ref, n_ref, payload_ref, *, s: int, bits: int):
    v = v_ref[...]  # (rows, 32, W) f32
    norms = n_ref[...]  # (rows, 1, 1) f32
    safe = jnp.where(norms > 0, norms, 1.0)
    p = jnp.abs(v) / safe * s
    q = jnp.clip(jnp.floor(p + u_ref[...]), 0.0, float(s))
    q = jnp.where(norms > 0, q, 0.0)
    codes = (jnp.sign(v) * q + s).astype(jnp.int32)  # sign-folded, in [0, 2s]
    payload_ref[...] = _pack_planes(codes, bits)


def _unpack_dequantize_kernel(payload_ref, n_ref, v_ref, *, s: int, bits: int):
    q = _unpack_planes(payload_ref[...], bits) - s
    v_ref[...] = q.astype(jnp.float32) * (n_ref[...] / s)


@functools.partial(jax.jit, static_argnames=("s", "rows_per_tile"))
def qsgd_quantize_pack_blocks(
    v: jnp.ndarray, u: jnp.ndarray, *, s: int, rows_per_tile: int | None = None
):
    """Fused quantize + bit-pack: v, u (n_blocks, block) f32 ->
    (payload uint32 (n_blocks, bits*block/32), norms f32 (n_blocks,))."""
    n_blocks, block = v.shape
    rows_per_tile = rows_per_tile or _auto_rows(n_blocks)
    assert block % 32 == 0, block
    bits = qsgd_code_bits(s)
    w = block // 32
    norms = jnp.sqrt(jnp.sum(v * v, axis=1))  # the oracle's expression
    (v, u, n), padded = _pad_rows([v, u, norms], n_blocks, rows_per_tile)
    grid = (padded // rows_per_tile,)
    tile = pl.BlockSpec((rows_per_tile, 32, w), lambda i: (i, 0, 0))
    payload = pl.pallas_call(
        functools.partial(_quantize_pack_kernel, s=s, bits=bits),
        grid=grid,
        in_specs=[tile, tile, pl.BlockSpec((rows_per_tile, 1, 1), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((rows_per_tile, bits, w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, bits, w), jnp.int32),
        interpret=_interpret(),
    )(v.reshape(padded, 32, w), u.reshape(padded, 32, w), n.reshape(padded, 1, 1))
    payload = jax.lax.bitcast_convert_type(payload, jnp.uint32).reshape(padded, bits * w)
    return payload[:n_blocks], norms


@functools.partial(jax.jit, static_argnames=("s", "block", "rows_per_tile"))
def qsgd_unpack_dequantize_blocks(
    payload: jnp.ndarray,
    norms: jnp.ndarray,
    *,
    s: int,
    block: int,
    rows_per_tile: int | None = None,
):
    """Fused unpack + dequantize: (n_blocks, bits*block/32) uint32 payload +
    (n_blocks,) f32 norms -> (n_blocks, block) f32."""
    n_blocks = payload.shape[0]
    rows_per_tile = rows_per_tile or _auto_rows(n_blocks)
    bits = qsgd_code_bits(s)
    w = block // 32
    assert payload.shape[1] == bits * w, (payload.shape, bits, block)
    (payload, norms), padded = _pad_rows([payload, norms], n_blocks, rows_per_tile)
    grid = (padded // rows_per_tile,)
    out = pl.pallas_call(
        functools.partial(_unpack_dequantize_kernel, s=s, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows_per_tile, bits, w), lambda i: (i, 0, 0)),
            pl.BlockSpec((rows_per_tile, 1, 1), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_tile, 32, w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, 32, w), jnp.float32),
        interpret=_interpret(),
    )(jax.lax.bitcast_convert_type(payload, jnp.int32).reshape(padded, bits, w),
      norms.reshape(padded, 1, 1))
    return out.reshape(padded, block)[:n_blocks]

"""Pallas TPU flash-attention kernels (q-blocked causal/windowed GQA): the
forward, and the two backward kernels of its VJP.

Forward (`flash_attention`): grid (B * H, T / BLOCK_Q).  Each program holds
one BLOCK_Q x hd query tile and its KV head's whole (S, hd) K and V slabs
in VMEM (2 * S * hd * 2 bytes in bf16: 4 MiB at S 8,192, hd 128, twice that
double-buffered), and loops over the key blocks of BLOCK_K rows that the
query block sees, keeping an f32 online softmax per row (running max m, row
sum l, accumulator).  The loop bounds (`_key_blocks`, shared with the
backward dq kernel): hi = min(cdiv(S, BLOCK_K), last_q // BLOCK_K + 1)
under `causal`, lo = max(first_q - window + 1, 0) // BLOCK_K under
`window`, every block otherwise.  `scored_block_share` counts the blocks
scored: at T = S = 2,048, 62.5% in 256 x 512 blocks (136 of 256 in 128 x
128).  Every block scored is masked.  Operands are upcast to f32.  Run as
the forward of the VJP (`return_lse=True`) it also writes each row's
logsumexp.  Tiles: DEFAULT_BLOCK_Q x DEFAULT_BLOCK_K = 256 x 512, the
fastest of {128, 256, 512}^2 in a chip sweep at T = S = 2,048, 16/8 heads,
hd 128 (PERF.md).

Backward (`flash_attention_bwd`): both kernels recompute
P = exp(Q K^T * scale - lse) block by block from the saved logsumexp, and
skip the blocks the causal mask and `window` leave empty.
  * `flash_bwd_dq`: grid (B * H, T / BLOCK_Q), the kv-head's K and V slabs in
    VMEM as in the forward; loops over the KV blocks a query block sees.
  * `flash_bwd_dkv`: grid (B * Hkv, S / BLOCK_K), the Q and dO slabs of the
    g = H / Hkv query heads that share the KV head in VMEM; loops over the
    query blocks that see the KV block.
Matmul operands are in the dtype the kernels are given, products accumulate
in f32; lse, delta = rowsum(dO * O) and dS are f32.

GQA: query head h reads kv head h // (H // Hkv) via the BlockSpec index maps
— no head replication in memory.

Used on the training/prefill path of every `use_flash` model
(`models.attention._flash_attention_ad`); the blockwise online-softmax
attention in models/attention.py, and its VJP, are the non-flash path and
the oracle these kernels are validated against (interpret mode off the TPU).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# the forward's query and key blocks (chip sweep at T=S=2048, hd 128: PERF.md)
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
# the backward's query and KV blocks (chip sweep at T=S=2048, hd 128: PERF.md)
BWD_BLOCK_Q = 512
BWD_BLOCK_K = 512
NEG_INF = -1e30
LANES = 128


def _mask(q_pos, k_pos, *, causal: bool, window: int | None, seq_len: int):
    mask = k_pos < seq_len                          # padding
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return mask


def _row(col):
    """(n, 1) -> (1, n), through a lane broadcast and a 2-D transpose."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1]


def _col(row):
    """(1, n) -> (n, 1), the inverse of `_row`."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


def _dot_t(a, b):
    """a @ b.T with f32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _key_blocks(first_q, last_q, *, causal: bool, window: int | None, seq_len: int,
                block_k: int):
    """The key blocks [lo, hi) that query rows first_q..last_q see: every
    block that holds a key < `seq_len` the causal mask and `window` leave
    to one of the rows.  Takes ints or traced int32 scalars."""
    lo, hi = 0, pl.cdiv(seq_len, block_k)
    if causal:
        hi = jnp.minimum(hi, last_q // block_k + 1)
    if window is not None:
        first_k = jnp.maximum(first_q - window + 1, 0)
        lo = jnp.where(first_k < seq_len, first_k // block_k, hi)
    return lo, hi


def _fwd_blocks(T: int, S: int, block_q: int, block_k: int):
    """The forward's blocks: each cut to its sequence rounded up to 128."""
    return min(block_q, pl.cdiv(T, 128) * 128), min(block_k, pl.cdiv(S, 128) * 128)


def scored_block_share(T: int, S: int, *, causal: bool = True, window: int | None = None,
                       block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K) -> float:
    """The share of a call's (query block, key block) pairs that the forward
    scores: those holding at least one (q, k) pair the mask leaves, with
    query rows < T.  The kernel's own loop bounds (`_key_blocks`)."""
    block_q, block_k = _fwd_blocks(T, S, block_q, block_k)
    scored = 0
    for first in range(0, T, block_q):
        lo, hi = _key_blocks(first, min(first + block_q, T) - 1, causal=causal,
                             window=window, seq_len=S, block_k=block_k)
        scored += int(hi) - int(lo)
    return scored / (pl.cdiv(T, block_q) * pl.cdiv(S, block_k))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *lse_ref, scale: float, causal: bool,
                  window: int | None, seq_len: int, q_len: int, block_q: int, block_k: int):
    first = pl.program_id(1) * block_q
    last = jnp.minimum(first + block_q, q_len) - 1  # rows past T are not scored
    lo, hi = _key_blocks(first, last, causal=causal, window=window, seq_len=seq_len,
                         block_k=block_k)
    q = q_ref[...].astype(jnp.float32) * scale      # (bq, hd)
    q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, carry):
        m, l, acc = carry  # noqa: E741 — flash-attn's row-sum name
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[pl.ds(start, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(start, block_k), :].astype(jnp.float32)
        s = _dot_t(q, k)                            # (bq, bk)
        # every block is masked: scoring the blocks inside the mask without
        # it, in a loop of their own, measured slower on the chip (PERF.md)
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(_mask(q_pos, k_pos, causal=causal, window=window, seq_len=seq_len),
                      s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # A row that sees no key of an early block keeps m = NEG_INF and
        # gathers p = 1 per key; its first real score makes alpha
        # exp(NEG_INF - m_new) = 0, which wipes that from l and acc.
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                alpha * acc + jnp.dot(p, v, preferred_element_type=jnp.float32))

    m, l, acc = jax.lax.fori_loop(  # noqa: E741
        lo, hi, body, (jnp.full((block_q, 1), NEG_INF, jnp.float32),
                       jnp.zeros((block_q, 1), jnp.float32),
                       jnp.zeros(q.shape, jnp.float32)))
    l = jnp.maximum(l, 1e-30)  # noqa: E741
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    if lse_ref:
        lse_ref[0][...] = _row(m + jnp.log(l))


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _heads_first(x, pad: int):
    """(B, L, n, hd) -> (B * n, L + pad, hd)."""
    B, L, n, hd = x.shape
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return x.transpose(0, 2, 1, 3).reshape(B * n, L + pad, hd)


def _heads_last(x, B: int, L: int):
    """(B * n, Lp, hd) -> (B, L, n, hd)."""
    n, Lp, hd = x.shape[0] // B, x.shape[1], x.shape[2]
    return x.reshape(B, n, Lp, hd).transpose(0, 2, 1, 3)[:, :L]


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "return_lse")
)
def flash_attention(q, k, v, *, causal: bool = True, window: int | None = None,
                    block_q: int = DEFAULT_BLOCK_Q, block_k: int = DEFAULT_BLOCK_K,
                    return_lse: bool = False):
    """q (B,T,H,hd); k/v (B,S,Hkv,hd) -> (B,T,H,hd).  A block is cut to its
    sequence rounded up to 128; T is padded to `block_q` and S to `block_k`
    inside.

    With `return_lse` also each row's logsumexp of the scaled, masked scores,
    (B, H, T) f32: what `flash_attention_bwd` recomputes P from."""
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    assert H % Hkv == 0
    g = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    block_q, block_k = _fwd_blocks(T, S, block_q, block_k)
    pad_t, pad_s = (-T) % block_q, (-S) % block_k
    Tp, Sp = T + pad_t, S + pad_s
    qh, kh, vh = _heads_first(q, pad_t), _heads_first(k, pad_s), _heads_first(v, pad_s)

    grid = (B * H, Tp // block_q)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        seq_len=S, q_len=T, block_q=block_q, block_k=block_k,
    )
    o_spec = pl.BlockSpec((None, block_q, hd), lambda bh, iq: (bh, iq, 0))
    o_shape = jax.ShapeDtypeStruct((B * H, Tp, hd), q.dtype)
    if return_lse:
        # 4-D so that the custom call's 3-D shapes stay (out, q, k, v)
        o_spec = [o_spec, pl.BlockSpec((None, None, 1, block_q),
                                       lambda bh, iq: (bh, 0, 0, iq))]
        o_shape = [o_shape, jax.ShapeDtypeStruct((B * H, 1, 1, Tp), jnp.float32)]

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, hd), lambda bh, iq: (bh, iq, 0)),
            pl.BlockSpec((None, Sp, hd), lambda bh, iq, g=g: (bh // g, 0, 0)),
            pl.BlockSpec((None, Sp, hd), lambda bh, iq, g=g: (bh // g, 0, 0)),
        ],
        out_specs=o_spec,
        out_shape=o_shape,
        name="flash_attention",      # the custom call's name under a VJP too
        interpret=_interpret(),
    )(qh, kh, vh)

    if not return_lse:
        return _heads_last(out, B, T)
    out, lse = out
    return _heads_last(out, B, T), lse.reshape(B, H, Tp)[:, :, :T]


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   scale: float, causal: bool, window: int | None, seq_len: int,
                   block_q: int, block_k: int):
    iq = pl.program_id(1)
    q, do = q_ref[...], do_ref[...]                 # (bq, hd)
    lse, delta = _col(lse_ref[...]), _col(delta_ref[...])  # (bq, 1)
    first = iq * block_q
    lo, hi = _key_blocks(first, first + block_q - 1, causal=causal, window=window,
                         seq_len=seq_len, block_k=block_k)
    q_pos = first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(j, acc):
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[pl.ds(start, block_k), :]
        v = v_ref[pl.ds(start, block_k), :]
        s = _dot_t(q, k) * scale                    # (bq, bk)
        k_pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(_mask(q_pos, k_pos, causal=causal, window=window, seq_len=seq_len),
                      s, NEG_INF)
        p = jnp.exp(s - lse)
        ds = p * (_dot_t(do, v) - delta)
        return acc + jnp.dot(ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    acc = jax.lax.fori_loop(lo, hi, body, jnp.zeros(q.shape, jnp.float32))
    dq_ref[...] = (acc * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, do_ref, lse_ref, delta_ref, k_ref, v_ref, dk_ref, dv_ref, *,
                    scale: float, causal: bool, window: int | None, seq_len: int,
                    q_len: int, block_q: int, block_k: int):
    ik = pl.program_id(1)
    k, v = k_ref[...], v_ref[...]                   # (bk, hd)
    first, last = ik * block_k, ik * block_k + block_k - 1
    lo = first // block_q if causal else 0
    hi = pl.cdiv(q_len, block_q)                    # rows past T carry no gradient
    if window is not None:
        hi = jnp.minimum(hi, (last + window - 1) // block_q + 1)
    k_pos = first + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)

    def body(i, carry):
        dk, dv = carry
        start = pl.multiple_of(i * block_q, block_q)
        q_pos = start + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 1)
        mask = _mask(q_pos, k_pos, causal=causal, window=window, seq_len=seq_len)
        for h in range(q_ref.shape[0]):             # the g query heads of this KV head
            q = q_ref[h, pl.ds(start, block_q), :]  # (bq, hd)
            do = do_ref[h, pl.ds(start, block_q), :]
            lse = lse_ref[pl.ds(h, 1), pl.ds(start, block_q)]      # (1, bq)
            delta = delta_ref[pl.ds(h, 1), pl.ds(start, block_q)]
            st = jnp.where(mask, _dot_t(k, q) * scale, NEG_INF)    # (bk, bq)
            pt = jnp.exp(st - lse)
            dv += jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
            dst = pt * (_dot_t(v, do) - delta)
            dk += jnp.dot(dst.astype(q.dtype), q, preferred_element_type=jnp.float32)
        return dk, dv

    zero = jnp.zeros(k.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, hi, body, (zero, zero))
    dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k")
)
def flash_attention_bwd(q, k, v, o, lse, do, *, causal: bool = True,
                        window: int | None = None, block_q: int = BWD_BLOCK_Q,
                        block_k: int = BWD_BLOCK_K):
    """The VJP of `flash_attention`: (dq, dk, dv) from q, k, v, the output o,
    the rows' logsumexp `lse` (B, H, T) that `flash_attention(...,
    return_lse=True)` wrote, and the output cotangent do. A block is cut to
    its sequence rounded up to 128; T is padded to `block_q` and S to
    `block_k` inside."""
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    block_q = min(block_q, pl.cdiv(T, 128) * 128)
    block_k = min(block_k, pl.cdiv(S, 128) * 128)
    pad_t, pad_s = (-T) % block_q, (-S) % block_k
    Tp, Sp = T + pad_t, S + pad_s

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)  # (B, T, H)
    rows = lambda x: jnp.pad(x, ((0, 0), (0, 0), (0, pad_t))).reshape(B * H, Tp)  # noqa: E731
    lse, delta = rows(lse), rows(delta.transpose(0, 2, 1))
    qh, doh = _heads_first(q, pad_t), _heads_first(do, pad_t)
    kh, vh = _heads_first(k, pad_s), _heads_first(v, pad_s)
    opts = dict(scale=scale, causal=causal, window=window, seq_len=S,
                block_q=block_q, block_k=block_k)

    q_blk = pl.BlockSpec((None, block_q, hd), lambda bh, iq: (bh, iq, 0))
    kv_slab = pl.BlockSpec((None, Sp, hd), lambda bh, iq, g=g: (bh // g, 0, 0))
    row_blk = pl.BlockSpec((None, 1, block_q), lambda bh, iq: (bh, 0, iq))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **opts),
        grid=(B * H, Tp // block_q),
        in_specs=[q_blk, kv_slab, kv_slab, q_blk, row_blk, row_blk],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct((B * H, Tp, hd), q.dtype),
        name="flash_bwd_dq",
        interpret=_interpret(),
    )(qh, kh, vh, doh, lse.reshape(B * H, 1, Tp), delta.reshape(B * H, 1, Tp))

    q_slab = pl.BlockSpec((None, g, Tp, hd), lambda bkv, ik: (bkv, 0, 0, 0))
    row_slab = pl.BlockSpec((None, g, Tp), lambda bkv, ik: (bkv, 0, 0))
    kv_blk = pl.BlockSpec((None, block_k, hd), lambda bkv, ik: (bkv, ik, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, q_len=T, **opts),
        grid=(B * Hkv, Sp // block_k),
        in_specs=[q_slab, q_slab, row_slab, row_slab, kv_blk, kv_blk],
        out_specs=[kv_blk, kv_blk],
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, Sp, hd), k.dtype),
                   jax.ShapeDtypeStruct((B * Hkv, Sp, hd), v.dtype)],
        name="flash_bwd_dkv",
        interpret=_interpret(),
    )(qh.reshape(B * Hkv, g, Tp, hd), doh.reshape(B * Hkv, g, Tp, hd),
      lse.reshape(B * Hkv, g, Tp), delta.reshape(B * Hkv, g, Tp), kh, vh)

    return _heads_last(dq, B, T), _heads_last(dk, B, S), _heads_last(dv, B, S)

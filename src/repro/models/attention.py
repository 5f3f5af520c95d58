"""Attention blocks: GQA (full / sliding-window) and DeepSeek MLA.

Training/prefill attention is *blockwise* (online-softmax over KV chunks via
lax.scan) so a 32k-token prefill never materialises the (T, T) score matrix —
the TPU-native equivalent of flash attention, and the shape the Pallas fast
path in repro/kernels/flash_attention.py mirrors. Decode attends one query
against a fixed-capacity cache (full or ring-buffered sliding window).

With `cfg.use_flash` the training/prefill path routes through the Pallas
kernels instead (`_flash_attention_ad`): the forward is the fused q-blocked
kernel, which also writes each row's logsumexp when it runs under a VJP, and
the backward is two Pallas kernels that recompute the softmax from it (no
(T, S) residuals saved).  `blockwise_attention` and its VJP stay the path
without flash and the oracle the kernels are tested against
(tests/test_kernels_flash.py).

Shapes: x (B, T, D); q (B, T, H, hd); kv (B, S, Hkv, hd); caches (B, S, Hkv, hd).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import apply_rope, dense_init, rms_norm, rope_angles

NEG_INF = -1e30


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------


def init_attention(cfg: ArchConfig, key, dtype) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype=dtype),
        "wk": dense_init(ks[1], d, hkv * hd, dtype=dtype),
        "wv": dense_init(ks[2], d, hkv * hd, dtype=dtype),
        "wo": dense_init(ks[3], h * hd, d, dtype=dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def init_mla(cfg: ArchConfig, key, dtype) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 8)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {
        "wkv_a": dense_init(ks[2], d, m.kv_lora_rank + m.qk_rope_head_dim, dtype=dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
        "wkv_b": dense_init(
            ks[3], m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim), dtype=dtype
        ),
        "wo": dense_init(ks[4], h * m.v_head_dim, d, dtype=dtype),
    }
    if m.q_lora_rank:
        p["wq_a"] = dense_init(ks[0], d, m.q_lora_rank, dtype=dtype)
        p["q_norm"] = jnp.ones((m.q_lora_rank,), dtype)
        p["wq_b"] = dense_init(ks[1], m.q_lora_rank, h * qk_head, dtype=dtype)
    else:
        p["wq"] = dense_init(ks[0], d, h * qk_head, dtype=dtype)
    return p


# --------------------------------------------------------------------------
# blockwise (flash-style) attention core
# --------------------------------------------------------------------------


def blockwise_attention(
    q, k, v, *, causal: bool, window: int | None = None, kv_block: int = 512,
    q_offset: int = 0,
):
    """Online-softmax attention. q (B,T,H,hd), k/v (B,S,Hkv,hd) -> (B,T,H,hd).

    Never materialises (T,S); scans over S in `kv_block` chunks keeping
    running (max, sum, acc). GQA: H % Hkv == 0, kv heads broadcast.
    `q_offset`: absolute position of q[0] (for prefill q==kv it is 0).
    """
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]  # MLA: value head dim may differ from qk head dim
    assert H % Hkv == 0
    g = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    # pad S to a multiple of kv_block
    pad = (-S) % kv_block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nblk = Sp // kv_block

    qf = (q * scale).astype(jnp.float32).reshape(B, T, Hkv, g, hd)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(T)

    kb = kf.reshape(B, nblk, kv_block, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vb = vf.reshape(B, nblk, kv_block, Hkv, hd_v).transpose(1, 0, 2, 3, 4)

    def step(carry, inp):
        m_prev, l_prev, acc = carry
        kblk, vblk, blk_idx = inp  # (B, kv_block, Hkv, hd)
        kv_pos = blk_idx * kv_block + jnp.arange(kv_block)
        s = jnp.einsum("bthgd,bshd->bthgs", qf, kblk)  # (B,T,Hkv,g,kv_block)
        mask = jnp.broadcast_to(kv_pos[None, :] < S, (T, kv_pos.shape[0]))  # padding
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        if window is not None:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bthgs,bshd->bthgd", p, vblk)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, T, Hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, T, Hkv, g), jnp.float32)
    a0 = jnp.zeros((B, T, Hkv, g, hd_v), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, T, H, hd_v).astype(q.dtype)


@functools.cache
def _flash_attention_ad(causal: bool, window: int | None):
    """Differentiable flash attention: the Pallas forward kernel, and the
    Pallas backward kernels of `kernels.flash_attention.flash_attention_bwd`.
    Run as the VJP's forward, the kernel also writes each row's logsumexp;
    the backward recomputes P from it block by block.  Residuals are q, k,
    v, the output and the logsumexp: activation memory stays O(T·hd), never
    O(T·S), which is the whole point of putting flash on the training path.
    Called without a VJP (eval, prefill) it is the forward kernel alone."""
    from repro.kernels.flash_attention import flash_attention, flash_attention_bwd

    @jax.custom_vjp
    def fa(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window)

    def fwd(q, k, v):
        o, lse = flash_attention(q, k, v, causal=causal, window=window, return_lse=True)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        return flash_attention_bwd(*res, do, causal=causal, window=window)

    fa.defvjp(fwd, bwd)
    return fa


def decode_attention(q, k_cache, v_cache, cache_len, *, window: int | None = None):
    """Single-step decode: q (B,1,H,hd) vs cache (B,S,Hkv,hd); positions
    >= cache_len are masked. Sliding-window caches are ring buffers, so all
    live entries are valid and `window` masking is already structural."""
    B, T, H, hd = q.shape
    S, Hkv = k_cache.shape[1], k_cache.shape[2]
    g = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    qf = (q * scale).astype(jnp.float32).reshape(B, T, Hkv, g, hd)
    s = jnp.einsum("bthgd,bshd->bthgs", qf, k_cache.astype(jnp.float32))
    pos = jnp.arange(S)
    valid = pos[None, :] < cache_len[:, None]  # cache_len: (B,)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bthgs,bshd->bthgd", p, v_cache.astype(jnp.float32))
    return out.reshape(B, T, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------
# GQA block apply
# --------------------------------------------------------------------------


def _project_qkv(cfg: ArchConfig, p, x, positions):
    B, T, _ = x.shape
    h, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, T, h, hd)
    k = k.reshape(B, T, hkv, hd)
    v = v.reshape(B, T, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def attention_forward(cfg: ArchConfig, p, x, *, window: int | None = None):
    """Training / prefill self-attention (causal)."""
    B, T, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    q, k, v = _project_qkv(cfg, p, x, positions)
    if cfg.use_flash:
        out = _flash_attention_ad(True, window)(q, k, v)
    else:
        out = blockwise_attention(q, k, v, causal=True, window=window)
    return out.reshape(B, T, -1) @ p["wo"]


def attention_decode(cfg: ArchConfig, p, x, cache: dict, *, window: int | None = None):
    """One-token decode. cache = {"k": (B,S,Hkv,hd), "v": ..., "len": (B,)}.

    Full-attention caches write at index `len`; sliding-window caches are ring
    buffers written at `len % S`.
    """
    B, T, _ = x.shape
    assert T == 1
    positions = cache["len"][:, None]  # absolute position
    q, k, v = _project_qkv(cfg, p, x, positions)
    S = cache["k"].shape[1]
    slot = cache["len"] % S if window is not None else jnp.minimum(cache["len"], S - 1)
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
    v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
    new_len = cache["len"] + 1
    eff_len = jnp.minimum(new_len, S) if window is not None else new_len
    out = decode_attention(q, k_cache, v_cache, eff_len, window=window)
    y = out.reshape(B, T, -1) @ p["wo"]
    return y, {"k": k_cache, "v": v_cache, "len": new_len}


def init_attn_cache(cfg: ArchConfig, batch: int, capacity: int, dtype) -> dict:
    hkv, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, capacity, hkv, hd), dtype),
        "v": jnp.zeros((batch, capacity, hkv, hd), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }


# --------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# --------------------------------------------------------------------------


def _mla_q(cfg: ArchConfig, p, x, positions):
    m = cfg.mla
    B, T, _ = x.shape
    h = cfg.num_heads
    if m.q_lora_rank:
        q = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, T, h, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    cos, sin = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def mla_forward(cfg: ArchConfig, p, x):
    """Training/prefill MLA: materialise per-head K/V from the latent."""
    m = cfg.mla
    B, T, _ = x.shape
    h = cfg.num_heads
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    q_nope, q_rope = _mla_q(cfg, p, x, positions)

    kv_a = x @ p["wkv_a"]
    c_kv, k_rope = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    cos, sin = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    k_rope = apply_rope(k_rope.reshape(B, T, 1, m.qk_rope_head_dim), cos, sin)

    kv = (c_kv @ p["wkv_b"]).reshape(B, T, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (B, T, h, m.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    out = blockwise_attention(q, k, v, causal=True)
    return out.reshape(B, T, -1) @ p["wo"]


def mla_decode(cfg: ArchConfig, p, x, cache: dict):
    """Absorbed-form decode: the cache holds only (c_kv, k_rope) — MLA's point.

    score = q_nope^T W_ukT c_kv + q_rope^T k_rope;  out = (probs @ c_kv) W_uv.
    cache = {"c_kv": (B,S,r), "k_rope": (B,S,dr), "len": (B,)}.
    """
    m = cfg.mla
    B, T, _ = x.shape
    h = cfg.num_heads
    positions = cache["len"][:, None]
    q_nope, q_rope = _mla_q(cfg, p, x, positions)  # (B,1,h,*)

    kv_a = x @ p["wkv_a"]
    c_new, kr_new = jnp.split(kv_a, [m.kv_lora_rank], axis=-1)
    c_new = rms_norm(c_new, p["kv_norm"], cfg.norm_eps)
    cos, sin = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta)
    kr_new = apply_rope(kr_new.reshape(B, T, 1, m.qk_rope_head_dim), cos, sin)[:, :, 0]

    bidx = jnp.arange(B)
    S = cache["c_kv"].shape[1]
    slot = jnp.minimum(cache["len"], S - 1)
    c_kv = cache["c_kv"].at[bidx, slot].set(c_new[:, 0])
    k_rope = cache["k_rope"].at[bidx, slot].set(kr_new[:, 0])
    new_len = cache["len"] + 1

    w_uk, w_uv = jnp.split(
        p["wkv_b"].reshape(m.kv_lora_rank, h, m.qk_nope_head_dim + m.v_head_dim),
        [m.qk_nope_head_dim],
        axis=-1,
    )
    # absorb: q_abs (B,1,h,r)
    q_abs = jnp.einsum("bthd,rhd->bthr", q_nope, w_uk)
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    s = jnp.einsum("bthr,bsr->bths", q_abs, c_kv) + jnp.einsum(
        "bthd,bsd->bths", q_rope, k_rope
    )
    s = s.astype(jnp.float32) * scale
    valid = jnp.arange(S)[None, :] < new_len[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bths,bsr->bthr", probs, c_kv.astype(jnp.float32)).astype(x.dtype)
    out = jnp.einsum("bthr,rhd->bthd", ctx, w_uv)
    y = out.reshape(B, T, -1) @ p["wo"]
    return y, {"c_kv": c_kv, "k_rope": k_rope, "len": new_len}


def init_mla_cache(cfg: ArchConfig, batch: int, capacity: int, dtype) -> dict:
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, capacity, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, capacity, m.qk_rope_head_dim), dtype),
        "len": jnp.zeros((batch,), jnp.int32),
    }

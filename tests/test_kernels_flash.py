"""Flash-attention Pallas kernel vs the pure-jnp oracles (shape/dtype sweep)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import (flash_attention, flash_attention_bwd,
                                          scored_block_share)
from repro.models.attention import _flash_attention_ad, blockwise_attention


# the forward's tiles: as the default cuts them, and smaller and unequal
# both ways, so that T and S are multiples of neither block
BLOCKS = [dict(block_q=64), dict(block_q=64, block_k=32), dict(block_q=32, block_k=64)]
BLOCK_IDS = ["64-default", "64-32", "32-64"]


def _lse_oracle(q, k, *, causal, window):
    """Each row's logsumexp of the scaled, masked scores, (B, H, T), from the
    whole (T, S) score matrix."""
    B, T, H, hd = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    k = jnp.repeat(k, H // Hkv, axis=2)
    s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32), k.astype(jnp.float32),
                   precision="highest") / np.sqrt(hd)
    q_pos, k_pos = jnp.arange(T)[:, None], jnp.arange(S)[None]
    mask = jnp.ones((T, S), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)


def _check_forward(q, k, v, *, causal=True, window=None, **blocks):
    """The forward, with and without its logsumexp, against the blockwise
    oracle's output and the dense logsumexp."""
    want = np.asarray(blockwise_attention(q, k, v, causal=causal, window=window, kv_block=64))
    out = flash_attention(q, k, v, causal=causal, window=window, **blocks)
    np.testing.assert_allclose(np.asarray(out), want, atol=3e-5)
    out, lse = flash_attention(q, k, v, causal=causal, window=window, return_lse=True, **blocks)
    np.testing.assert_allclose(np.asarray(out), want, atol=3e-5)
    assert lse.shape == (q.shape[0], q.shape[2], q.shape[1]) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse),
                               np.asarray(_lse_oracle(q, k, causal=causal, window=window)),
                               atol=3e-5)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks", BLOCKS, ids=BLOCK_IDS)
@pytest.mark.parametrize("T,S", [(128, 128), (64, 256), (200, 200)])
@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2)])
def test_flash_matches_blockwise(T, S, H, Hkv, blocks, causal):
    key = jax.random.PRNGKey(T + S + H)
    B, hd = 2, 32
    q = jax.random.normal(key, (B, T, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, Hkv, hd), jnp.float32)
    _check_forward(q, k, v, causal=causal, **blocks)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("blocks", BLOCKS, ids=BLOCK_IDS)
@pytest.mark.parametrize("window", [1, 16, 64, 100, 192, 300])
def test_flash_sliding_window(window, blocks, causal):
    """Windows of one key, under, at and over `block_q`, and of S or more.
    With 64-row query blocks over 32-key blocks, a window under 64 leaves
    rows that see no key of the first block scored: they must not leak
    into the row sums."""
    key = jax.random.PRNGKey(7)
    B, T, H, Hkv, hd = 1, 192, 2, 1, 32
    q = jax.random.normal(key, (B, T, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, hd), jnp.float32)
    _check_forward(q, k, v, causal=causal, window=window, **blocks)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_dtypes(dtype):
    key = jax.random.PRNGKey(1)
    B, T, H, hd = 1, 64, 2, 64
    q = jax.random.normal(key, (B, T, H, hd)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, H, hd)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, H, hd)).astype(dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64)
    assert out.dtype == dtype and out.shape == q.shape
    ref = blockwise_attention(q, k, v, causal=True)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


@pytest.mark.parametrize("blocks", BLOCKS, ids=BLOCK_IDS)
def test_flash_nonaligned_shapes_padded(blocks):
    key = jax.random.PRNGKey(2)
    B, T, S, H, hd = 1, 50, 77, 2, 32  # neither T nor S aligned
    q = jax.random.normal(key, (B, T, H, hd), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, H, hd), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, H, hd), jnp.float32)
    _check_forward(q, k, v, **blocks)


def _scored_by_brute_force(T, S, block_q, block_k, causal, window):
    """The share of (query block, key block) pairs, over query rows < T,
    that hold at least one (q, k) pair the mask leaves."""
    q_pos, k_pos = np.arange(T)[:, None], np.arange(S)[None]
    mask = np.ones((T, S), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    nq, nk = -(-T // block_q), -(-S // block_k)
    tiles = np.zeros((nq * block_q, nk * block_k), bool)
    tiles[:T, :S] = mask
    return tiles.reshape(nq, block_q, nk, block_k).any(axis=(1, 3)).sum() / (nq * nk)


def test_scored_block_share_at_the_cell():
    """T = S = 2,048 in 128 x 128 blocks: the causal mask leaves 136 of 256."""
    assert scored_block_share(2048, 2048, block_q=128, block_k=128) == 136 / 256


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("window", [None, 1, 100, 128, 700, 5000])
@pytest.mark.parametrize("T,S", [(2048, 2048), (300, 1000), (1000, 300), (77, 50)])
def test_scored_block_share_matches_brute_force(T, S, window, causal):
    for block_q in (64, 128, 256, 512):
        for block_k in (64, 128, 256, 512):
            got = scored_block_share(T, S, causal=causal, window=window,
                                     block_q=block_q, block_k=block_k)
            # the kernel cuts each block to its sequence rounded up to 128
            bq, bk = min(block_q, -(-T // 128) * 128), min(block_k, -(-S // 128) * 128)
            assert got == _scored_by_brute_force(T, S, bq, bk, causal, window), (block_q, block_k)



# --------------------------------------------------------------------------
# the backward kernels: jax.grad through _flash_attention_ad vs the VJP of
# the blockwise oracle
# --------------------------------------------------------------------------


def _grads(fn, q, k, v, ct):
    loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * ct)  # noqa: E731
    return jax.grad(loss, (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("window", [None, 16, 64])
@pytest.mark.parametrize("T", [200, 50])              # neither a multiple of 128: padded
@pytest.mark.parametrize("H,Hkv", [(4, 4), (8, 2)])
def test_flash_backward_matches_blockwise_vjp(H, Hkv, T, window, dtype):
    key = jax.random.PRNGKey(T + H + (window or 0))
    B, hd = 1, 32
    q = jax.random.normal(key, (B, T, H, hd)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, hd)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, hd)).astype(dtype)
    ct = jax.random.normal(jax.random.fold_in(key, 3), (B, T, H, hd))
    got = _grads(_flash_attention_ad(True, window), q, k, v, ct)
    want = _grads(lambda q, k, v: blockwise_attention(q, k, v, causal=True, window=window),
                  q, k, v, ct)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        err = np.max(np.abs(a - b)) / np.max(np.abs(b))
        assert err <= tol, (name, err)


@pytest.mark.parametrize("window", [None, 40, 100])
@pytest.mark.parametrize("block_q,block_k", [(32, 64), (64, 32)])
def test_flash_backward_block_edges(block_q, block_k, window):
    """Blocks smaller than T and unequal, T padded: the first and last
    blocks each kernel's loop visits under the causal mask and window."""
    key = jax.random.PRNGKey(block_q + (window or 0))
    B, T, H, Hkv, hd = 1, 300, 4, 2, 32
    q = jax.random.normal(key, (B, T, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, T, Hkv, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, T, Hkv, hd))
    ct = jax.random.normal(jax.random.fold_in(key, 3), (B, T, H, hd))
    o, lse = flash_attention(q, k, v, window=window, return_lse=True)
    got = flash_attention_bwd(q, k, v, o, lse, ct, window=window,
                              block_q=block_q, block_k=block_k)
    _, vjp = jax.vjp(lambda q, k, v: blockwise_attention(q, k, v, causal=True, window=window),
                     q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, vjp(ct)):
        err = np.max(np.abs(np.asarray(a) - np.asarray(b))) / np.max(np.abs(np.asarray(b)))
        assert err <= 1e-5, (name, err)


def _toy_lm(flash: bool):
    from repro.configs.base import ArchConfig
    from repro.models.fed import LMFedModel

    cfg = ArchConfig(name="toy-lm", family="dense", num_layers=2, d_model=64, num_heads=4,
                     num_kv_heads=2, d_ff=128, vocab_size=64, qk_norm=True, dtype="bfloat16",
                     block_pattern=("attn", "local"), sliding_window=16)
    return LMFedModel(cfg, remat=True, flash=flash)


def _toy_batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 40), 0, 64)
    return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}


def test_lm_gradient_with_flash_matches_blockwise():
    """A 2-layer bf16 LM (one full-causal, one sliding-window block) under
    remat: every leaf's gradient within bf16 tolerance of the path without
    flash."""
    ref, fl = _toy_lm(False), _toy_lm(True)
    params, batch = ref.init(jax.random.PRNGKey(0)), _toy_batch()
    want = jax.jit(jax.grad(ref.loss))(params, batch)
    got = jax.jit(jax.grad(fl.loss))(params, batch)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 3e-2 * np.linalg.norm(b)


@pytest.mark.parametrize("flash", [True, False], ids=["flash", "blockwise"])
def test_only_the_path_without_flash_differentiates_the_blockwise_oracle(flash, monkeypatch):
    """With flash the gradient runs the Pallas kernels and never calls the
    blockwise oracle; without flash it runs the oracle and no kernel."""
    from repro.models import attention

    calls = []
    oracle = attention.blockwise_attention
    monkeypatch.setattr(attention, "blockwise_attention",
                        lambda *a, **kw: calls.append(1) or oracle(*a, **kw))
    model = _toy_lm(flash)
    params = model.init(jax.random.PRNGKey(0))
    jaxpr = str(jax.make_jaxpr(jax.grad(model.loss))(params, _toy_batch()))
    assert bool(calls) is not flash
    assert ("pallas_call" in jaxpr) is flash

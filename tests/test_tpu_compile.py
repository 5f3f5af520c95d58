"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with libtpu compiles
for a *described* v5e:2x2 topology, which refuses what Mosaic cannot lower
(unaligned blocks, unsupported casts or reductions, lane reshapes, VMEM
overflow) — faults that interpret mode, which every other test runs the
kernels in, cannot see.  Each compile must contain `tpu_custom_call`, i.e.
the kernel really lowered to Mosaic.

The topology is described inside a module fixture (never at import: only
one process may load libtpu, and every test worker imports this file), and
JAX's persistent compilation cache is off around these compiles — an entry
written for a described chip cannot be read back without one.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import ops, qsgd
from repro.kernels.ref import qsgd_code_bits

CUSTOM_CALL = "tpu_custom_call"
LEVELS = [1, 16, 127]
N_BLOCKS = [8, 300, 3072]  # one tile, a padded tail tile, a 1024x3072 leaf


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu writes logs to the temp dir
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no libtpu / no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Route every kernel wrapper to its TPU lowering (off interpret mode)."""
    monkeypatch.setattr(qsgd, "_interpret", lambda: False)
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(ops, "_use_pallas", lambda: True)


def _compiled(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n_blocks", N_BLOCKS)
@pytest.mark.parametrize("s", LEVELS)
def test_quantize_pack_compiles(one_chip, on_tpu, s, n_blocks):
    x = jax.ShapeDtypeStruct((n_blocks, 1024), jnp.float32, sharding=one_chip)
    hlo = _compiled(lambda v, u: qsgd.qsgd_quantize_pack_blocks(v, u, s=s), x, x)
    assert CUSTOM_CALL in hlo


@pytest.mark.parametrize("n_blocks", N_BLOCKS)
@pytest.mark.parametrize("s", LEVELS)
def test_unpack_dequantize_compiles(one_chip, on_tpu, s, n_blocks):
    payload = jax.ShapeDtypeStruct((n_blocks, qsgd_code_bits(s) * 32), jnp.uint32,
                                   sharding=one_chip)
    norms = jax.ShapeDtypeStruct((n_blocks,), jnp.float32, sharding=one_chip)
    hlo = _compiled(
        lambda p, n: qsgd.qsgd_unpack_dequantize_blocks(p, n, s=s, block=1024),
        payload, norms)
    assert CUSTOM_CALL in hlo


def test_vmapped_encode_compiles(one_chip, on_tpu):
    """The uplink as `engine.compress_uplinks` runs it: one qwen3 MLP leaf
    (1024x3072) encoded per sender, vmapped over 4 senders."""
    leaves = jax.ShapeDtypeStruct((4, 1024, 3072), jnp.float32, sharding=one_chip)
    keys = jax.ShapeDtypeStruct((4, 2), jnp.uint32, sharding=one_chip)
    hlo = _compiled(jax.vmap(lambda v, k: ops.qsgd_encode(v, k, s=16)), leaves, keys)
    assert CUSTOM_CALL in hlo


@pytest.mark.parametrize("return_lse", [False, True], ids=["out", "lse"])
@pytest.mark.parametrize("seq,window", [(128, None), (2048, None), (8192, 2048)])
def test_flash_attention_compiles(one_chip, on_tpu, seq, window, return_lse):
    """qwen3 heads: 16 query heads, 8 KV heads, head_dim 128, bf16, at the
    forward's default tiles; at 8,192 tokens under a 2,048-key window the
    tiled key loop holds whole K and V slabs of 8,192 rows in VMEM."""
    q = jax.ShapeDtypeStruct((1, seq, 16, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, 8, 128), jnp.bfloat16, sharding=one_chip)
    hlo = _compiled(lambda q, k, v: fa.flash_attention(q, k, v, window=window,
                                                       return_lse=return_lse), q, kv, kv)
    assert CUSTOM_CALL in hlo
    assert "%flash_attention" in hlo


@pytest.mark.parametrize("seq", [128, 2048])
def test_flash_attention_backward_compiles(one_chip, on_tpu, seq):
    """jax.grad through the flash path at the qwen3 heads: the forward and
    both backward kernels lower to Mosaic, and no f32 block of 512 scores
    (the blockwise oracle's scan) is left in the program."""
    from repro.models.attention import _flash_attention_ad

    q = jax.ShapeDtypeStruct((1, seq, 16, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, 8, 128), jnp.bfloat16, sharding=one_chip)
    loss = lambda q, k, v: jnp.sum(_flash_attention_ad(True, None)(q, k, v).astype(jnp.float32))  # noqa: E731
    hlo = _compiled(jax.grad(loss, (0, 1, 2)), q, kv, kv)
    assert CUSTOM_CALL in hlo
    for kernel in ("%flash_attention", "%flash_bwd_dq", "%flash_bwd_dkv"):
        assert kernel in hlo
    assert not re.search(r"f32\[[\d,]*,512\]", hlo)

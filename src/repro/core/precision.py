"""Precision — the mixed-precision policy of the round engine.

One frozen dataclass names the three dtypes a federated round touches:

  * ``compute`` — the dtype clients train in: forward/backward, local
    optimizer steps, and the raw deltas all run here.  bf16 halves the
    per-client params/activation footprint, which is what makes
    `client_microbatch` + remat land a 0.6B-param LM round on one host.
  * ``master`` — the dtype of the authoritative params held at the ES (the
    whole-run scan carry) and of the delta accumulator: client deltas are
    cast UP before the gamma-weighted aggregate, so rounding happens once
    per client message, not once per accumulation step.
  * ``wire`` — the dtype a dense uplink/broadcast travels in.  The engine
    does not consume this field directly; drivers build the matching
    `DenseChannel(wire_dtype=...)` from it (`dense_wire_channel`) and price
    the ledger off the channel, so recorded bits always match the payload.

The policy is threaded through the engine as a static (hashable) argument:
each `Precision` value compiles its own round function, and ``None`` keeps
the exact pre-mixed-precision f32 graphs byte-for-byte (the default-path
parity contract in tests/test_engine_parity.py).  Client-held optimizer
state follows ``compute`` — it is initialized from the compute-cast params —
so only the ES keeps f32 state; grad mode (the paper-literal Eq. (5) path)
ignores the policy entirely and the drivers' grad-mode gate excludes it.

The engine tags its casts with `jax.named_scope`: "precision_cast" (going
down) survives jit into compiled op_names (tests/test_obs.py), so a device
trace attributes the down-cast traffic directly.  The up-cast
("master_accumulate") fuses into the gamma-weighted aggregate einsum, whose
op_name carries the engine's "intra_agg" scope — so the accumulate cost of
a mixed-precision round lands there (the fused aggregate reads bf16 and
writes f32).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

# the dtype names a policy accepts (widths come from comm.bits.dtype_bits;
# pinned in sync by tests/test_channels.py::test_precision_dtype_table_sync)
_SUPPORTED = ("float32", "bfloat16", "float16", "float8_e4m3fn")


@dataclasses.dataclass(frozen=True)
class Precision:
    """Mixed-precision policy: compute / master / wire dtype names."""

    compute: str = "bfloat16"
    master: str = "float32"
    wire: str = "bfloat16"

    def __post_init__(self):
        for field in ("compute", "master", "wire"):
            dt = getattr(self, field)
            if dt not in _SUPPORTED:
                raise ValueError(
                    f"Precision.{field}={dt!r} not in {_SUPPORTED}")


def cast_floats(tree: PyTree, dtype) -> PyTree:
    """Cast every floating leaf of `tree` to `dtype` (ints/keys untouched)."""
    dt = jnp.dtype(dtype)

    def cast(leaf):
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating):
            return jnp.asarray(leaf).astype(dt)
        return leaf

    return jax.tree.map(cast, tree)


def compute_cast(tree: PyTree, precision: Precision | None) -> PyTree:
    """Params/batch/lr cast to the compute dtype, tagged for attribution.

    Identity (no ops inserted) when `precision` is None — the default path's
    graph must stay byte-for-byte the pre-mixed-precision round."""
    if precision is None:
        return tree
    with jax.named_scope("precision_cast"):
        return cast_floats(tree, precision.compute)


def master_cast(tree: PyTree, precision: Precision | None) -> PyTree:
    """Deltas cast up to the master dtype before accumulation, tagged."""
    if precision is None:
        return tree
    with jax.named_scope("master_accumulate"):
        return cast_floats(tree, precision.master)


def dense_wire_channel(precision: Precision):
    """The `DenseChannel` matching a policy's wire dtype: the uplink travels
    (and is priced) at ``precision.wire`` width — bf16 halves every dense
    message exactly (`comm.bits.dtype_bits`)."""
    from repro.comm.channels import DenseChannel

    return DenseChannel(wire_dtype=precision.wire)


def resolve_channel(precision: Precision | None, channel=None,
                    qsgd_levels: int | None = None, bits_per_param: int = 32):
    """The drivers' shared uplink-channel rule.  An explicit `channel` wins;
    a quantized config (`qsgd_levels`) wins over the policy wire (QSGD codes
    are already narrower than any float wire); otherwise a `precision`
    policy makes the dense uplink travel — and be priced — at wire width;
    else the historical dense channel, byte-for-byte."""
    from repro.comm.channels import make_channel

    if channel is not None:
        return channel
    if qsgd_levels is None and precision is not None:
        return dense_wire_channel(precision)
    return make_channel(qsgd_levels, bits_per_param)


def downlink_bits_per_param(precision: Precision | None,
                            bits_per_param: int = 32) -> int:
    """Width of a dense model broadcast (ES->client, ES->ES, ES<->PS): the
    policy's wire dtype when mixed precision is on — the server ships the
    compute-dtype model, so the ledger must price that — else the
    configured dense width."""
    from repro.comm.bits import dtype_bits

    return dtype_bits(precision.wire) if precision is not None else bits_per_param

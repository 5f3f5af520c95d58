"""Operations and bytes the work needs, counted from a configuration's shapes.

These are the benchmark's yardstick: they count what the model and the wire
format require, not what an implementation happens to execute, so a change
that does the same work in fewer operations is measured by the same count.
Recomputed (rematerialised) operations are not counted.
"""
from __future__ import annotations

import math


def lm_matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix multiply per token: the layers'
    projections and MLP, and the head (the embedding lookup is a gather)."""
    d, h, hkv, hd, f = (c["hidden_size"], c["num_attention_heads"],
                        c["num_key_value_heads"], c["head_dim"], c["intermediate_size"])
    per_layer = d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f
    return c["num_hidden_layers"] * per_layer + d * c["vocab_size"]


def lm_forward_flops_per_token(c: dict, seq: int) -> float:
    """2 per multiply-add of the matmuls, plus causal attention: scores and
    values at half the sequence on average (2 * 2 * heads * head_dim * seq / 2)."""
    attn = 2 * c["num_attention_heads"] * c["head_dim"] * seq
    return 2 * lm_matmul_params(c) + c["num_hidden_layers"] * attn


def lm_train_flops_per_token(c: dict, seq: int) -> float:
    """Forward plus backward (twice the forward)."""
    return 3 * lm_forward_flops_per_token(c, seq)


def train_flops_per_round(config: dict, mix: dict) -> float:
    """Model operations of the trained work of one Fed-CHS round: the active
    cluster's clients each take K local steps on one batch."""
    fed, data, pop = mix["federation"], mix["data"], mix["population"]
    steps = pop["clients_per_cluster"] * fed["local_steps"]
    tokens = data["batch"] * data["seq"]
    return steps * tokens * lm_train_flops_per_token(config, data["seq"])


def flash_forward_flops(batch_heads: int, t: int, s: int, head_dim: int) -> float:
    """The causal attention forward: 2 * 2 * (B*H) * T * S * hd / 2."""
    return 2.0 * batch_heads * t * s * head_dim


def packed_wire_bytes(n: int, bits: int, block: int) -> int:
    """Bytes of one leaf's packed QSGD wire: ceil(n / block) blocks of
    `bits * block / 32` uint32 words plus one float32 norm each."""
    blocks = max(1, math.ceil(n / block))
    return blocks * (bits * block // 32) * 4 + blocks * 4


def qsgd_code_bits(levels: int) -> int:
    """Bits of one sign-folded code of 2s+1 values."""
    return max(1, math.ceil(math.log2(2 * levels + 1)))


def qsgd_message_bytes(leaf_sizes, levels: int, block: int, delta_bytes: int,
                       master_bytes: int) -> int:
    """Bytes the QSGD uplink of one message must move: read each delta leaf
    once in its dtype, write its packed wire, read the wire, write the
    decoded leaf in the master dtype."""
    bits = qsgd_code_bits(levels)
    total = 0
    for n in leaf_sizes:
        wire = packed_wire_bytes(n, bits, block)
        total += n * delta_bytes + 2 * wire + n * master_bytes
    return total

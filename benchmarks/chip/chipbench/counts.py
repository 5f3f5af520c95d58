"""Operations and bytes the work needs, counted from a configuration's shapes.

These are the benchmark's yardstick: they count what the model and the wire
format require, not what an implementation happens to execute, so a change
that does the same work in fewer operations is measured by the same count.
Recomputed (rematerialised) operations are not counted.
"""
from __future__ import annotations

import json
import math
import os
import re


def layer_windows(c: dict) -> list:
    """Each layer's attention window: None for full causal attention, the
    `sliding_window` for a `sliding_attention` entry of `layer_types`.
    Without `layer_types` every layer is full; a window stated for no layer
    (with `use_sliding_window` not false) is an error."""
    n, types = c["num_hidden_layers"], c.get("layer_types")
    if types is None:
        if c.get("sliding_window") is not None and c.get("use_sliding_window", True):
            raise ValueError("a sliding_window with no layer_types to say which layers use it")
        return [None] * n
    if len(types) != n or not set(types) <= {"full_attention", "sliding_attention"}:
        raise ValueError(f"layer_types must give full_attention or sliding_attention "
                         f"for each of the {n} layers: {types}")
    return [c["sliding_window"] if t == "sliding_attention" else None for t in types]


def moe_keys(c: dict) -> dict | None:
    """The expert layers' sizes as the file states them, None for a dense
    model.  `num_experts` is the number held here, its published value is
    in the file's `published` object where the cut changed it (`reduced` in
    `BENCHMARK.json`).  `moe_intermediate_size` defaults to
    `intermediate_size`; the shared experts' width to
    `moe_intermediate_size`, and their number to one where only that width
    is given."""
    if not c.get("num_experts"):
        return None
    f = c.get("moe_intermediate_size") or c["intermediate_size"]
    shared_f = c.get("shared_expert_intermediate_size")
    n_shared = c.get("num_shared_experts", 1 if shared_f else 0)
    return {"num_experts": c["num_experts"], "num_experts_per_tok": c["num_experts_per_tok"],
            "moe_intermediate_size": f, "num_shared_experts": n_shared,
            "shared_expert_intermediate_size": (shared_f or f) if n_shared else None,
            "num_dense_layers": c.get("num_dense_layers", 0),
            "published_experts": c.get("published", {}).get("num_experts", c["num_experts"])}


def ffn_matmul_params(c: dict, layer: int):
    """Parameters of one layer's FFN that multiply each token: a SwiGLU of
    `intermediate_size` in a dense layer; in an expert layer the router over
    every published expert, the top-k routed experts scaled by the share of
    experts held here (the absent ones' work lies on other chips), and the
    shared experts.  Capacity padding is not counted."""
    d, moe = c["hidden_size"], moe_keys(c)
    if moe is None or layer < moe["num_dense_layers"]:
        return 3 * d * c["intermediate_size"]
    routed = (moe["num_experts_per_tok"] * 3 * d * moe["moe_intermediate_size"]
              * moe["num_experts"] / moe["published_experts"])
    shared = moe["num_shared_experts"] * 3 * d * (moe["shared_expert_intermediate_size"] or 0)
    return d * moe["published_experts"] + routed + shared


# a block's traits that the counts read; a block file's `counted` object
# states those its `config.json` does not, and an absent one is off
COUNTED = ("qk_norm", "attn_out_gate", "post_norms")
BLOCKS_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "blocks")


def block_file(c: dict) -> dict:
    """`blocks/<model_type>.json`: what a block of the file's published
    `model_type` holds that `config.json` does not state.  Its `counted`
    object gives the traits of COUNTED (AFMoE: an output gate
    o = W_o (sigmoid(h W_g) * attn(h)) with W_g of d x H*hd, and RMSNorms on
    the attention and FFN outputs, four a layer), its `program` object the
    `ArchConfig` fields that only the program reads.  A model_type with no
    file, or a counted trait the counts do not know, is an error."""
    kind = c.get("model_type")
    path = os.path.join(BLOCKS_DIR, f"{kind}.json")
    if not isinstance(kind, str) or not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", kind) \
            or not os.path.isfile(path):
        known = sorted(n[:-5] for n in os.listdir(BLOCKS_DIR) if n.endswith(".json"))
        raise ValueError(f"model_type {kind!r}: there are block files for {known}")
    with open(path) as f:
        body = json.load(f)
    unknown = sorted(set(body["counted"]) - set(COUNTED))
    if unknown:
        raise ValueError(f"blocks/{kind}.json: the counts know no trait {unknown}")
    return body


def block(c: dict) -> dict:
    """The counted traits of the file's block, each True or False."""
    counted = block_file(c)["counted"]
    return {t: bool(counted.get(t, False)) for t in COUNTED}


def attn_matmul_params(c: dict) -> int:
    """The attention's projections: q, k, v, o and an output gate d x H*hd."""
    d, h, hkv, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    gate = d * h * hd if block(c)["attn_out_gate"] else 0
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + gate


def lm_matmul_params(c: dict):
    """Parameters that take part in a matrix multiply per token: the layers'
    projections and FFN, and the head (the embedding lookup is a gather).
    Element-wise work (norms, the gate's sigmoid and product) is not counted."""
    attn = attn_matmul_params(c)
    layers = sum(attn + ffn_matmul_params(c, i) for i in range(c["num_hidden_layers"]))
    return layers + c["hidden_size"] * c["vocab_size"]


def lm_params(c: dict) -> int:
    """Every parameter the model holds here: the embedding, an untied head,
    the final norm, and each layer's norms (two, four with `post_norms`),
    attention (its q and k norms with `qk_norm`, a qkv bias where
    `attention_bias`, an output gate) and FFN: a SwiGLU of
    `intermediate_size` in a dense layer; in an expert layer the router over
    the published experts, the experts held and the shared experts.  The
    harness holds the program's parameters to it, so `lm_matmul_params`
    counts the model that runs."""
    d, h, hkv, hd = (c["hidden_size"], c["num_attention_heads"],
                     c["num_key_value_heads"], c["head_dim"])
    traits = block(c)
    attn = attn_matmul_params(c) + (2 * hd if traits["qk_norm"] else 0)
    if c.get("attention_bias"):
        attn += (h + 2 * hkv) * hd
    norms = (4 if traits["post_norms"] else 2) * d
    moe = moe_keys(c)

    def ffn(layer):
        if moe is None or layer < moe["num_dense_layers"]:
            return 3 * d * c["intermediate_size"]
        shared = moe["num_shared_experts"] * 3 * d * (moe["shared_expert_intermediate_size"] or 0)
        return (d * moe["published_experts"]
                + moe["num_experts"] * 3 * d * moe["moe_intermediate_size"] + shared)

    layers = sum(norms + attn + ffn(i) for i in range(c["num_hidden_layers"]))
    head = 0 if c["tie_word_embeddings"] else d * c["vocab_size"]
    return c["vocab_size"] * d + head + d + layers


def keys_per_query(seq: int, window: int | None = None) -> float:
    """Keys a query attends to on average under the causal mask: T/2, or
    w - w^2/(2T) for a window w < T (the first w queries see a growing
    prefix, the rest w keys each)."""
    if window is None or window >= seq:
        return seq / 2
    return window - window * window / (2 * seq)


def lm_forward_flops_per_token(c: dict, seq: int):
    """2 per multiply-add of the matmuls, plus attention: scores and values
    over the keys each query attends to (2 * 2 * heads * head_dim *
    `keys_per_query`), full causal layers at half the sequence."""
    h, hd = c["num_attention_heads"], c["head_dim"]
    windows = [w for w in layer_windows(c) if w is not None and w < seq]
    full = c["num_hidden_layers"] - len(windows)
    attn = full * 2 * h * hd * seq + sum(4 * h * hd * keys_per_query(seq, w) for w in windows)
    return 2 * lm_matmul_params(c) + attn


def lm_train_flops_per_token(c: dict, seq: int):
    """Forward plus backward (twice the forward)."""
    return 3 * lm_forward_flops_per_token(c, seq)


def train_flops_per_round(config: dict, mix: dict) -> float:
    """Model operations of the trained work of one Fed-CHS round: the active
    cluster's clients each take K local steps on one batch."""
    fed, data, pop = mix["federation"], mix["data"], mix["population"]
    steps = pop["clients_per_cluster"] * fed["local_steps"]
    tokens = data["batch"] * data["seq"]
    return steps * tokens * lm_train_flops_per_token(config, data["seq"])


def flash_forward_flops(batch_heads: int, t: int, s: int, head_dim: int,
                        window: int | None = None) -> float:
    """The causal attention forward: 2 * 2 * (B*H) * T * S * hd / 2, or with
    a window w < S, 2 * 2 * (B*H) * T * hd * (w - w^2 / (2S))."""
    if window is None or window >= s:
        return 2.0 * batch_heads * t * s * head_dim
    return 4.0 * batch_heads * t * head_dim * keys_per_query(s, window)


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

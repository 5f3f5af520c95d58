"""The work counted for windowed and expert layers, from a configuration's
published-style keys, against hand counts: keys per query under a window,
the expert layers' FFN scaled by the share of experts held, the AFMoE
block's output gate and post-norms, and the flash readers on kernels named
with their window."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import catalog, counts  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from test_chipbench_flash_bwd import BF16, PEAKS, _ctx  # noqa: E402

S, F = "sliding_attention", "full_attention"
# Trinity-Mini (huggingface.co/arcee-ai/Trinity-Mini config.json, model_type
# afmoe), cut to its two dense layers and one period and a half: 8 of its
# 128 experts held
TRINITY_CUT = {
    "model_type": "afmoe", "hidden_act": "silu",
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "intermediate_size": 6144, "vocab_size": 200192, "tie_word_embeddings": False,
    "num_hidden_layers": 6, "layer_types": [S, S, S, F, S, S], "sliding_window": 2048,
    "global_attn_every_n_layers": 4, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 8, "moe_intermediate_size": 1024, "num_shared_experts": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.826,
    "load_balance_coeff": 0.001, "mup_enabled": True, "n_group": 1, "topk_group": 1,
    "num_expert_groups": 1, "num_limited_groups": 1, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "rope_scaling": None, "max_position_embeddings": 131072,
    "use_grouped_mm": True,
    "published": {"num_hidden_layers": 32, "num_experts": 128,
                  "layer_types": [S, S, S, F] * 8},
}
# the planned cell's cut (model-configs guide, section 4): 16 of the 128
# experts, as one of 8 chips that share each layer, and an eighth of the vocabulary
TRINITY_MINI = dict(TRINITY_CUT, num_experts=16, vocab_size=25024,
                    published=dict(TRINITY_CUT["published"], vocab_size=200192))


def test_keys_per_query_under_a_window():
    # T 8,192 with a 2,048 window: 8192 * 2048 - 2048^2 / 2 score pairs a head
    assert counts.keys_per_query(8192, 2048) == 1792
    assert 8192 * counts.keys_per_query(8192, 2048) == 8192 * 2048 - 2048 ** 2 / 2 == 14_680_064
    assert counts.keys_per_query(8192) == 4096                  # full causal: T / 2
    full_over_window = counts.keys_per_query(8192) / counts.keys_per_query(8192, 2048)
    assert full_over_window == pytest.approx(2.2857, abs=1e-4)
    assert counts.keys_per_query(8192, 128) == 127              # 32x fewer than full
    assert counts.keys_per_query(2048, 4096) == counts.keys_per_query(2048)   # w >= T is full


def test_flash_forward_flops_with_a_window():
    assert counts.flash_forward_flops(32, 8192, 8192, 128, 2048) == 4 * 32 * 8192 * 128 * 1792
    assert counts.flash_forward_flops(16, 2048, 2048, 128, 2048) == counts.flash_forward_flops(
        16, 2048, 2048, 128)
    assert counts.flash_forward_flops(16, 2048, 2048, 128, None) == (
        2 * 2 * 16 * 2048 * 2048 * 128 / 2)


def test_layer_windows():
    assert counts.layer_windows(TRINITY_CUT) == [2048, 2048, 2048, None, 2048, 2048]
    qwen3 = catalog.load_json(os.path.join(BENCH, "configs", "qwen3-0.6b.json"))
    assert counts.layer_windows(qwen3) == [None] * 28            # use_sliding_window false
    with pytest.raises(ValueError, match="layer_types"):
        counts.layer_windows({"num_hidden_layers": 2, "sliding_window": 512})
    with pytest.raises(ValueError, match="each of the 6 layers"):
        counts.layer_windows(dict(TRINITY_CUT, layer_types=[S, F]))
    with pytest.raises(ValueError, match="each of the 2 layers"):
        counts.layer_windows({"num_hidden_layers": 2, "layer_types": [S, "chunked_attention"],
                              "sliding_window": 8})


def test_expert_layers_by_hand():
    d = 2048
    attn = d * 32 * 128 + 2 * d * 4 * 128 + 32 * 128 * d
    gate = d * 32 * 128                                          # AFMoE's output gate
    assert (attn, gate) == (18_874_368, 8_388_608)
    dense = 3 * d * 6144
    assert counts.ffn_matmul_params(TRINITY_CUT, 0) == dense
    assert counts.ffn_matmul_params(TRINITY_CUT, 1) == dense
    # the router over all 128 published experts, the top 8 at the held share 8 / 128, one shared
    router, routed, shared = d * 128, 8 * 3 * d * 1024 * 8 / 128, 3 * d * 1024
    assert (router, routed, shared) == (262_144, 3_145_728, 6_291_456)
    assert counts.ffn_matmul_params(TRINITY_CUT, 2) == router + routed + shared == 9_699_328
    matmul = 6 * (attn + gate) + 2 * dense + 4 * (router + routed + shared) + d * 200192
    assert counts.lm_matmul_params(TRINITY_CUT) == matmul == 687_865_856
    # five windowed layers at 1,792 keys a query, one full at 4,096
    attn_flops = 1 * 2 * 32 * 128 * 8192 + 5 * 4 * 32 * 128 * 1792
    assert attn_flops == 213_909_504
    assert counts.lm_forward_flops_per_token(TRINITY_CUT, 8192) == 2 * matmul + attn_flops
    assert counts.lm_train_flops_per_token(TRINITY_CUT, 8192) == 3 * 1_589_641_216


def test_parameters_held_by_hand():
    d = 2048
    attn = d * 32 * 128 + 2 * d * 4 * 128 + 32 * 128 * d + 2 * 128     # q and k norms
    gate, norms = d * 32 * 128, 4 * d             # the output gate; norms before and after
    dense = 3 * d * 6144
    # the router over the 128 published experts (the guide's section 4: it
    # keeps its published width), the 8 experts held, one shared expert
    expert_layer = d * 128 + 8 * 3 * d * 1024 + 3 * d * 1024
    assert (attn, dense, expert_layer) == (18_874_624, 37_748_736, 56_885_248)
    layers = 6 * (norms + attn + gate) + 2 * dense + 4 * expert_layer
    embed_and_head = 2 * 200192 * d                                    # untied
    assert counts.lm_params(TRINITY_CUT) == layers + embed_and_head + d == 1_286_655_488
    tied = dict(TRINITY_CUT, tie_word_embeddings=True)
    assert counts.lm_params(TRINITY_CUT) - counts.lm_params(tied) == 200192 * d
    biased = dict(TRINITY_CUT, attention_bias=True)
    assert counts.lm_params(biased) - counts.lm_params(TRINITY_CUT) == 6 * (32 + 2 * 4) * 128
    # a qwen3 block of the same sizes: no gate, two norms a layer
    qwen3_block = dict(TRINITY_CUT, model_type="qwen3")
    assert counts.lm_params(TRINITY_CUT) - counts.lm_params(qwen3_block) == 6 * (gate + 2 * d)
    assert (counts.lm_matmul_params(TRINITY_CUT) - counts.lm_matmul_params(qwen3_block)
            == 6 * gate)


def test_the_planned_trinity_mini_cut_by_hand():
    d, vocab = 2048, 25024
    attn = d * 32 * 128 + 2 * d * 4 * 128 + 32 * 128 * d + d * 32 * 128   # q, k, v, o, gate
    dense = 3 * d * 6144
    held = d * 128 + 16 * 3 * d * 1024 + 3 * d * 1024        # router, 16 experts, shared
    params = 6 * (4 * d + attn + 2 * 128) + 2 * dense + 4 * held + 2 * vocab * d + d
    assert counts.lm_params(TRINITY_MINI) == params == 770_493_952
    # the top 8 at the held share 16 / 128
    routed = d * 128 + 8 * 3 * d * 1024 * 16 // 128 + 3 * d * 1024
    matmul = 6 * attn + 2 * dense + 4 * routed + d * vocab
    assert counts.lm_matmul_params(TRINITY_MINI) == matmul == 341_704_704
    # at 4,096 a window of 2,048 sees 2048 - 2048^2 / 8192 = 1,536 keys a query
    attn_4k = 2 * 32 * 128 * 4096 + 5 * 4 * 32 * 128 * 1536
    train_4k = counts.lm_train_flops_per_token(TRINITY_MINI, 4096)
    assert train_4k == 3 * (2 * matmul + attn_4k) == 2_528_378_880
    assert counts.lm_train_flops_per_token(TRINITY_MINI, 8192) == 2_691_956_736


@pytest.mark.parametrize("kind", [None, "deepseek_v3", "../configs/qwen3-0.6b"],
                         ids=["absent", "unknown", "path"])
def test_a_block_the_counts_do_not_know_is_an_error(kind):
    c = dict(TRINITY_CUT, model_type=kind)
    for count in (counts.lm_params, counts.lm_matmul_params):
        with pytest.raises(ValueError, match=f"model_type {kind!r}"):
            count(c)


def test_a_block_file_adds_an_architecture(tmp_path, monkeypatch):
    blocks = tmp_path / "blocks"
    shutil.copytree(counts.BLOCKS_DIR, blocks)
    (blocks / "qwen3_moe.json").write_text((blocks / "qwen3.json").read_text())
    (blocks / "sandwich.json").write_text(json.dumps(
        {"source": "a test", "counted": {"qk_norm": True, "sandwich_norm": True},
         "program": {}}))
    monkeypatch.setattr(counts, "BLOCKS_DIR", str(blocks))
    qwen3_moe = dict(TRINITY_CUT, model_type="qwen3_moe")
    assert counts.lm_params(qwen3_moe) == counts.lm_params(dict(TRINITY_CUT, model_type="qwen3"))
    with pytest.raises(ValueError, match="blocks/sandwich.json: the counts know no trait "
                                         "\\['sandwich_norm'\\]"):
        counts.lm_params(dict(TRINITY_CUT, model_type="sandwich"))


@pytest.mark.parametrize("name", sorted(os.listdir(counts.BLOCKS_DIR)))
def test_each_block_file_states_its_source_and_traits(name):
    body = catalog.load_json(os.path.join(counts.BLOCKS_DIR, name))
    assert name.endswith(".json") and set(body) == {"source", "counted", "program"}
    assert set(body["counted"]) <= set(counts.COUNTED)
    assert all(isinstance(v, bool) for v in body["counted"].values())
    assert not set(body["program"]) & set(counts.COUNTED)


def test_routed_work_scales_with_the_experts_held():
    def routed(held, published):
        c = dict(TRINITY_CUT, num_experts=held, published={"num_experts": published})
        return counts.ffn_matmul_params(c, 2) - 2048 * published - 3 * 2048 * 1024

    assert routed(128, 128) == 8 * 3 * 2048 * 1024            # every expert here: the top 8
    assert routed(16, 128) == 2 * routed(8, 128) == routed(128, 128) / 8
    # with no published count the file's count is the published one
    assert counts.ffn_matmul_params(dict(TRINITY_CUT, published={}), 2) == (
        2048 * 8 + 8 * 3 * 2048 * 1024 + 3 * 2048 * 1024)


def test_shared_expert_width_and_count_defaults():
    c = dict(TRINITY_CUT, num_experts=128, published={})
    del c["num_shared_experts"]
    routed = 2048 * 128 + 8 * 3 * 2048 * 1024
    assert counts.ffn_matmul_params(c, 2) == routed                       # no shared expert
    # a shared width alone means one shared expert of that width
    assert counts.ffn_matmul_params(dict(c, shared_expert_intermediate_size=4096), 2) == (
        routed + 3 * 2048 * 4096)
    two_shared = counts.ffn_matmul_params(dict(c, num_shared_experts=2), 2)
    assert two_shared == routed + 2 * 3 * 2048 * 1024


# a layer of the cut above at 8,192 tokens: 32 query heads, 4 KV heads, window 2,048
FWD_W = (f"%flash_attention_w2048.3 = (bf16[32,8192,128]{BF16}, f32[32,1,1,8192]{{3,2,1,0}}) "
         f"custom-call(bf16[32,8192,128]{BF16} %a, bf16[4,8192,128]{BF16} %b, "
         f"bf16[4,8192,128]{BF16} %c), custom_call_target=\"tpu_custom_call\"")
FWD = FWD_W.replace("flash_attention_w2048", "flash_attention")
DQ_W = (f"%flash_bwd_dq_w2048 = bf16[32,8192,128]{BF16} custom-call(bf16[32,8192,128]{BF16} %q, "
        f"bf16[4,8192,128]{BF16} %k, bf16[4,8192,128]{BF16} %v, bf16[32,8192,128]{BF16} %do, "
        f"f32[32,1,8192]{{2,1,0}} %lse, f32[32,1,8192]{{2,1,0}} %delta), "
        f"custom_call_target=\"tpu_custom_call\"")
DKV_W = (f"%flash_bwd_dkv_w2048.7 = (bf16[4,8192,128]{BF16}, bf16[4,8192,128]{BF16}) "
         f"custom-call(bf16[4,8,8192,128]{{3,2,1,0}} %q), custom_call_target=\"tpu_custom_call\"")


def test_the_kernel_name_states_the_window():
    assert tr.kernel_window(FWD_W) == 2048 and tr.kernel_window(DKV_W) == 2048
    assert tr.kernel_window(DQ_W) == 2048
    assert tr.kernel_window(FWD) is None
    assert tr.kernel_window("%flash_bwd_dq.1 = bf16[16,2048,128] custom-call()") is None


def _read(name, events):
    return catalog.metric_reader(name)(_ctx(events))


def test_the_forward_share_counts_the_windowed_work():
    value = _read("flash_roofline", [(0, 10**9, FWD_W)])          # 1 ms
    need = 4 * 32 * 8192 * 128 * 1792
    assert value == pytest.approx(100 * need / PEAKS["bf16_flops_per_s"] / 1e-3)
    full = _read("flash_roofline", [(0, 10**9, FWD)])
    assert full / value == pytest.approx(4096 / 1792)
    # one full and one windowed layer: the least times add
    both = _read("flash_roofline", [(0, 10**9, FWD), (2 * 10**9, 3 * 10**9, FWD_W)])
    assert both == pytest.approx((full + value) / 2)


def test_the_backward_share_counts_the_windowed_work():
    value = _read("flash_bwd_roofline", [(0, 5 * 10**8, DQ_W), (10**9, 10**9 + 7 * 10**8, DKV_W)])
    need = 2 * 4 * 32 * 8192 * 128 * 1792
    assert value == pytest.approx(100 * need / PEAKS["bf16_flops_per_s"] / 1.2e-3)

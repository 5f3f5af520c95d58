"""A configuration file states its architecture as data: the standard keys,
with the published-style window and expert keys, build the program's
`ArchConfig`, an `arch` object sets its other fields, the counts hold the
parameters the program builds, and a `toy` object cuts the file to toy
size.  A toy MoE with sliding-window layers, described by files alone, runs
set-up's first call through the harness on the CPU."""
import dataclasses
import json
import os
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import chipbench_toy  # noqa: E402
from chipbench import catalog, cli, counts, program  # noqa: E402

ROOT = catalog.ROOT
QWEN3 = catalog.load_json(os.path.join(BENCH, "configs", "qwen3-0.6b.json"))
S, F = "sliding_attention", "full_attention"
# a model in the shape of a windowed MoE: three sliding-window layers to one
# full-attention layer, 128 experts of width 1,024, top 8, one shared expert
MOE = dict(QWEN3, name="toy-moe-window", num_hidden_layers=8, layer_types=[S, S, S, F] * 2,
           sliding_window=2048, num_experts=128, num_experts_per_tok=8,
           moe_intermediate_size=1024, num_shared_experts=1,
           toy={"num_hidden_layers": 4, "layer_types": [S, S, S, F], "sliding_window": 8,
                "num_experts": 4, "num_experts_per_tok": 2, "moe_intermediate_size": 32})


def _built(config):
    """The program's parameter shapes for a configuration file."""
    return jax.eval_shape(program.inner_model(config).init, jax.random.PRNGKey(0))


def test_the_qwen3_file_builds_todays_arch_config():
    from repro.configs.base import ArchConfig

    arch = program.arch_config(QWEN3)
    today = ArchConfig(
        name="qwen3-0.6b", family="dense", num_layers=28, d_model=1024, num_heads=16,
        num_kv_heads=8, head_dim=128, d_ff=3072, vocab_size=151936, qkv_bias=False,
        qk_norm=True, rope_theta=1000000.0, act="silu", norm_eps=1e-06, tie_embeddings=True,
        dtype="float32")
    assert arch == today and hash(arch) == hash(today)
    assert dataclasses.asdict(arch) == {
        "name": "qwen3-0.6b", "family": "dense", "num_layers": 28, "d_model": 1024,
        "num_heads": 16, "num_kv_heads": 8, "d_ff": 3072, "vocab_size": 151936,
        "head_dim": 128, "qkv_bias": False, "qk_norm": True, "rope_theta": 1000000.0,
        "sliding_window": None, "block_pattern": ("attn",), "act": "silu",
        "num_experts": 0, "experts_per_token": 0, "num_shared_experts": 0,
        "router_aux_coef": 0.01, "moe_groups": 1, "expert_axis": "model",
        "moe_shardmap": False, "mla": None, "ssm_state": 0, "ssm_conv": 4, "ssm_expand": 2,
        "ssm_head_dim": 64, "ssm_chunk": 256, "lru_width": None, "encoder_layers": 0,
        "num_audio_frames": 0, "num_patches": 0, "mtp_depth": 0, "norm_eps": 1e-06,
        "tie_embeddings": True, "dtype": "float32", "use_flash": False,
        "long_context_ok": False}


def test_arch_overrides_apply_after_the_standard_keys():
    arch = program.arch_config(dict(MOE, arch={"moe_groups": 2, "router_aux_coef": 0.0}))
    assert (arch.moe_groups, arch.router_aux_coef) == (2, 0.0)
    assert arch == dataclasses.replace(program.arch_config(MOE), moe_groups=2,
                                       router_aux_coef=0.0)


@pytest.mark.parametrize("change,pattern,window,experts", [
    ({}, ("local", "local", "local", "attn"), 2048, (128, 8, 1, 1024)),
    ({"num_hidden_layers": 7, "layer_types": [S, F, F] * 2 + [S]},     # a tail of one layer
     ("local", "attn", "attn"), 2048, (128, 8, 1, 1024)),
    ({"layer_types": [F] * 8, "num_experts": 0}, ("attn",), None, (0, 0, 0, 3072)),
], ids=["period-4", "tail", "full-dense"])
def test_the_windows_and_experts_come_from_the_published_keys(change, pattern, window, experts):
    arch = program.arch_config(dict(MOE, **change))
    assert arch.block_pattern == pattern and arch.sliding_window == window
    assert (arch.num_experts, arch.experts_per_token, arch.num_shared_experts,
            arch.d_ff) == experts
    assert arch.family == ("moe" if experts[0] else "dense")
    windows = [window if arch.block_kind(i) == "local" else None for i in range(arch.num_layers)]
    assert windows == counts.layer_windows(dict(MOE, **change))


def test_an_unknown_arch_field_is_an_error():
    with pytest.raises(ValueError, match="num_local_experts"):
        program.arch_config(dict(QWEN3, arch={"num_local_experts": 8}))


@pytest.mark.parametrize("field", program.SET_BY_KEYS + program.UNCOUNTED)
def test_arch_may_not_set_what_the_keys_set_or_the_counts_cannot_count(field):
    with pytest.raises(ValueError, match=f"may not set \\['{field}'\\]"):
        program.arch_config(dict(QWEN3, arch={field: 1}))


@pytest.mark.parametrize("config", [QWEN3, MOE, chipbench_toy.cut(MOE)],
                         ids=["qwen3", "moe", "moe-toy"])
def test_the_counts_hold_the_parameters_the_program_builds(config):
    program.check_counts_agree(config, _built(config))
    if config is QWEN3:
        assert counts.lm_params(QWEN3) == 596_049_920


@pytest.mark.parametrize("change", [
    {"num_dense_layers": 2},                     # the program has no leading dense layers
    {"shared_expert_intermediate_size": 2048},   # its shared experts have the routed width
], ids=["dense-layers", "shared-width"])
def test_the_counts_and_the_program_must_agree(change):
    config = dict(MOE, **change)
    with pytest.raises(ValueError, match="parameters"):
        program.check_counts_agree(config, _built(config))


def test_leading_dense_layers_pass_once_the_program_builds_them():
    # one dense layer ahead of one period of expert layers, at toy size
    moe = chipbench_toy.cut(MOE)
    lead = dict(moe, num_hidden_layers=5, num_dense_layers=1, layer_types=[F, S, S, S, F])
    with pytest.raises(ValueError, match="parameters"):
        program.check_counts_agree(lead, _built(lead))      # every layer built with experts
    # a program that builds the dense layer, in a layout of its own: the
    # check reads only the sizes of its leaves
    dense_layer = _built(dict(moe, num_experts=0, num_hidden_layers=1, layer_types=[F]))["super"]
    program.check_counts_agree(lead, {"experts": _built(moe), "dense": dense_layer})


def _like(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32), tree,
                        is_leaf=lambda x: isinstance(x, tuple))


@pytest.mark.parametrize("tree", [
    {"embed": (16, 8), "a_param": (8,)},                 # a vector outside the layers
    {"super": [{"gate_bias": (3, 8)}], "embed": (16, 8)},  # one vector per stacked layer
], ids=["top", "stacked"])
def test_an_unnamed_vector_leaf_is_an_error_that_names_it(tree):
    with pytest.raises(ValueError, match="a_param|gate_bias"):
        program.weights_maker(_like(tree), seed=3)


def test_the_toy_cut_applies_the_files_toy_object():
    toy = chipbench_toy.cut(MOE)
    assert toy["hidden_size"] == chipbench_toy.LM_TOY["hidden_size"]
    assert (toy["num_hidden_layers"], toy["num_experts"], toy["sliding_window"],
            toy["moe_intermediate_size"]) == (4, 4, 8, 32)
    merged = chipbench_toy.cut(dict(MOE, arch={"moe_groups": 2},
                                    toy={"arch": {"router_aux_coef": 0.0}}))
    assert merged["arch"] == {"moe_groups": 2, "router_aux_coef": 0.0}   # an object is updated
    assert chipbench_toy.cut(QWEN3) == dict(QWEN3, **chipbench_toy.LM_TOY)   # no toy object


@pytest.mark.parametrize("config", catalog.load_json(os.path.join(ROOT, "BENCHMARK.json"))
                         ["configs"], ids=lambda c: c["name"])
def test_each_reduced_key_states_its_published_value(config):
    body = catalog.load_json(os.path.join(ROOT, config["file"]))
    assert set(config["reduced"]) <= set(body.get("published", {}))


def test_a_toy_moe_with_windows_runs_set_up_from_files_alone(tmp_path):
    from repro.obs.trace import SpanTracer

    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench_dir, ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    # a later change adds a configuration file, a limits file and two entries
    (bench_dir / "configs" / "toy-moe-window.json").write_text(json.dumps(MOE))
    (bench_dir / "limits" / "toy-moe-window.chs-dense-s2048.json").write_text(
        (bench_dir / "limits" / "qwen3-0.6b.chs-dense-s2048.json").read_text())
    bench = catalog.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "toy-moe-window", "source": "a test",
                             "file": "benchmarks/chip/configs/toy-moe-window.json",
                             "reduced": [], "why": "a windowed MoE"})
    bench["workloads"].append({"name": "toy-moe-window.chs-dense-s2048",
                               "config": "toy-moe-window", "traffic": "chs-dense-s2048",
                               "chips": 1, "why": "a windowed MoE"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = chipbench_toy.toy("toy-moe-window.chs-dense-s2048", root=str(root),
                             bench_dir=str(bench_dir))
    arch = program.arch_config(cell.config)
    assert (arch.num_experts, arch.d_ff, arch.sliding_window, arch.num_layers) == (4, 32, 8, 4)
    # train_mfu's count at toy size, by hand: per layer q/k/v/o 12,288, the
    # router 256, the top 2 of 4 experts 12,288, the shared expert 6,144; the
    # tied head 32,768; attention at 32 tokens 4 heads x 16: 2 * 2 * 64 * 16
    # on the full layer, 2 * 2 * 64 * (8 - 8^2 / 64) on each windowed one
    matmul = 4 * (12_288 + 256 + 12_288 + 6_144) + 64 * 512
    attn = 2 * 2 * 64 * 16 + 3 * 2 * 2 * 64 * 7
    assert counts.lm_matmul_params(cell.config) == matmul == 156_672
    assert counts.lm_forward_flops_per_token(cell.config, 32) == 2 * matmul + attn
    # 2 clients x K=4 steps x 32 tokens, forward and backward
    assert counts.train_flops_per_round(cell.config, cell.mix) == 2 * 4 * 32 * 3 * 322_816
    setup = cli.first_call(cell, 2**33 + 7, SpanTracer(profiler=False))
    params = setup.w0["super"]
    # one stacked tree per position of the period; each holds a router and stacked experts
    assert len(params) == 4
    for layer in params:
        ffn = layer["ffn"]
        assert ffn["router"].shape == (1, 64, 4)
        assert ffn["w_gate"].shape == ffn["w_in"].shape == (1, 4, 64, 32)
        assert ffn["w_out"].shape == (1, 4, 32, 64)
        assert ffn["shared"]["w_gate"].shape == (1, 64, 32)
    losses, _ = setup.logged
    assert setup.failed == 0 and losses and all(np.isfinite(losses))
    assert all(p.read_bytes() == b for p, b in before.items())

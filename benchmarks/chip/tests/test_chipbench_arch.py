"""A configuration file states its architecture as data: the standard keys,
with the published-style window and expert keys, and the routing keys and
block traits of an AFMoE file, build the program's `ArchConfig` (a field
it lacks is named), an `arch` object sets its other fields, the counts hold the
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
from test_chipbench_counts_arch import TRINITY_MINI  # noqa: E402

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
# the planned Trinity-Mini file (AFMoE), with a toy object of the same shape
TRINITY = dict(TRINITY_MINI, name="trinity-mini", family="lm", precision=QWEN3["precision"],
               remat=True, flash=True,
               toy={"num_hidden_layers": 6, "layer_types": [S, S, S, F, S, S],
                    "sliding_window": 8, "num_experts": 4, "num_experts_per_tok": 2,
                    "moe_intermediate_size": 32, "published": {"num_experts": 16}})
# what Trinity-Mini's keys and block ask of ArchConfig beyond today's
# fields: (key, field, value)
AFMOE_KEYS = [
    ("num_dense_layers", "num_dense_layers", 2), ("intermediate_size", "dense_d_ff", 6144),
    ("published.num_experts", "router_experts", 128), ("score_func", "router_score", "sigmoid"),
    ("route_norm", "route_norm", True), ("route_scale", "route_scale", 2.826),
    ("mup_enabled", "embed_scale", 2048 ** 0.5), ("model_type afmoe", "attn_out_gate", True),
    ("model_type afmoe", "post_norms", True), ("model_type afmoe", "rope_local_only", True),
]
AFMOE_FIELDS = {field: value for _, field, value in AFMOE_KEYS}


def _standin(monkeypatch, lacking=()):
    """Patch in an `ArchConfig` that has today's fields and those
    Trinity-Mini asks for, less `lacking`, so that what `arch_config` maps and
    refuses is tested apart from the fields the program has."""
    from repro.configs import base

    spec = [(f.name, f.type, dataclasses.field(default=f.default,
                                               default_factory=f.default_factory))
            for f in dataclasses.fields(base.ArchConfig)]
    have = {name for name, _, _ in spec}
    spec += [(name, type(value), dataclasses.field(default=None))
             for name, value in AFMOE_FIELDS.items() if name not in have]
    standin = dataclasses.make_dataclass(
        "ArchConfig", [f for f in spec if f[0] not in lacking], frozen=True)
    monkeypatch.setattr(base, "ArchConfig", standin)
    return standin


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
    pinned = {
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
    built = dataclasses.asdict(arch)
    assert {k: built[k] for k in pinned} == pinned
    # a field the program adds keeps its default for this file
    added = {f.name: f.default for f in dataclasses.fields(ArchConfig) if f.name not in pinned}
    assert {k: built[k] for k in added} == added


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


@pytest.mark.parametrize("change,lacking,refusal", [
    # a program with no leading dense layers: arch_config refuses the file
    ({"num_dense_layers": 2}, ("num_dense_layers",),
     "no field for num_dense_layers -> num_dense_layers$"),
    # its shared experts have the routed width: the counts refuse the build
    ({"shared_expert_intermediate_size": 2048}, None, "parameters"),
], ids=["dense-layers", "shared-width"])
def test_the_counts_and_the_program_must_agree(change, lacking, refusal, monkeypatch):
    if lacking:
        _standin(monkeypatch, lacking)
    config = dict(MOE, **change)
    with pytest.raises(ValueError, match=refusal):
        program.check_counts_agree(config, _built(config))


def test_leading_dense_layers_pass_once_the_program_builds_them(monkeypatch):
    # one dense layer ahead of one period of expert layers, at toy size
    moe = chipbench_toy.cut(MOE)
    lead = dict(moe, num_hidden_layers=5, num_dense_layers=1, layer_types=[F, S, S, S, F])
    with monkeypatch.context() as m:         # a program with no field for them
        _standin(m, ("num_dense_layers",))
        with pytest.raises(ValueError, match="no field for num_dense_layers -> num_dense_layers$"):
            _built(lead)
    # a program that builds the dense layer, in a layout of its own: the
    # check reads only the sizes of its leaves
    experts = _built(moe)
    dense_layer = _built(dict(moe, num_experts=0, num_hidden_layers=1, layer_types=[F]))["super"]
    program.check_counts_agree(lead, {"experts": experts, "dense": dense_layer})
    # the AFMoE block of the same cut: each layer adds the output gate and two
    # post-norms, and the router scores the 16 published experts, 4 held; the
    # dense layer runs outside the scan, unstacked
    afmoe = dict(lead, model_type="afmoe", published={"num_experts": 16})
    dense = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), dense_layer[0])
    standin = dict(experts, super=[_as_afmoe(x, (1,)) for x in experts["super"]],
                   dense=_as_afmoe(dense, ()))
    program.check_counts_agree(afmoe, standin)
    gateless = dict(standin, dense=_as_afmoe(dense, (), gate=False))
    with pytest.raises(ValueError, match="parameters"):
        program.check_counts_agree(afmoe, gateless)
    # every leaf has an init rule, and the post-norms start at one
    weights = program.weights_maker(standin, seed=3)()
    for layer in weights["super"] + [weights["dense"]]:
        assert np.all(layer["ln_attn_out"] == 1) and np.all(layer["ffn_out_norm"] == 1)


def _as_afmoe(layer, stack, gate=True):
    """A built layer of toy width 64, stacked `stack` deep, with AFMoE's
    extra leaves: the output gate, the two post-norms and, in an expert
    layer, a router over 16 experts."""
    def sds(*shape):
        return jax.ShapeDtypeStruct(stack + shape, jnp.float32)

    out = dict(layer, ln_attn_out=sds(64), ffn_out_norm=sds(64))
    if gate:
        out["attn"] = dict(layer["attn"], w_out_gate=sds(64, 64))
    if "router" in layer["ffn"]:
        out["ffn"] = dict(layer["ffn"], router=sds(64, 16))
    return out


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


@pytest.mark.parametrize("tree", [
    {"super": [{"ln_attn_out": (3, 8)}], "embed": (16, 8)},
    {"super": [{"ffn_out_norm": (3, 8)}], "embed": (16, 8)},
    {"ln_attn_out": (8,), "embed": (16, 8)},
    {"ffn_out_norm": (8,), "embed": (16, 8)},
], ids=["ln-stacked", "norm-stacked", "ln", "norm"])
def test_a_vector_named_as_a_norm_gets_weight_one(tree):
    weights = program.weights_maker(_like(tree), seed=3)()
    norms = [x for path, x in jax.tree_util.tree_flatten_with_path(weights)[0]
             if program._leaf_name(path) != "embed"]
    assert len(norms) == 1 and np.all(norms[0] == 1)


def test_a_norm_that_is_no_vector_is_an_error():
    # stacked layers go under `super`; elsewhere a norm of rank 2 would be
    # taken for a matrix
    with pytest.raises(ValueError, match="'ln_attn_out' of shape \\(3, 8\\) is no vector"):
        program.weights_maker(_like({"dense": [{"ln_attn_out": (3, 8)}]}), seed=3)


@pytest.mark.parametrize("key,field", [(k, f) for k, f, _ in AFMOE_KEYS],
                         ids=[f for _, f, _ in AFMOE_KEYS])
def test_an_afmoe_file_names_each_field_the_program_lacks(key, field, monkeypatch):
    _standin(monkeypatch, (field,))
    with pytest.raises(ValueError, match=f"has no field for {key} -> {field}$"):
        program.arch_config(chipbench_toy.cut(TRINITY))


def test_an_afmoe_file_is_built_or_refused_by_the_fields_this_program_has():
    from repro.configs.base import ArchConfig

    have = {f.name for f in dataclasses.fields(ArchConfig)}
    lacking = [f"{key} -> {field}" for key, field, _ in AFMOE_KEYS if field not in have]
    if not lacking:
        assert program.arch_config(chipbench_toy.cut(TRINITY)).attn_out_gate
        return
    with pytest.raises(ValueError, match=f"has no field for {', '.join(lacking)}$"):
        program.arch_config(chipbench_toy.cut(TRINITY))


def test_a_program_with_the_fields_gets_trinity_minis_values(monkeypatch):
    standin = _standin(monkeypatch)
    arch = program.arch_config(TRINITY)
    assert "arch" not in TRINITY and isinstance(arch, standin)
    assert {f: getattr(arch, f) for f in AFMOE_FIELDS} == AFMOE_FIELDS
    assert (arch.qk_norm, arch.router_aux_coef) == (True, 0.0)
    assert (arch.num_layers, arch.d_model, arch.num_heads, arch.num_kv_heads, arch.head_dim,
            arch.d_ff, arch.vocab_size, arch.tie_embeddings) == (
        6, 2048, 32, 4, 128, 1024, 25024, False)
    assert (arch.num_experts, arch.experts_per_token, arch.num_shared_experts) == (16, 8, 1)
    assert (arch.sliding_window, arch.block_pattern) == (2048, ("local",) * 3 + ("attn",))
    assert (arch.rope_theta, arch.norm_eps, arch.family) == (10000.0, 1e-05, "moe")
    # keys at their off values set nothing, nor does a qwen3 block (its
    # qk-norm is a standard field)
    assert program.keyed_fields(QWEN3) == []
    off = dict(TRINITY, model_type="qwen3", num_dense_layers=0, route_norm=False,
               route_scale=1.0, mup_enabled=False, published={"num_experts": 16})
    del off["score_func"]
    assert program.keyed_fields(off) == []


@pytest.mark.parametrize("field", list(AFMOE_FIELDS) + ["router_aux_coef"])
def test_arch_may_not_set_what_the_block_file_or_the_routing_keys_set(field, monkeypatch):
    _standin(monkeypatch)
    with pytest.raises(ValueError, match=f"may not set \\['{field}'\\]"):
        program.arch_config(dict(TRINITY, arch={field: 1}))


@pytest.mark.parametrize("change,refusal", [
    ({"model_type": "deepseek_v3"}, "model_type 'deepseek_v3'"),
    ({"n_group": 8}, "group-limited routing {'n_group': 8}"),
    ({"topk_group": 4}, "group-limited routing {'topk_group': 4}"),
    ({"num_expert_groups": 8}, "group-limited routing {'num_expert_groups': 8}"),
    ({"num_limited_groups": 4}, "group-limited routing {'num_limited_groups': 4}"),
    ({"score_func": "softplus"}, "score_func 'softplus' is neither"),
], ids=["model-type", "n-group", "topk-group", "expert-groups", "limited-groups", "score"])
def test_a_block_or_routing_the_counts_do_not_know_is_refused(change, refusal):
    with pytest.raises(ValueError, match=refusal):
        program.arch_config(dict(TRINITY, **change))


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

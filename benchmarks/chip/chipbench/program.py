"""The system under test, built for one cell: the program's model, task and
`FedCHSConfig`, with the benchmark's weights and data.

The weights are made by the benchmark, on the device, in one jitted call
from the seed, in the program's own parameter layout (`jax.eval_shape` of
the program's init) and in the dtype the configuration trains its masters
in.  They reach `run_fed_chs` through `SeededModel.init`, so the program
starts every call from the same weights the reference starts from.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import counts, traffic


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def _init_rule(path, shape) -> str:
    """The benchmark's init rule of one leaf: norm weights (vectors named
    `ln*` or `*norm`) one, biases zero, the embedding N(0, 0.02^2), every
    other matrix N(0, 1 / fan_in).  The scanned layers (`super`) are stacked
    on a leading axis.  A leaf that is no matrix and has no rule, or a norm
    that is no vector, is an error that names it."""
    name = _leaf_name(path)
    rank = len(shape) - int(any(getattr(k, "key", None) == "super" for k in path))
    if name.startswith("ln") or name.endswith("norm"):
        if rank != 1:
            raise ValueError(f"the norm {name!r} of shape {tuple(shape)} is no vector: "
                             f"stacked layers go under 'super'")
        return "ones"
    if name.startswith("b") and rank == 1:
        return "zeros"
    if name == "embed":
        return "normal_0.02"
    if rank >= 2:
        return "fan_in"
    raise ValueError(f"no init rule for the leaf {name!r} of shape {tuple(shape)}")


def _init_leaf(rule: str, key, shape, dtype):
    if rule == "ones":
        return jnp.ones(shape, dtype)
    if rule == "zeros":
        return jnp.zeros(shape, dtype)
    if rule == "normal_0.02":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    w = jax.random.normal(key, shape, jnp.float32) * np.sqrt(1.0 / shape[-2])
    return w.astype(dtype)


@functools.cache
def _weights_fn(treedef, leaves: tuple):
    def make(key):
        out = []
        for i, (rule, shape, dtype) in enumerate(leaves):
            out.append(_init_leaf(rule, jax.random.fold_in(key, i), shape, dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(make)


def weights_maker(like, seed: int):
    """() -> a fresh weights pytree shaped like `like` (ShapeDtypeStructs)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    leaves = tuple((_init_rule(p, x.shape), tuple(x.shape), jnp.dtype(x.dtype)) for p, x in flat)
    fn = _weights_fn(treedef, leaves)
    key = jax.random.PRNGKey(traffic.sub_seed(seed, traffic.TAG_WEIGHTS))
    return lambda: fn(key)


# --------------------------------------------------------------------------
# the program's model, with the benchmark's weights
# --------------------------------------------------------------------------


class Capture:
    """Host copies of the params the driver evaluates, while `on`."""

    def __init__(self):
        self.on = False
        self.params: list = []


@dataclasses.dataclass(frozen=True)
class SeededModel:
    """A program `FedModel` whose `init` returns the benchmark's weights.

    Equality and hash are the wrapped model's, so every seed in a process
    reuses the program's compiled rounds."""

    inner: Any
    make: Any = dataclasses.field(compare=False, hash=False)
    capture: Capture = dataclasses.field(compare=False, hash=False,
                                         default_factory=Capture)

    @property
    def name(self) -> str:
        return self.inner.name

    @property
    def metric_name(self) -> str:
        return self.inner.metric_name

    @property
    def metric_mode(self) -> str:
        return self.inner.metric_mode

    def init(self, key):
        del key
        return self.make()

    def loss(self, params, batch):
        return self.inner.loss(params, batch)

    def eval_metric(self, params, eval_data):
        if self.capture.on:
            self.capture.params.append(jax.device_get(params))
        return self.inner.eval_metric(params, eval_data)


# `ArchConfig` fields that a file's standard keys set (`qk_norm` from its
# block file), and fields of layers that `chipbench.counts` cannot count: a
# file's `arch` object sets neither, nor a field that `keyed_fields` sets
SET_BY_KEYS = ("name", "family", "num_layers", "d_model", "num_heads", "num_kv_heads",
               "head_dim", "d_ff", "vocab_size", "qkv_bias", "qk_norm", "rope_theta", "act",
               "norm_eps", "tie_embeddings", "dtype", "use_flash", "sliding_window",
               "block_pattern", "num_experts", "experts_per_token", "num_shared_experts")
UNCOUNTED = ("mla", "ssm_state", "ssm_conv", "ssm_expand", "ssm_head_dim", "ssm_chunk",
             "lru_width", "encoder_layers", "num_audio_frames", "num_patches", "mtp_depth")
# routing keys whose only value the program and the counts know is 1
GROUP_KEYS = ("n_group", "topk_group", "num_expert_groups", "num_limited_groups")


def block_pattern(windows: list) -> tuple:
    """The layers' kinds (`local` where a layer has a window, else `attn`)
    as their shortest period, so the program scans one group a period."""
    kinds = ["attn" if w is None else "local" for w in windows]
    n = len(kinds)
    period = next(p for p in range(1, n + 1) if all(kinds[i] == kinds[i % p] for i in range(n)))
    return tuple(kinds[:period])


def keyed_fields(config: dict) -> list:
    """(key, `ArchConfig` field, value) for each published key beyond the
    standard ones that is on, each counted trait of the file's block that is
    on, and each field its block file gives the program: a key that is
    absent or at its off value sets nothing."""
    moe = counts.moe_keys(config) or {}
    dense = moe.get("num_dense_layers", 0)
    router = moe.get("published_experts")
    scale = config.get("route_scale")
    on = [
        ("num_dense_layers", "num_dense_layers", dense or None),
        ("intermediate_size", "dense_d_ff", config["intermediate_size"] if dense else None),
        ("published.num_experts", "router_experts",
         router if router != moe.get("num_experts") else None),
        ("score_func", "router_score", config.get("score_func")),
        ("route_norm", "route_norm", True if config.get("route_norm") else None),
        ("route_scale", "route_scale", None if scale in (None, 1) else float(scale)),
        ("mup_enabled", "embed_scale",
         math.sqrt(config["hidden_size"]) if config.get("mup_enabled") else None),
    ]
    counted = [t for t, v in counts.block(config).items() if v and t not in SET_BY_KEYS]
    traits = {**dict.fromkeys(counted, True), **counts.block_file(config)["program"]}
    on += [(f"model_type {config['model_type']}", f, v) for f, v in traits.items()]
    return [(key, f, v) for key, f, v in on if v is not None]


def arch_config(config: dict):
    """The program's `ArchConfig` for a decoder LM configuration file: the
    standard keys, the windows and experts from the published-style keys
    that `chipbench.counts` reads, the routing and embedding keys and the
    block's traits of `keyed_fields`, then the file's `arch` object of the
    other `ArchConfig` fields (a list becomes a tuple).  An expert layer
    holds the first `num_experts` of the `router_experts` its router
    scores.  A key that is on, whose field `ArchConfig` lacks, is an error
    that names the key and the field."""
    from repro.configs.base import ArchConfig

    name = config["name"]
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {config['hidden_act']!r}: the program's LM gates with silu")
    grouped = {k: config[k] for k in GROUP_KEYS if config.get(k, 1) != 1}
    if grouped:
        raise ValueError(f"{name}: group-limited routing {grouped}: the counts know one group")
    if config.get("score_func", "softmax") not in ("sigmoid", "softmax"):
        raise ValueError(f"{name}: score_func {config['score_func']!r} is neither sigmoid "
                         f"nor softmax")
    windows, moe = counts.layer_windows(config), counts.moe_keys(config)
    arch = ArchConfig(
        name=name, family="moe" if moe else "dense",
        num_layers=config["num_hidden_layers"], d_model=config["hidden_size"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        d_ff=moe["moe_intermediate_size"] if moe else config["intermediate_size"],
        vocab_size=config["vocab_size"],
        qkv_bias=bool(config.get("attention_bias", False)),
        qk_norm=counts.block(config)["qk_norm"], rope_theta=float(config["rope_theta"]),
        sliding_window=next((w for w in windows if w is not None), None),
        block_pattern=block_pattern(windows), act="silu",
        num_experts=moe["num_experts"] if moe else 0,
        experts_per_token=moe["num_experts_per_tok"] if moe else 0,
        num_shared_experts=moe["num_shared_experts"] if moe else 0,
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=bool(config["tie_word_embeddings"]),
        dtype=config["precision"]["master"])
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    keyed = keyed_fields(config)
    lacking = [f"{key} -> {f}" for key, f, _ in keyed if f not in fields]
    if lacking:
        raise ValueError(f"{name}: the program's ArchConfig has no field for "
                         f"{', '.join(lacking)}")
    arch = dataclasses.replace(arch, **{f: v for _, f, v in keyed})
    over = config.get("arch") or {}
    unknown = sorted(set(over) - fields)
    if unknown:
        raise ValueError(f"{name}: 'arch' names no ArchConfig field {unknown}")
    refused = sorted(set(over) & (set(SET_BY_KEYS + UNCOUNTED) | {f for _, f, _ in keyed}))
    if refused:
        raise ValueError(f"{name}: 'arch' may not set {refused}: the file's keys or its "
                         f"block file set them, or chipbench.counts cannot count their layers")
    return dataclasses.replace(arch, **{k: tuple(v) if isinstance(v, list) else v
                                        for k, v in over.items()})


def check_counts_agree(config: dict, like) -> None:
    """The model `chipbench.counts` counts from the file's keys is the model
    the program built: the parameters the counts hold (`counts.lm_params`)
    are the sizes of the program's leaves (`like`, from `jax.eval_shape` of
    its init), summed.  A leading dense layer, an expert width or a number
    of experts that the program does not build is an error, not a quiet
    miscount of `train_mfu`."""
    built = sum(math.prod(x.shape) for x in jax.tree.leaves(like))
    stated = counts.lm_params(config)
    if built != stated:
        raise ValueError(f"{config['name']}: the program builds {built:,} parameters, "
                         f"the file's keys count {stated:,}")


def inner_model(config: dict):
    if config["family"] != "lm":
        raise ValueError(f"unknown family {config['family']!r}")
    from repro.models.fed import LMFedModel

    return LMFedModel(arch_config(config), remat=config["remat"], flash=config["flash"])


def precision_policy(config: dict):
    from repro.core.precision import Precision

    p = config["precision"]
    return Precision(compute=p["compute"], master=p["master"], wire=p["wire"])


def channel(spec: dict):
    from repro.comm.channels import QSGDChannel

    if spec["kind"] == "dense":
        return None  # the driver's rule: the policy's wire dtype, else f32
    if spec["kind"] == "qsgd":
        return QSGDChannel(spec["levels"])
    raise ValueError(f"unknown channel {spec}")


@dataclasses.dataclass
class Program:
    task: Any
    config: Any           # FedCHSConfig of one call
    model: SeededModel
    weights: Any          # () -> the initial weights (a fresh device copy)


def build(config: dict, fed: traffic.Federation, seed: int, tracer) -> Program:
    from repro.core import FedCHSConfig
    from repro.core.simulation import FLTask
    from repro.obs import RunTelemetry

    inner = inner_model(config)
    like = jax.eval_shape(inner.init, jax.random.PRNGKey(0))
    check_counts_agree(config, like)
    make = weights_maker(like, seed)
    model = SeededModel(inner, make)
    task = FLTask.from_source(model, fed.source, fed.clusters, seed=0)
    lrs = [float(x) for x in fed.lrs]
    conf = FedCHSConfig(
        rounds=fed.rounds, local_steps=fed.local_steps, local_epochs=fed.local_epochs,
        topology=fed.topology, topology_seed=fed.topology_seed,
        initial_cluster=fed.initial_cluster, eval_every=fed.eval_every,
        channel=channel(fed.channel), precision=precision_policy(config),
        client_microbatch=fed.client_microbatch, schedule=lambda k: lrs[k],
        seed=fed.program_seed, chunk_rounds=max(32, fed.eval_every),
        obs=RunTelemetry(taps=False, tracer=tracer))
    return Program(task, conf, model, make)

"""Bring-up smoke test: Fed-CHS end to end on a TPU, through `run_fed_chs`.

    python chip_smoke.py              # one chip: kernels, paper path, LM path
    python chip_smoke.py --chips 4    # four chips: sharded federation parity

Phases, all in this one process (a chip belongs to one process at a time):

  device   print platform / device_kind / count; exit non-zero unless the
           devices are TPUs (there is no CPU fallback).
  kernels  the packed-QSGD encode/decode compile to the Pallas kernels
           (`tpu_custom_call` in the compiled HLO), and on the device the
           payload and norms of a (1024, 3072) leaf equal the `ref.py`
           oracle bit for bit, for s in {1, 16, 127}.
  paper    Fed-CHS on the Appendix-A MLP task (synthetic MNIST, 20 clients,
           4 clusters) in delta mode, E=5, `QSGDChannel(16)`, through the
           whole-run scan path: accuracy rises, every loss is finite.
  lm       qwen3-0.6b at full width (`LMFedModel(remat=True, flash=True)`,
           `TokenSource`): 2 clients / 2 clusters, batch 1, seq 128,
           `client_microbatch=1`, bf16/f32/bf16 `Precision`, 2 rounds x 2
           local steps.  Prints loss, held-out perplexity, seconds, compile
           seconds and `peak_bytes_in_use` per round; the flash kernel must
           be in the compiled round program.
  sharded  (--chips 4 only) the paper-path run on a ("clusters", "clients")
           mesh of all four chips against the same run with mesh=None.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed phase raises, and the script exits non-zero without printing it.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.comm.channels import QSGDChannel  # noqa: E402
from repro.configs.base import ArchConfig  # noqa: E402
from repro.core import FedCHSConfig, run_fed_chs  # noqa: E402
from repro.core.precision import Precision  # noqa: E402
from repro.core.simulation import FLTask  # noqa: E402
from repro.obs import RunTelemetry  # noqa: E402
from repro.obs.trace import SpanTracer  # noqa: E402

CUSTOM_CALL = "tpu_custom_call"


def check(ok: bool, what) -> None:
    """Fail the phase (an explicit raise: `assert` is stripped under -O)."""
    if not ok:
        raise AssertionError(what)


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> dict:
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {info['platform']} devices")
    if info["count"] < chips:
        raise SystemExit(f"--chips {chips} needs {chips} TPUs, found {info['count']}")
    return info


# --------------------------------------------------------------------------
# timing helpers
# --------------------------------------------------------------------------

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


@dataclasses.dataclass
class RoundClock(SpanTracer):
    """Span tracer that marks the end of every recorded round: `run_fed_chs`
    closes an "eval" span after each eval round, so with eval_every=1 the
    marks are round boundaries (wall time, compile seconds so far, peak
    bytes).  `on_duration` is the JAX monitoring listener that counts the
    backend compile seconds."""

    compile_seconds: float = 0.0
    marks: list = dataclasses.field(default_factory=list)

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compile_seconds += duration

    @contextlib.contextmanager
    def span(self, name: str):
        with super().span(name):
            yield self
        if name == "eval":
            self.marks.append((time.perf_counter(), self.compile_seconds, peak_bytes()))


def timed_run(task, config):
    """run_fed_chs with eval_every=1; returns (result, per-round rows of
    (seconds, compile seconds, peak bytes))."""
    clock = RoundClock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    try:
        t0 = time.perf_counter()
        res = run_fed_chs(task, dataclasses.replace(
            config, eval_every=1, obs=RunTelemetry(taps=False, tracer=clock)))
    finally:
        jax.monitoring.unregister_event_duration_listener(clock.on_duration)
    rows, c0 = [], 0.0
    for t, c, peak in clock.marks:
        rows.append((t - t0, c - c0, peak))
        t0, c0 = t, c
    return res, rows


def compiled_text(fn, *args, **kwargs) -> str:
    return fn.lower(*args, **kwargs).compile().as_text()


# --------------------------------------------------------------------------
# kernels: Pallas QSGD against the ref.py oracle, on the device
# --------------------------------------------------------------------------


def phase_kernels(leaf_shape=(1024, 3072), levels=(1, 16, 127), *,
                  require_kernel: bool = True) -> None:
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)
    v = jax.random.normal(key, leaf_shape, jnp.float32)
    k = jax.random.fold_in(key, 1)
    n = math.prod(leaf_shape)
    block = ops.DEFAULT_BLOCK
    nb = -(-n // block)
    blocks = jnp.zeros((nb * block,), jnp.float32).at[:n].set(v.reshape(-1))
    blocks = blocks.reshape(nb, block)
    u = ops._cheap_uniform(k, blocks.shape)
    oracle_codes = jax.jit(ref.qsgd_quantize_codes_ref, static_argnums=2)
    oracle_pack = jax.jit(ref.pack_codes_ref, static_argnums=1)
    oracle_deq = jax.jit(ref.qsgd_dequantize_codes_ref, static_argnums=2)
    faults = []
    for s in levels:
        enc_hlo = compiled_text(ops.qsgd_encode, v, k, s=s)
        wire = ops.qsgd_encode(v, k, s=s)
        dec_hlo = compiled_text(ops.qsgd_decode, wire, s=s, shape=leaf_shape)
        back = ops.qsgd_decode(wire, s=s, shape=leaf_shape)
        codes, norms = oracle_codes(blocks, u, s)
        payload = oracle_pack(codes, ref.qsgd_code_bits(s))
        deq = oracle_deq(codes, norms, s).reshape(-1)[:n].reshape(leaf_shape)
        got_p, want_p = np.asarray(wire["payload"]), np.asarray(payload)
        got_n, want_n = np.asarray(wire["norms"]), np.asarray(norms)
        p_diff = int(np.sum(got_p != want_p))
        n_diff = int(np.sum(got_n.view(np.uint32) != want_n.view(np.uint32)))
        d_err = float(np.max(np.abs(np.asarray(back) - np.asarray(deq))))
        d_scale = float(np.max(np.abs(np.asarray(deq))))
        enc_k, dec_k = CUSTOM_CALL in enc_hlo, CUSTOM_CALL in dec_hlo
        print(f"kernels: s={s} leaf={leaf_shape} payload={got_p.shape} "
              f"encode {CUSTOM_CALL}={enc_k} decode {CUSTOM_CALL}={dec_k} "
              f"payload words differing={p_diff} norms differing={n_diff} "
              f"decode max|diff|={d_err:.3e} (max|v|={d_scale:.3e})", flush=True)
        if require_kernel and not (enc_k and dec_k):
            faults.append(f"s={s}: Pallas kernel missing from the compiled HLO")
        if p_diff or n_diff:
            faults.append(f"s={s}: {p_diff} payload words / {n_diff} norms "
                          "differ from ref.py")
        if d_err > 1e-6 * d_scale:
            faults.append(f"s={s}: decode differs from ref.py by {d_err}")
    check(not faults, "; ".join(faults))


# --------------------------------------------------------------------------
# paper path: Appendix-A MLP, delta mode, E=5, packed QSGD
# --------------------------------------------------------------------------


def paper_task() -> FLTask:
    """Synthetic MNIST, 20 Dirichlet(0.6) clients in 4 clusters, the MLP."""
    from repro.data import assign_clusters, dirichlet_partition, make_dataset
    from repro.models.classifier import make_classifier

    ds = make_dataset("mnist", train_size=3000, test_size=600, seed=0)
    clients = dirichlet_partition(ds.train_y, 20, 0.6, seed=0)
    clusters = assign_clusters(20, 4, seed=0)
    model = make_classifier("mlp", "mnist", ds.spec.image_shape, 10)
    return FLTask(model, ds, clients, clusters, batch_size=32, seed=0)


PAPER_CONFIG = FedCHSConfig(rounds=8, local_steps=10, local_epochs=5,
                            channel=QSGDChannel(16), seed=0)


def phase_paper(task: FLTask | None = None) -> None:
    task = task or paper_task()
    acc0 = task.evaluate(task.init_params())
    res, rows = timed_run(task, PAPER_CONFIG)
    for r, acc, loss, (sec, comp, peak) in zip(res.rounds, res.test_acc,
                                               res.train_loss, rows):
        print(f"paper: round {r} loss {loss:.4f} acc {acc:.4f} "
              f"seconds {sec:.3f} compile_seconds {comp:.3f} "
              f"peak_bytes_in_use {peak}", flush=True)
    print(f"paper: acc before training {acc0:.4f}, after {res.test_acc[-1]:.4f}; "
          f"ledger {res.ledger.total_bits()} bits", flush=True)
    check(all(math.isfinite(x) for x in res.train_loss), res.train_loss)
    check(res.test_acc[-1] > acc0 + 0.2, (acc0, res.test_acc))


# --------------------------------------------------------------------------
# LM path: qwen3-0.6b clients at full width
# --------------------------------------------------------------------------


def qwen3_config() -> ArchConfig:
    from repro.configs.registry import get_config

    # f32 params: the run state is the master copy under the precision policy
    return dataclasses.replace(get_config("qwen3-0.6b"), dtype="float32")


def lm_task(cfg: ArchConfig, seq: int = 128) -> FLTask:
    """2 clients, one per cluster, batch 1 (examples/train_lm_fedchs.py
    --config defaults, with flash attention on)."""
    from repro.data.sources import TokenSource
    from repro.models.fed import LMFedModel

    model = LMFedModel(cfg, remat=True, flash=True)
    source = TokenSource(cfg.vocab_size, 2, 1, seq, topics=4, seed=0)
    return FLTask.from_source(model, source, [[0], [1]], seed=0)


# 2 rounds x 2 local steps; lr 0.3 is the example's default
LM_CONFIG = FedCHSConfig(rounds=2, local_steps=2, local_epochs=1,
                         precision=Precision(), client_microbatch=1,
                         schedule=lambda k: 0.3, seed=0)


def round_program_text(task: FLTask, config: FedCHSConfig) -> str:
    """Compiled HLO of the scanned round program `run_fed_chs` runs for
    `config` (one round per chunk, as with eval_every=1)."""
    from repro.core.engine import scan_chunk_fn
    from repro.core.fed_chs import _fed_chs_scan_plan

    config = dataclasses.replace(config, eval_every=1)
    plan, _, _ = _fed_chs_scan_plan(task, task.source, config)
    xs = plan.stage(np.flatnonzero(np.asarray(plan.trained))[:1])

    def shape_of(x):
        x = jnp.asarray(x) if isinstance(x, np.ndarray) else x
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    args = jax.tree.map(shape_of, (plan.carry, xs, plan.consts))
    return compiled_text(scan_chunk_fn(plan.body), *args)


def phase_lm(cfg: ArchConfig | None = None, *, seq: int = 128,
             require_kernel: bool = True) -> None:
    cfg = cfg or qwen3_config()
    task = lm_task(cfg, seq=seq)
    config = LM_CONFIG
    print(f"lm: {cfg.name} {cfg.num_layers}L d={cfg.d_model} vocab={cfg.vocab_size} "
          f"{task.num_params()} params; 2 clients / 2 clusters, batch 1, seq {seq}, "
          f"client_microbatch=1, bf16/f32/bf16, {config.rounds} rounds x "
          f"{config.local_steps} local steps", flush=True)
    ppl0 = task.evaluate(task.init_params())
    print(f"lm: held-out ppl at init {ppl0:.2f}", flush=True)
    res, rows = timed_run(task, config)
    for r, ppl, loss, (sec, comp, peak) in zip(res.rounds, res.test_acc,
                                               res.train_loss, rows):
        print(f"lm: round {r} loss {loss:.4f} ppl {ppl:.2f} seconds {sec:.3f} "
              f"compile_seconds {comp:.3f} peak_bytes_in_use {peak}", flush=True)
    hlo = round_program_text(task, config)
    flash = CUSTOM_CALL in hlo
    print(f"lm: flash kernel ({CUSTOM_CALL}) in the compiled round: {flash}",
          flush=True)
    # Random-init logits have unit variance, so held-out ppl starts near
    # e^(1/2) * vocab, and four SGD steps on near-uniform Markov tokens
    # cannot bring it under the vocabulary size.  What the check can hold
    # the round to: no divergence, the held-out loss within one nat of the
    # uniform predictor's ln(vocab).
    check(all(math.isfinite(x) for x in res.train_loss), res.train_loss)
    check(all(math.isfinite(p) and p < math.e * cfg.vocab_size for p in res.test_acc),
          (res.test_acc, cfg.vocab_size))
    check(flash or not require_kernel,
          "flash kernel missing from the compiled round program")


# --------------------------------------------------------------------------
# sharded federation: 4 chips against 1
# --------------------------------------------------------------------------


def phase_sharded() -> None:
    from repro.launch.mesh import make_federation_mesh

    mesh = make_federation_mesh(2, 2)
    devs = list(mesh.devices.flat)
    print(f"sharded: mesh {dict(mesh.shape)} on "
          f"{[f'{d.platform}:{d.id}' for d in devs]}", flush=True)
    task = paper_task()
    config = PAPER_CONFIG
    t0 = time.perf_counter()
    one = run_fed_chs(task, config)
    t1 = time.perf_counter()
    many = run_fed_chs(task, dataclasses.replace(config, mesh=mesh))
    t2 = time.perf_counter()
    diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree.leaves(one.final_params),
                             jax.tree.leaves(many.final_params))]
    same = all(d == 0.0 for d in diffs)
    print(f"sharded: 1-device run {t1 - t0:.3f} s, mesh run {t2 - t1:.3f} s "
          "(both include compilation)", flush=True)
    print(f"sharded: params bit-identical={same} max|diff| per leaf={diffs}", flush=True)
    print(f"sharded: acc 1-device={one.test_acc} mesh={many.test_acc}", flush=True)
    print(f"sharded: ledger bits 1-device={one.ledger.total_bits()} "
          f"mesh={many.ledger.total_bits()}", flush=True)
    check(len(devs) == 4, f"mesh holds {len(devs)} devices, not 4")
    check(one.ledger.total_bits() == many.ledger.total_bits(), "ledger totals differ")
    check(one.ledger.history == many.ledger.history, "ledger histories differ")
    check(same, diffs)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded federation phase on 4 chips")
    args = ap.parse_args(argv)
    info = require_tpu(args.chips)
    from repro.utils import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        phase_sharded()
    else:
        phase_kernels()
        phase_paper()
        phase_lm()
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()

"""LM-scale suite: the memory-lean mixed-precision engine at LM client scale.

What `client_microbatch` + `Precision` buy for federated LM pretraining,
read from the REAL compiled round function (XLA memory analysis, not a
model of it):

  * lmscale/peak_bytes_{vmapped,mb1} — compiled peak-live bytes (argument +
    temp + output - donated) of one delta-mode Fed-CHS round at n_clients=8,
    all-clients-vmapped vs client_microbatch=1.  The suite fails unless mb1
    at least halves them (`GATE_PEAK`): scanning clients through one
    training slot drops the per-client activation replicas from O(n) to
    O(microbatch), which is the knob that lands a 0.6B-param client on one
    host.
  * lmscale/tokens_per_s_{toy,scaled} — XLA:CPU training throughput of the
    memory-lean configuration (bf16 compute + f32 master + bf16 wire, remat
    on the scaled arm) at toy and scaled-up dims, through the full driver.
    Informational and not chip speed (that is benchmarks/chip/).
  * lmscale/dense_wire_bf16 — the wire half of the policy: the bf16 dense
    uplink's exact `channel_wire_bits` vs the f32 dense message.  The suite
    fails unless it is EXACTLY 2.00x: the ledger prices the true payload, so
    the ratio is arithmetic, not measurement.

Standalone usage (CI's lm-scale-smoke job runs it through benchmarks/run.py):

  PYTHONPATH=src:. python benchmarks/fig_lm_scale.py [--quick]
"""
from __future__ import annotations

import time

GATE_PEAK = 2.0  # mb=1 must at least halve compiled peak-live bytes


def _lm_task(*, d_model: int, layers: int, vocab: int, seq: int, batch: int,
             clients: int, clusters: int = 1, remat: bool = False,
             seed: int = 0):
    from repro.configs.base import ArchConfig
    from repro.core.simulation import FLTask
    from repro.data.sources import TokenSource
    from repro.models.fed import LMFedModel

    cfg = ArchConfig(
        name=f"lmscale-d{d_model}l{layers}", family="dense",
        num_layers=layers, d_model=d_model,
        num_heads=max(d_model // 64, 1), num_kv_heads=max(d_model // 128, 1),
        d_ff=4 * d_model, vocab_size=vocab, dtype="float32",
    )
    model = LMFedModel(cfg, remat=remat)
    source = TokenSource(vocab, clients, batch, seq, topics=2, seed=seed)
    # clusters=1 puts every client in one round (the axis client_microbatch
    # folds — used for the direct-engine peak measurement); the driver-level
    # timed rows need >= 2 clusters for the ES topology
    members = [[i for i in range(clients) if i % clusters == m]
               for m in range(clusters)]
    task = FLTask.from_source(model, source, members, seed=seed)
    return task


def _compiled_round(task, microbatch, precision, *, local_steps=4, epochs=2):
    """Lower + compile one delta-mode round; return (compiled, seconds)."""
    import jax.numpy as jnp

    from repro.core.engine import RoundEngine, _delta_round_fn
    from repro.core.precision import dense_wire_channel

    channel = dense_wire_channel(precision)
    engine = RoundEngine(task.model, channel, client_microbatch=microbatch,
                         precision=precision)
    params = task.init_params()
    n = len(task.cluster_members[0])
    opt_state = engine.init_opt_state(params, n)
    batch = task.sample_round_batches(0, local_steps, epochs)
    gammas = jnp.asarray(task.cluster_weights(0))
    J = local_steps // epochs
    lrs = jnp.full((J, epochs), 0.05, jnp.float32)
    fn = _delta_round_fn(engine.model, channel, engine.local_opt, False,
                         microbatch, precision)
    t0 = time.time()
    compiled = fn.lower(params, opt_state, batch, gammas, lrs, None).compile()
    return compiled, time.time() - t0


def _peak_bytes(compiled) -> int:
    from repro.roofline.analysis import analyze_compiled

    return int(analyze_compiled(compiled)["memory"]["peak_bytes"])


def _round_us(task, cfg, reps: int = 2) -> float:
    """Best-of-reps steady-state round time through the full driver."""
    from repro.core import run_fed_chs

    run_fed_chs(task, cfg)  # compile + warm the engine caches
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        run_fed_chs(task, cfg)
        best = min(best, time.time() - t0)
    return best / cfg.rounds * 1e6


def run(quick: bool = True):
    from repro.comm.bits import dense_message_bits
    from repro.comm.channels import DenseChannel, channel_wire_bits
    from repro.core import FedCHSConfig
    from repro.core.precision import Precision

    rows = []
    prec = Precision()  # bf16 compute / f32 master / bf16 wire

    # -- peak-live bytes: vmapped vs microbatched, identical math ------------
    # dims chosen so per-client activations dominate params: the O(n) term
    # the microbatch knob removes is what the measurement isolates
    mem_task = _lm_task(d_model=256, layers=4, vocab=2048, seq=128,
                        batch=8 if quick else 16, clients=8)
    c0, s0 = _compiled_round(mem_task, None, prec)
    c1, s1 = _compiled_round(mem_task, 1, prec)
    p0, p1 = _peak_bytes(c0), _peak_bytes(c1)
    ratio = p0 / p1
    rows.append(("lmscale/peak_bytes_vmapped", s0 * 1e6,
                 f"peak_B={p0}_n=8_clients"))
    rows.append(("lmscale/peak_bytes_mb1", s1 * 1e6,
                 f"{ratio:.2f}x_peak_reduction_vs_vmapped"))
    print(f"  peak live bytes n=8: vmapped {p0 / 1e6:.1f} MB  mb=1 "
          f"{p1 / 1e6:.1f} MB  ({ratio:.2f}x reduction)")

    # -- tokens/s: toy vs scaled dims under the memory-lean configuration ----
    toy = _lm_task(d_model=64, layers=2, vocab=512, seq=64, batch=4,
                   clients=8, clusters=2)
    K, E = 4, 2
    cfg = FedCHSConfig(rounds=4 if quick else 12, local_steps=K,
                       local_epochs=E, eval_every=100, initial_cluster=0,
                       precision=prec, client_microbatch=2, seed=0)
    us = _round_us(toy, cfg)
    tokens = 4 * K * 4 * 64  # clients-per-cluster * steps * batch * seq
    rows.append(("lmscale/tokens_per_s_toy", us,
                 f"{tokens / (us / 1e6):.0f}_tok_s_d64_L2"))
    print(f"  toy d=64 L=2: {tokens / (us / 1e6):.0f} tok/s")

    scaled = _lm_task(d_model=256, layers=4, vocab=2048, seq=128, batch=4,
                      clients=8, clusters=2, remat=True)
    cfg_s = FedCHSConfig(rounds=2 if quick else 6, local_steps=2,
                         local_epochs=1, eval_every=100, initial_cluster=0,
                         precision=prec, client_microbatch=2, seed=0)
    us_s = _round_us(scaled, cfg_s)
    tokens_s = 4 * 2 * 4 * 128
    rows.append(("lmscale/tokens_per_s_scaled", us_s,
                 f"{tokens_s / (us_s / 1e6):.0f}_tok_s_d256_L4_remat"))
    print(f"  scaled d=256 L=4 (remat): {tokens_s / (us_s / 1e6):.0f} tok/s")

    # -- the wire half: bf16 dense uplink is EXACTLY half the f32 message ----
    d = mem_task.num_params()
    sizes = mem_task.param_leaf_sizes()
    half = channel_wire_bits(DenseChannel(wire_dtype=prec.wire), d, sizes)
    full = dense_message_bits(d)
    exact = "_exact" if full == 2 * half else "_INEXACT"
    rows.append(("lmscale/dense_wire_bf16", 0.0,
                 f"{full / half:.2f}x_vs_f32_dense{exact}"))
    print(f"  dense wire: bf16 {half} bits vs f32 {full} bits "
          f"({full / half:.2f}x{exact})")

    failures = []
    if ratio < GATE_PEAK:
        failures.append(f"lmscale/peak_bytes_mb1: {ratio:.2f}x < {GATE_PEAK:.2f}x "
                        "peak reduction vs vmapped")
    if full != 2 * half:
        failures.append(f"lmscale/dense_wire_bf16: bf16 wire is not exactly half "
                        f"the f32 dense message ({full} vs {half} bits)")
    if failures:
        raise SystemExit("; ".join(failures))
    return rows


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", default=True)
    ap.add_argument("--full", dest="quick", action="store_false")
    args = ap.parse_args()

    rows = run(quick=args.quick)
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()

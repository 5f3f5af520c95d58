"""Population-scaling suite: the device-mesh sharded round engine.

`config.mesh` maps the scanned round engine's client/cluster axes onto a
("clusters", "clients") device mesh (`repro.sharding.fed`), bit-identically
to the single-device run (tests/test_sharding_fed.py).  This suite measures
what the mesh buys at population scale:

  * population/fedavg_round_{unsharded,sharded} — steady-state scanned round
    time at a fixed population, identical math.  The sharded arm's derived
    field is the gated ratio (`run.py --json` fails below 0.9x): on forced
    host devices sharing one CPU the structural claim is *parity* — same
    total FLOPs through one core, collectives must hide under the compute —
    while on a real mesh the client-axis FLOPs split D ways.
  * population/staged_batch_n{N} — the memory half, and the reason the mesh
    raises the max simulable population: per-device bytes of the staged
    per-chunk batch shard vs the global stack.  Each device holds 1/D of the
    client axis, so population capacity scales with mesh size instead of
    capping at one device's memory.
  * population/sweep_seed_sharded — `run_sweep(mesh=...)`: the vmapped
    multi-seed sweep's leading seed axis device-sharded (pure GSPMD).

Without >= 8 devices every arm falls back to single-device (derived
`single_device_fallback`, never gated).  Standalone usage forces 8 host
devices BEFORE jax initializes:

  PYTHONPATH=src:. python benchmarks/fig_population.py [--quick]

(standalone applies the 0.9x gate itself and exits nonzero on regression —
the CI sharding-smoke job runs exactly this).
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__":  # must precede any jax import
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses
import time

GATE = 0.9  # sharded round must stay within 10% of unsharded (see run.py)


def _per_device_bytes(tree) -> int:
    """Max bytes any single device holds of `tree` (addressable shards)."""
    per: dict = {}
    import jax

    for leaf in jax.tree.leaves(tree):
        for sh in leaf.addressable_shards:
            per[sh.device] = per.get(sh.device, 0) + sh.data.nbytes
    return max(per.values())


def _global_bytes(tree) -> int:
    import jax

    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


def _population_task(num_clients: int, train_size: int, seed: int = 0):
    from repro.core.simulation import FLTask
    from repro.data import assign_clusters, dirichlet_partition, make_dataset
    from repro.models.classifier import make_classifier

    ds = make_dataset("mnist", train_size=train_size,
                      test_size=max(train_size // 5, 100), seed=seed)
    clients = dirichlet_partition(ds.train_y, num_clients, 0.6, seed=seed)
    clusters = assign_clusters(num_clients, 4, seed=seed)
    model = make_classifier("mlp", "mnist", ds.spec.image_shape, 10)
    # batch 16: puts the round in the compute-dominated regime where the
    # parity gate is meaningful — at tiny batches the client-delta gather
    # (pure memcpy on forced host devices) dominates and the ratio measures
    # memory bandwidth, not the engine (0.78x at batch 8 vs ~1.0x here)
    return FLTask(model, ds, clients, clusters, batch_size=16, seed=seed)


def _run_us(task, cfg, reps: int = 3) -> float:
    """Best-of-reps steady-state round time (min filters shared-runner noise,
    which only ever adds time)."""
    from repro.core.baselines import run_fedavg

    run_fedavg(task, cfg)  # compile + warm the engine caches
    best = float("inf")
    for _ in range(reps):
        t0 = time.time()
        run_fedavg(task, cfg)
        best = min(best, time.time() - t0)
    return best / cfg.rounds * 1e6


def _paired_us(task, cfg_a, cfg_b, reps: int = 3) -> tuple[float, float]:
    """Best-of-reps for two arms with INTERLEAVED timed calls (a, b, a, b,
    ...).  The sharded/unsharded ratio is a gate: sequential best-of
    measurements pick up slow machine-load drift on a shared container as a
    phantom (de)regression — interleaving cancels it (same fix as
    engine_speedup._steady_pair for the telemetry gate)."""
    from repro.core.baselines import run_fedavg

    run_fedavg(task, cfg_a)  # compile + warm both arms' engine caches
    run_fedavg(task, cfg_b)
    best = [float("inf"), float("inf")]
    for _ in range(reps):
        for i, cfg in enumerate((cfg_a, cfg_b)):
            t0 = time.time()
            run_fedavg(task, cfg)
            best[i] = min(best[i], time.time() - t0)
    return (best[0] / cfg_a.rounds * 1e6, best[1] / cfg_b.rounds * 1e6)


def run(quick: bool = True):
    import jax

    from repro.core.baselines import FedAvgConfig
    from repro.core.baselines.fedavg import _fedavg_scan_plan
    from repro.core.sweep import run_sweep
    from repro.launch.mesh import make_federation_mesh

    rows = []
    sharded = jax.device_count() >= 8
    mesh = make_federation_mesh(2, 4) if sharded else None
    n = 32
    rounds = 6 if quick else 24
    task = _population_task(n, 1024 if quick else 4096)
    cfg = FedAvgConfig(rounds=rounds, local_steps=8, eval_every=100,
                       chunk_rounds=rounds, seed=0)

    if sharded:
        us0, us1 = _paired_us(task, cfg, dataclasses.replace(cfg, mesh=mesh))
        rows.append(("population/fedavg_round_unsharded", us0, f"n={n}_clients"))
        speedup = us0 / us1
        rows.append(("population/fedavg_round_sharded", us1,
                     f"{speedup:.2f}x_vs_unsharded"))
        print(f"  fedavg round n={n}: unsharded {us0:.0f} us  sharded "
              f"{us1:.0f} us  ({speedup:.2f}x on {mesh.devices.size} devices)")
    else:
        us0 = _run_us(task, cfg)
        rows.append(("population/fedavg_round_unsharded", us0, f"n={n}_clients"))
        rows.append(("population/fedavg_round_sharded", us0,
                     "single_device_fallback"))
        print("  < 8 devices: sharded arms fall back to single-device")

    # memory scaling: per-device share of the staged client-axis batch stack.
    # The staged xs is THE population-proportional allocation (params/opt
    # state are tiny beside it at scale); 1/D per device => max population
    # scales with mesh size.
    for n_mem in (16, 32) if quick else (16, 32, 64):
        t_mem = _population_task(n_mem, 1024)
        c_mem = FedAvgConfig(rounds=2, local_steps=4, eval_every=100,
                             chunk_rounds=2, seed=0,
                             mesh=mesh if sharded else None)
        plan, _, _ = _fedavg_scan_plan(t_mem, t_mem.source, c_mem)
        import numpy as np

        idxs = np.flatnonzero(np.asarray(plan.trained))
        t0 = time.time()
        xs_put = plan.xs_put if plan.xs_put is not None else jax.device_put
        xs = xs_put(plan.stage(idxs))
        jax.block_until_ready(jax.tree.leaves(xs))
        us_stage = (time.time() - t0) * 1e6
        per_dev = _per_device_bytes(xs["batch"])
        tot = _global_bytes(xs["batch"])
        rows.append((f"population/staged_batch_n{n_mem}", us_stage,
                     f"per_device_B={per_dev}_of_{tot}"))
        print(f"  staged batch n={n_mem}: {per_dev / 1e6:.2f} MB/device of "
              f"{tot / 1e6:.2f} MB global ({tot / per_dev:.1f}x headroom)")

    # seed-axis sharding: the sweep's leading axis over the whole mesh
    seeds = range(8)
    sweep_cfg = FedAvgConfig(rounds=rounds, local_steps=4, eval_every=100,
                             chunk_rounds=rounds)
    run_sweep(task, sweep_cfg, seeds)
    t0 = time.time()
    run_sweep(task, sweep_cfg, seeds)
    us_sw0 = (time.time() - t0) / rounds * 1e6
    if sharded:
        run_sweep(task, sweep_cfg, seeds, mesh=mesh)
        t0 = time.time()
        run_sweep(task, sweep_cfg, seeds, mesh=mesh)
        us_sw1 = (time.time() - t0) / rounds * 1e6
        rows.append(("population/sweep_seed_sharded", us_sw1,
                     f"{us_sw0 / us_sw1:.2f}x_vs_unsharded_8seeds"))
        print(f"  sweep 8 seeds: unsharded {us_sw0:.0f} us/round  sharded "
              f"{us_sw1:.0f} us/round")
    else:
        rows.append(("population/sweep_seed_sharded", us_sw0,
                     "single_device_fallback"))
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
    for name, _us, derived in rows:
        if name == "population/fedavg_round_sharded" and derived.endswith(
                "x_vs_unsharded"):
            s = float(derived.split("x")[0])
            if s < GATE:
                print(f"PERF REGRESSION: {name}: {s:.2f}x < {GATE:.2f}x "
                      "vs unsharded", file=sys.stderr)
                sys.exit(1)


if __name__ == "__main__":
    main()

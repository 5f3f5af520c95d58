"""Population-scaling suite: the device-mesh sharded round engine.

`config.mesh` maps the scanned round engine's client/cluster axes onto a
("clusters", "clients") device mesh (`repro.sharding.fed`), bit-identically
to the single-device run (tests/test_sharding_fed.py).  This suite records
what the mesh buys at population scale, in bytes, not time:

  * population/staged_batch_n{N} — per-device bytes of the staged per-chunk
    batch shard vs the global stack, the reason the mesh raises the max
    simulable population.  Each device holds 1/D of the client axis, so
    population capacity scales with mesh size instead of capping at one
    device's memory.
  * population/sweep_seed_sharded — `run_sweep(mesh=...)`: the vmapped
    multi-seed sweep's leading seed axis device-sharded (pure GSPMD), run
    once to show the sharded path runs.

Without >= 8 devices both fall back to single-device (derived
`single_device_fallback`).  Standalone usage forces 8 host devices BEFORE
jax initializes:

  PYTHONPATH=src:. python benchmarks/fig_population.py [--quick]
"""
from __future__ import annotations

import os

if __name__ == "__main__":  # must precede any jax import
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")


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
    return FLTask(model, ds, clients, clusters, batch_size=16, seed=seed)


def run(quick: bool = True):
    import jax
    import numpy as np

    from repro.core.baselines import FedAvgConfig
    from repro.core.baselines.fedavg import _fedavg_scan_plan
    from repro.core.sweep import run_sweep
    from repro.launch.mesh import make_federation_mesh

    rows = []
    sharded = jax.device_count() >= 8
    mesh = make_federation_mesh(2, 4) if sharded else None

    # memory scaling: per-device share of the staged client-axis batch stack.
    # The staged xs is THE population-proportional allocation (params/opt
    # state are tiny beside it at scale); 1/D per device => max population
    # scales with mesh size.
    for n_mem in (16, 32) if quick else (16, 32, 64):
        t_mem = _population_task(n_mem, 1024)
        c_mem = FedAvgConfig(rounds=2, local_steps=4, eval_every=100,
                             chunk_rounds=2, seed=0, mesh=mesh)
        plan, _, _ = _fedavg_scan_plan(t_mem, t_mem.source, c_mem)
        idxs = np.flatnonzero(np.asarray(plan.trained))
        xs_put = plan.xs_put if plan.xs_put is not None else jax.device_put
        xs = xs_put(plan.stage(idxs))
        per_dev = _per_device_bytes(xs["batch"])
        tot = _global_bytes(xs["batch"])
        rows.append((f"population/staged_batch_n{n_mem}", 0.0,
                     f"per_device_B={per_dev}_of_{tot}"))
        print(f"  staged batch n={n_mem}: {per_dev / 1e6:.2f} MB/device of "
              f"{tot / 1e6:.2f} MB global ({tot / per_dev:.1f}x headroom)")

    # seed-axis sharding: the sweep's leading axis over the whole mesh
    rounds = 6 if quick else 24
    task = _population_task(32, 1024 if quick else 4096)
    sweep_cfg = FedAvgConfig(rounds=rounds, local_steps=4, eval_every=100,
                             chunk_rounds=rounds)
    results = run_sweep(task, sweep_cfg, range(8), mesh=mesh)
    acc = float(np.mean([r.final_acc() for r in results]))
    where = f"{mesh.devices.size}_devices" if sharded else "single_device_fallback"
    rows.append(("population/sweep_seed_sharded", 0.0,
                 f"8_seeds_mean_acc={acc:.3f}_{where}"))
    print(f"  sweep 8 seeds on {where}: mean final acc {acc:.3f}")
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

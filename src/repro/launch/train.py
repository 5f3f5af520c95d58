"""Production training launcher.

Two modes, selected by ``--execute``:

* default (lower-only): build the full assigned config on the production
  mesh (single- or multi-pod) and ``.lower().compile()`` the Fed-CHS round
  — the deployment path. On this CPU container the mesh is made of
  placeholder host devices (the launcher sets
  ``xla_force_host_platform_device_count`` before any jax import, same as
  dryrun.py), on a real v5e slice it is the actual chips.

* ``--execute``: run a REAL multi-round Fed-CHS training loop at reduced
  (smoke) scale on the available devices — per-cluster non-IID Markov token
  streams, the paper's eta_k schedule, sequential chain passing. This is
  what CI and the quickstart exercise.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --multi-pod
  PYTHONPATH=src python -m repro.launch.train --arch dbrx-132b --execute --rounds 50
"""
from __future__ import annotations

import os

if "XLA_FLAGS" not in os.environ:  # before any jax import (device count locks at init)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import time


def main() -> None:
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=["train_4k"])
    ap.add_argument("--variant", default="fedchs", choices=["fedchs", "hfl"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="beyond-paper optimized lowering (EXPERIMENTS.md §Perf)")
    ap.add_argument("--execute", action="store_true",
                    help="run a real reduced-scale training loop instead of lowering")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--chains", type=int, default=2, help="clusters (execute mode)")
    ap.add_argument("--batch", type=int, default=4, help="per-chain batch (execute mode)")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=2e-2)
    ap.add_argument("--K", type=int, default=20, help="paper's within-cluster steps")
    ap.add_argument("--ckpt", default=None,
                    help="execute: checkpoint dir (resumes if one exists)")
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args()

    if args.execute:
        _execute(args)
    else:
        _lower(args)


def _lower(args) -> None:
    import jax

    from repro.configs.registry import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_lowering, lower_spec

    cfg = get_config(args.arch)
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    spec = build_lowering(cfg, args.shape, mesh, variant=args.variant,
                          optimized=args.opt)
    t0 = time.time()
    lowered = lower_spec(spec, mesh)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    print(f"{spec.name} on {'2x16x16' if args.multi_pod else '16x16'} mesh: "
          f"compiled in {time.time() - t0:.1f}s")
    print("  bytes/device (argument+output+temp): "
          f"{(mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes) / 2**30:.2f} GiB")
    if cost:
        flops = cost.get("flops", 0.0)
        print(f"  HLO flops/device: {flops:.3e}")
    print("  (roofline terms: python -m repro.launch.dryrun --arch ... ; "
          "table in EXPERIMENTS.md §Roofline)")


def _execute(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import smoke_config
    from repro.data.tokens import MarkovTokens
    from repro.launch.steps import make_train_round
    from repro.models import transformer as tf
    from repro.optim.schedules import paper_sqrt_schedule

    cfg = smoke_config(args.arch)
    print(f"{args.arch} (reduced: {cfg.num_layers}L d={cfg.d_model}) "
          f"-> {cfg.param_count() / 1e6:.1f}M params, variant={args.variant}")

    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    C = args.chains
    stacked = jax.tree.map(lambda x: jnp.stack([x] * C), params)

    # per-cluster non-IID corpora: disjoint Markov topic mixtures; the rng is
    # derived from (cluster, round) so a checkpoint resume replays the exact
    # same stream.
    gens = [MarkovTokens(cfg.vocab_size, topics=4, seed=100 + c) for c in range(C)]

    def batch_for(t):
        toks = np.stack(
            [g.sample(np.random.default_rng((c + 1) * 100003 + t), args.batch,
                      args.seq + 1) for c, g in enumerate(gens)]
        )
        batch = {
            "tokens": jnp.asarray(toks[:, :, :-1]),
            "labels": jnp.asarray(toks[:, :, 1:]),
        }
        if cfg.is_encoder_decoder:
            batch["frames"] = jnp.zeros((C, args.batch, cfg.num_audio_frames, cfg.d_model),
                                        jnp.float32)
        if cfg.num_patches:
            batch["patches"] = jnp.zeros((C, args.batch, cfg.num_patches, 1024), jnp.float32)
        return batch

    # round-resumable checkpointing (npz pytree, repro/checkpoint)
    t_start = 0
    if args.ckpt:
        from repro.checkpoint.io import load_pytree, save_pytree

        pfile = os.path.join(args.ckpt, "params.npz")
        mfile = os.path.join(args.ckpt, "meta.npz")
        if os.path.exists(pfile) and os.path.exists(mfile):
            import numpy as _np

            stacked = load_pytree(pfile, stacked)
            t_start = int(_np.load(mfile)["round"]) + 1
            print(f"resumed from {args.ckpt} at round {t_start}")

    round_fn = jax.jit(make_train_round(cfg, variant=args.variant, remat=False),
                       donate_argnums=(0,))
    sched = paper_sqrt_schedule(K=args.K, half=False)
    t0 = time.time()
    for t in range(t_start, args.rounds):
        lr = jnp.float32(args.lr * sched(0) * args.K)
        stacked, loss = round_fn(stacked, batch_for(t), lr)
        if t % max(args.rounds // 10, 1) == 0 or t == args.rounds - 1:
            print(f"round {t:4d}  loss {float(loss):.4f}", flush=True)
        if args.ckpt and (t % args.ckpt_every == 0 or t == args.rounds - 1):
            import numpy as _np

            save_pytree(os.path.join(args.ckpt, "params.npz"), stacked)
            _np.savez(os.path.join(args.ckpt, "meta.npz"), round=_np.int64(t))
    print(f"done in {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()

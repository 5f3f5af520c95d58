"""Production serving launcher (decode shapes).

Two modes, mirroring launch/train.py:

* default (lower-only): build the full assigned config and
  ``.lower().compile()`` the serve_step (ONE token vs a seq_len KV/state
  cache) on the production mesh — the deployment path for decode_32k /
  long_500k.

* ``--execute``: a real continuous-batching serving loop at reduced (smoke)
  scale on CPU: a request queue, fixed batch slots, per-slot prefill
  (teacher-forced cache fill), greedy decode, and slot recycling when a
  request finishes — the serving analogue of the train driver.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-32b --shape decode_32k
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --execute --requests 12
"""
from __future__ import annotations

import argparse
import os
import time


def main() -> None:
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    # Set before any jax BACKEND INIT (jax reads XLA_FLAGS lazily, at first
    # use) — and only on the CLI path: importing this module (e.g. tests
    # pulling in serve_loop) must not force a 512-device partition on the
    # host process, which perturbs XLA:CPU's compute partitioning and with
    # it the bit-exact engine parity pins.
    if "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default=None,
                    help="model architecture (required except --federation)")
    ap.add_argument("--shape", default="decode_32k", choices=["decode_32k", "long_500k"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--execute", action="store_true")
    ap.add_argument("--requests", type=int, default=8, help="execute: total requests")
    ap.add_argument("--slots", type=int, default=4, help="execute: concurrent batch slots")
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--federation", action="store_true",
                    help="run the async federation service (repro.async_fl) "
                         "with continuous checkpointing instead of serving")
    ap.add_argument("--checkpoint", default=None,
                    help="federation: run-state path prefix (continuous save)")
    ap.add_argument("--resume", action="store_true",
                    help="federation: resume from --checkpoint if present")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--clients", type=int, default=12)
    ap.add_argument("--clusters", type=int, default=3)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--quorum-frac", type=float, default=1.0)
    ap.add_argument("--deadline-s", type=float, default=None)
    ap.add_argument("--churn-p", type=float, default=1.0,
                    help="federation: per-(client, activation) availability")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kill-after-activation", type=int, default=None,
                    help=argparse.SUPPRESS)  # crash-test: os._exit after the
    #   checkpoint at this activation lands — simulates a hard kill mid-run
    args = ap.parse_args()
    if args.federation:
        _federation(args)
    else:
        if args.arch is None:
            ap.error("--arch is required unless --federation")
        if args.execute:
            _execute(args)
        else:
            _lower(args)


def _federation(args) -> None:
    """Async federation as a service: event-driven Fed-CHS with continuous
    crash-safe checkpointing.  Kill the process at any point; relaunching
    with --resume continues bit-identical to an uninterrupted run (the
    subprocess parity test in tests/test_resume_parity.py drives exactly
    this entry point, using the hidden --kill-after-activation switch to
    die mid-run immediately after a checkpoint lands)."""
    import json

    from repro.async_fl import AsyncFedCHSConfig, run_async_fed_chs
    from repro.core.simulation import FLTask
    from repro.data import assign_clusters, dirichlet_partition, make_dataset
    from repro.models.classifier import make_classifier
    from repro.part import AlwaysOn, BernoulliTrace

    ds = make_dataset("mnist", train_size=2000, test_size=400, seed=args.seed)
    clients = dirichlet_partition(ds.train_y, args.clients, 0.6, seed=args.seed)
    clusters = assign_clusters(args.clients, args.clusters, seed=args.seed)
    model = make_classifier("mlp", "mnist", ds.spec.image_shape, 10)
    task = FLTask(model, ds, clients, clusters, batch_size=16, seed=args.seed)

    on_checkpoint = None
    if args.kill_after_activation is not None:
        def on_checkpoint(a: int) -> None:
            if a >= args.kill_after_activation:
                print(f"killed after activation {a}", flush=True)
                os._exit(1)  # hard kill: no atexit, no flushes — a real crash

    trace = (AlwaysOn() if args.churn_p >= 1.0
             else BernoulliTrace(p=args.churn_p, seed=args.seed + 17))
    config = AsyncFedCHSConfig(
        rounds=args.rounds, local_steps=args.local_steps,
        initial_cluster=0, quorum_frac=args.quorum_frac,
        deadline_s=args.deadline_s, trace=trace, eval_every=5,
        seed=args.seed, checkpoint=args.checkpoint, resume=args.resume,
        on_checkpoint=on_checkpoint,
    )
    t0 = time.time()
    res = run_async_fed_chs(task, config)
    print(json.dumps({
        "algo": res.name,
        "rounds": res.rounds,
        "test_acc": res.test_acc,
        "sim_times": res.sim_times,
        "total_bits": int(res.ledger.total_bits()),
        "staleness": {str(k): v for k, v in
                      res.ledger.staleness_histogram().items()},
        "wall_s": round(time.time() - t0, 2),
    }))


def _lower(args) -> None:
    from repro.configs.registry import get_config, long_context_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_lowering, lower_spec

    cfg = (long_context_config(args.arch) if args.shape == "long_500k"
           else get_config(args.arch))
    mesh = make_production_mesh(multi_pod=args.multi_pod)
    spec = build_lowering(cfg, args.shape, mesh)
    t0 = time.time()
    compiled = lower_spec(spec, mesh).compile()
    mem = compiled.memory_analysis()
    print(f"{spec.name} on {'2x16x16' if args.multi_pod else '16x16'} mesh: "
          f"compiled in {time.time() - t0:.1f}s")
    print("  bytes/device: "
          f"{(mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes) / 2**30:.2f} GiB")


def _splice_slot(base, donor, s: int):
    """Caches equal to `base` everywhere except batch slot `s`, taken from
    `donor`.  `tail` block caches carry the batch on axis 0; `super` blocks
    are `_stack_trees`-stacked, pushing batch to axis 1."""
    import jax

    def at(axis):
        def f(b, d):
            idx = (slice(None),) * axis + (s,)
            return b.at[idx].set(d[idx])

        return f

    return {
        "super": [jax.tree.map(at(1), b, d)
                  for b, d in zip(base["super"], donor["super"])],
        "tail": [jax.tree.map(at(0), b, d)
                 for b, d in zip(base["tail"], donor["tail"])],
    }


def serve_loop(cfg, params, *, requests: int, slots: int, prompt_len: int,
               max_new: int):
    """Continuous-batching greedy decode; returns ({request: tokens}, steps).

    Each request yields exactly `max_new` tokens: the prefill's last-position
    argmax plus `max_new - 1` batched decode steps (the retire test at
    `slot_gen >= max_new - 1` counts decode tokens only — the prefill token
    was appended at admit time).

    Admission prefills ONE slot against the shared (batch-wide) compiled
    decode step, then splices: the slot is first zeroed from a fresh cache
    (a recycled slot's `len` counter must restart at position 0), the
    prompt is teacher-forced through the batch step, and only slot `s`'s
    cache rows are kept — every other slot's KV/state is restored from the
    pre-admission snapshot.  Without the splice the batch-wide prefill
    advances ALL slots' caches `prompt_len` positions, corrupting every
    in-flight request (the cross-slot contamination bug this replaced):
    solo and batched decodes of the same request then diverge
    (tests/test_serve_exec.py pins solo == batched).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.tokens import synthetic_lm_batch
    from repro.models import transformer as tf

    S = slots
    capacity = prompt_len + max_new
    enc_len = cfg.num_audio_frames if cfg.is_encoder_decoder else 0
    fresh = tf.init_caches(cfg, S, capacity, enc_len=enc_len)
    caches = fresh
    step = jax.jit(lambda p, c, t: tf.decode_step(cfg, p, c, t))

    pending = list(range(requests))  # request ids
    prompts = {
        r: synthetic_lm_batch(cfg.vocab_size, 1, prompt_len, seed=r)["tokens"][0]
        for r in pending
    }
    # slot state: request id (or -1), tokens generated, next input token
    slot_req = [-1] * S
    slot_gen = [0] * S
    cur_tok = np.zeros((S, 1), np.int32)
    done: dict[int, list[int]] = {}
    steps = 0

    def admit(s: int) -> None:
        """Prefill request into slot s by teacher-forced ingestion."""
        nonlocal caches
        r = pending.pop(0)
        slot_req[s], slot_gen[s] = r, 0
        snapshot = caches
        caches = _splice_slot(caches, fresh, s)  # slot restarts at position 0
        for t in range(prompt_len):
            tok = np.array(cur_tok)
            tok[s, 0] = prompts[r][t]
            logits, caches = step(params, caches, jnp.asarray(tok))
        caches = _splice_slot(snapshot, caches, s)  # others: pre-admit state
        cur_tok[s, 0] = int(jnp.argmax(logits[s]))
        done[r] = [int(cur_tok[s, 0])]

    while pending or any(r >= 0 for r in slot_req):
        for s in range(S):
            if slot_req[s] < 0 and pending:
                admit(s)
        logits, caches = step(params, caches, jnp.asarray(cur_tok))
        steps += 1
        nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        for s in range(S):
            r = slot_req[s]
            if r < 0:
                continue
            slot_gen[s] += 1
            done[r].append(int(nxt[s]))
            cur_tok[s, 0] = nxt[s]
            if slot_gen[s] >= max_new - 1:
                slot_req[s] = -1  # retire; slot is re-admitted next iteration

    return done, steps


def _execute(args) -> None:
    import jax

    from repro.configs.registry import smoke_config
    from repro.models import transformer as tf

    cfg = smoke_config(args.arch)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    t0 = time.time()
    done, steps = serve_loop(
        cfg, params, requests=args.requests, slots=args.slots,
        prompt_len=args.prompt_len, max_new=args.max_new,
    )
    dt = time.time() - t0
    total = sum(len(v) for v in done.values())
    print(f"arch={cfg.name} (reduced) | {args.requests} requests over "
          f"{args.slots} slots | "
          f"{total} tokens in {dt:.1f}s ({total / max(dt, 1e-9):.1f} tok/s, "
          f"{steps} batched decode steps)")
    for r in list(done)[:2]:
        print(f"request {r}: {done[r][:12]} ...")


if __name__ == "__main__":
    main()

"""Federated transformer-LM pretraining with Fed-CHS — on the unified stack.

This used to be a side-path that called the raw transformer train step and
bypassed the round engine, the compression channels, the bit ledger, and the
network simulator.  It is now an `LMFedModel` + `TokenSource` FedTask driven
by the same `run_fed_chs` as the paper's MLP/LeNet experiments, which buys
the LM workload everything the classifier path already had:

  * QSGD/Top-K compressed uplinks (pick with --qsgd / --topk);
  * bit-exact per-message `CommLedger` accounting + `CommEvent` streams;
  * `repro.netsim` replay: simulated wall-clock time-to-perplexity under a
    configurable edge network;
  * client-held local optimizers (--adamw keeps AdamW moments on-device —
    uplink bits are identical to plain SGD).

Each client's token stream is non-IID (topic-skewed Markov chains over a
shared transition table), and every batch draw is keyed by
``(seed, client, draw_index)`` — the stream position is explicit, so a
resumed run replays the exact schedule of batches instead of resampling
from scratch (the old `batch_for(round_idx)` ignored its argument).

Defaults are CPU-sized (a few minutes).  Scale up with e.g.:
  PYTHONPATH=src python examples/train_lm_fedchs.py --d-model 768 --layers 12 \
      --vocab 32768 --seq 256 --batch 8 --rounds 300

--config <arch-id> swaps the hand-rolled dims for a registry architecture
and turns on the memory-lean engine configuration (bf16 compute + f32
master + bf16 dense wire, gradient rematerialization, and whatever
--client-microbatch you pass).  This is the 0.6B-client-scale entry point:

  PYTHONPATH=src python examples/train_lm_fedchs.py \
      --config qwen3_0_6b --client-microbatch 1

completes one full Fed-CHS round of qwen3-0.6b clients on a single host —
the microbatched engine holds ONE client's bf16 training state at a time,
so peak memory is model-sized, not population-sized (documented budget:
<= 24 GB peak RSS on CPU; see README "Memory model & mixed precision").
Config-mode defaults are one round of 2 clients / 2 clusters at batch 1,
seq 128 — every knob stays overridable.
"""
import argparse
import re
import resource
import time

from repro.comm.channels import DenseChannel, QSGDChannel, TopKChannel
from repro.configs.base import ArchConfig
from repro.core import FedCHSConfig, run_fed_chs
from repro.core.precision import Precision
from repro.core.simulation import FLTask
from repro.data.sources import TokenSource
from repro.models.fed import LMFedModel
from repro.netsim.adapters import simulate_run, time_to_accuracy
from repro.netsim.links import NetworkModel
from repro.optim.local import AdamWOpt

# documented peak-RSS budget for the --config qwen3_0_6b --client-microbatch 1
# acceptance run (master params 2.4 GB f32 + one client's bf16 compute state
# + XLA compile workspace, measured on CPU with headroom)
QWEN3_BUDGET_GB = 24.0


def _resolve_arch(name: str):
    """Registry id lookup, tolerant of -/_/. spelling (qwen3_0_6b works)."""
    import dataclasses

    from repro.configs.registry import ARCH_IDS, get_config

    key = re.sub(r"[^a-z0-9]", "", name.lower())
    for arch_id in ARCH_IDS:
        if re.sub(r"[^a-z0-9]", "", arch_id) == key:
            # f32 params: the run state IS the master copy under the
            # mixed-precision policy (the engine casts down per round)
            return arch_id, dataclasses.replace(get_config(arch_id),
                                                dtype="float32")
    raise SystemExit(f"unknown --config {name!r}; choose from {ARCH_IDS}")


def main():
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None, metavar="ARCH",
                    help="registry architecture id (e.g. qwen3_0_6b); "
                         "overrides --d-model/--layers/--vocab and turns on "
                         "the memory-lean defaults (bf16 compute, f32 "
                         "master, remat, 1 round of 2 clients)")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=4096)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=None, help="per-client batch")
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--clusters", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--local-steps", type=int, default=None,
                    help="K in-cluster steps/round")
    ap.add_argument("--local-epochs", type=int, default=None,
                    help="E steps per upload")
    ap.add_argument("--client-microbatch", type=int, default=None,
                    help="clients trained simultaneously per round (None = "
                         "all at once); 1 is the memory-lean setting")
    ap.add_argument("--mixed-precision", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="bf16 compute / f32 master / bf16 dense wire "
                         "(default: on with --config, off otherwise)")
    ap.add_argument("--remat", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="gradient rematerialization (default: on with "
                         "--config, off otherwise)")
    ap.add_argument("--qsgd", type=int, default=None,
                    help="QSGD levels for the client->ES uplink (0 = dense; "
                         "default 16, or 0 with --config where the bf16 "
                         "dense wire takes over)")
    ap.add_argument("--topk", type=float, default=0.0,
                    help="Top-K uplink fraction (overrides --qsgd when > 0)")
    ap.add_argument("--adamw", action="store_true",
                    help="client-held AdamW instead of plain SGD")
    ap.add_argument("--lr", type=float, default=0.3)
    ap.add_argument("--eval-every", type=int, default=None)
    ap.add_argument("--target-ppl", type=float, default=40.0,
                    help="perplexity threshold for the time-to-loss replay")
    args = ap.parse_args()

    lean = args.config is not None
    # config mode defaults to ONE memory-budgeted round at LM scale; toy mode
    # keeps the historical few-minute CPU run
    rounds = args.rounds if args.rounds is not None else (1 if lean else 40)
    local_steps = args.local_steps if args.local_steps is not None else \
        (2 if lean else 4)
    local_epochs = args.local_epochs if args.local_epochs is not None else \
        (1 if lean else 2)
    batch = args.batch if args.batch is not None else (1 if lean else 4)
    clients = args.clients if args.clients is not None else (2 if lean else 4)
    eval_every = args.eval_every if args.eval_every is not None else \
        (1 if lean else 5)
    qsgd = args.qsgd if args.qsgd is not None else (0 if lean else 16)
    mixed = args.mixed_precision if args.mixed_precision is not None else lean
    remat = args.remat if args.remat is not None else lean

    if lean:
        arch_id, cfg = _resolve_arch(args.config)
        print(f"arch {arch_id}: {cfg.num_layers}L d={cfg.d_model} "
              f"vocab={cfg.vocab_size}")
    else:
        cfg = ArchConfig(
            name="fedchs-lm", family="dense", num_layers=args.layers,
            d_model=args.d_model, num_heads=max(args.d_model // 64, 1),
            num_kv_heads=max(args.d_model // 128, 1), d_ff=4 * args.d_model,
            vocab_size=args.vocab, dtype="float32",
        )
    model = LMFedModel(cfg, remat=remat)
    source = TokenSource(cfg.vocab_size, clients, batch, args.seq,
                         topics=args.clusters * 2, seed=0)
    members = [[i for i in range(clients) if i % args.clusters == m]
               for m in range(args.clusters)]
    task = FLTask.from_source(model, source, members, seed=0)
    precision = Precision() if mixed else None
    print(f"model: {cfg.num_layers}L d={cfg.d_model} -> "
          f"{task.num_params()/1e6:.1f}M params, "
          f"{clients} clients / {args.clusters} ES clusters"
          + (f", microbatch={args.client_microbatch}"
             if args.client_microbatch else "")
          + (", bf16 compute / f32 master" if mixed else ""))

    if args.topk > 0:
        channel = TopKChannel(fraction=args.topk)
    elif qsgd > 0:
        channel = QSGDChannel(qsgd)
    elif precision is None:
        channel = DenseChannel()
    else:
        channel = None  # FedCHSConfig resolves the bf16 dense wire
    config = FedCHSConfig(
        rounds=rounds, local_steps=local_steps, local_epochs=local_epochs,
        eval_every=eval_every, channel=channel, seed=0,
        precision=precision, client_microbatch=args.client_microbatch,
        local_opt=AdamWOpt(weight_decay=0.0) if args.adamw else None,
        schedule=lambda k: args.lr,
    )

    t0 = time.time()
    res = run_fed_chs(task, config)
    wall = time.time() - t0
    for r, ppl, loss in zip(res.rounds, res.test_acc, res.train_loss):
        print(f"round {r:4d}  train loss {loss:.4f}  held-out ppl {ppl:8.2f}")
    print(f"done in {wall:.0f}s — uniform vocab ppl would be {cfg.vocab_size}")

    peak_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    budget = f" (budget <= {QWEN3_BUDGET_GB:.0f} GB)" if lean else ""
    print(f"peak RSS: {peak_gb:.1f} GB{budget}")
    if lean and peak_gb > QWEN3_BUDGET_GB:
        print(f"WARNING: over the documented {QWEN3_BUDGET_GB:.0f} GB budget")

    from repro.core.precision import resolve_channel

    mb = res.ledger.total_megabytes()
    resolved = resolve_channel(precision, channel)
    wire = getattr(resolved, "wire_dtype", None)
    ch_name = resolved.__class__.__name__ + (f"[{wire}]" if wire else "")
    print(f"\ncommunication: {mb:,.1f} MB total ({ch_name} uplink)")
    for hop, bits in res.ledger.breakdown().items():
        print(f"  {hop:15s} {bits / 8 / 1e6:10.1f} MB")

    timeline = simulate_run(task, res, NetworkModel(), local_steps=local_steps)
    tta = time_to_accuracy(res, timeline, args.target_ppl)
    print(f"\nnetsim replay (default edge network): one pass of this run takes "
          f"{timeline.makespan:,.1f}s of simulated wall-clock")
    if tta is None:
        print(f"never reached ppl <= {args.target_ppl}; best {res.best_acc():.2f} "
              "(raise --rounds or --lr)")
    else:
        print(f"time to ppl <= {args.target_ppl}: {tta:,.1f}s simulated")


if __name__ == "__main__":
    main()

"""Async vs sync Fed-CHS: simulated time-to-accuracy under churn/stragglers.

The synchronous chain is barrier-synchronous per activation: the ES waits
for EVERY cluster member before the model hops on, so one 16x straggler in
the active cluster stalls the whole sequential pass.  The async service
(`repro.async_fl`) fires at the quorum arrival (capped by a deadline) and
folds late updates staleness-discounted on the chain's next visit — it
trades a little statistical efficiency per fold for a lot of simulated
wall-clock.

Method, per scenario:
  * sync — train once with CommEvents on, replay through the scenario's
    `NetworkModel` (`repro.netsim.simulate_run`), read wall-clock-to-Γ;
  * async — actually EXECUTE under the same network + an availability trace
    (arrival times drive the event loop), read `sim_time_to_accuracy(Γ)`.

The async PS baselines (FedBuff FedAvg, two-tier Hier) run as context arms.
The derived field of each `asyncfl/<scenario>-fedchs_async` row carries
``<x>x_vs_sync_t2gamma``; the suite fails unless async beats sync in at
least one scenario.  The times are simulated, so the check reads no clock.
"""
from __future__ import annotations

import time

from benchmarks.common import BenchScale, build_task, run_algorithm
from repro.async_fl import (
    AsyncFedCHSConfig,
    AsyncPSConfig,
    run_async_fed_chs,
    run_async_fedavg,
    run_async_hier,
)
from repro.netsim import edge_cloud_network, simulate_run, time_to_accuracy
from repro.part import AlwaysOn, BernoulliTrace

GAMMA = 0.70  # below fig_time_to_acc's 0.80: partial-quorum folds give up a
              # little per-round progress, and the gate needs every arm to
              # cross the target at reduced scale

# scenario -> (network factory, availability trace factory, async knobs).
# Both regimes are ones where waiting for the full cohort is the bottleneck.
SCENARIOS = {
    # hard stragglers: a 16x-slow client stalls every sync visit to its
    # cluster; the async ES fires at the 70% quorum and folds the straggler's
    # update (discounted) when the chain comes back
    "straggler": dict(
        network=lambda: edge_cloud_network(seed=0, heterogeneity=0.4,
                                           straggler_frac=0.3,
                                           straggler_slowdown=16.0),
        trace=AlwaysOn,
        quorum_frac=0.7, deadline_s=None,
    ),
    # device churn + moderate stragglers: sync still waits for every member
    # it dispatched; async only dispatches the clients that are up and caps
    # its wait with a deadline
    "churn": dict(
        network=lambda: edge_cloud_network(seed=0, heterogeneity=0.3,
                                           straggler_frac=0.15,
                                           straggler_slowdown=8.0),
        trace=lambda: BernoulliTrace(p=0.8, seed=7),
        quorum_frac=0.8, deadline_s=5.0,
    ),
}


def _fmt(t):
    return "-" if t is None else f"{t:.2f}"


def run(quick: bool = True):
    scale = BenchScale() if quick else BenchScale.paper()
    task = build_task("mnist", "mlp", 0.6, scale)
    rows = []

    # one sync training run; CommEvents let every scenario re-time it host-side
    res_sync, wall = run_algorithm("fed_chs", task, scale, seed=0,
                                   track_events=True)
    rows.append(("asyncfl/train-fed_chs_sync", wall * 1e6 / scale.rounds,
                 f"final_acc={res_sync.final_acc():.3f}"))

    print(f"\nSimulated time-to-Γ (Γ={GAMMA}, seconds; '-' = not reached):")
    wins = 0
    for scen, spec in SCENARIOS.items():
        net = spec["network"]()
        tl = simulate_run(task, res_sync, net, local_steps=scale.local_steps)
        t_sync = time_to_accuracy(res_sync, tl, GAMMA)

        t0 = time.time()
        res_async = run_async_fed_chs(task, AsyncFedCHSConfig(
            rounds=scale.rounds, local_steps=scale.local_steps,
            eval_every=scale.eval_every, network=net, trace=spec["trace"](),
            quorum_frac=spec["quorum_frac"], deadline_s=spec["deadline_s"],
            seed=0))
        t_async = res_async.sim_time_to_accuracy(GAMMA)
        wall_async = time.time() - t0

        if t_sync is not None and t_async is not None and t_async < t_sync:
            wins += 1
            derived = f"{t_sync / t_async:.2f}x_vs_sync_t2gamma"
        elif t_sync is not None and t_async is not None:
            derived = f"{t_sync / t_async:.2f}x_vs_sync_t2gamma"
        else:
            derived = f"t2gamma_s={_fmt(t_async)}_sync={_fmt(t_sync)}"
        rows.append((f"asyncfl/{scen}-fedchs_sync", 0.0,
                     f"t2gamma_s={_fmt(t_sync)}"))
        rows.append((f"asyncfl/{scen}-fedchs_async",
                     wall_async * 1e6 / scale.rounds, derived))

        # async-PS context arms under the same physical network
        ps_cfg = AsyncPSConfig(rounds=scale.rounds, local_steps=scale.local_steps,
                               quorum_k=max(task.num_clients // 5, 2),
                               eval_every=scale.eval_every, network=net,
                               trace=spec["trace"](), seed=0)
        for arm, runner in (("fedavg_async", run_async_fedavg),
                            ("hier_async", run_async_hier)):
            r = runner(task, ps_cfg)
            rows.append((f"asyncfl/{scen}-{arm}", 0.0,
                         f"t2gamma_s={_fmt(r.sim_time_to_accuracy(GAMMA))}"))

        print(f"{scen:12s} sync={_fmt(t_sync):>8s}s  async={_fmt(t_async):>8s}s"
              f"  acc_async={res_async.final_acc():.3f}"
              f"  staleness={res_async.ledger.staleness_histogram()}")

    rows.append(("asyncfl/scenarios-won", float(wins),
                 f"async_beats_sync_in_{wins}_of_{len(SCENARIOS)}"))
    if not wins:
        raise SystemExit("asyncfl: async Fed-CHS beat sync in no scenario "
                         "(simulated time-to-accuracy)")
    return rows

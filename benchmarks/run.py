# One function per paper table or algorithmic suite; prints
# ``name,us_per_call,derived`` CSV.  The timings are XLA:CPU wall clock, not
# chip speed: speed is measured on the TPU by benchmarks/chip/run.py and
# recorded in PERF_LEDGER.jsonl.  A suite's own checks (exact bits, compiled
# peak bytes, simulated time to accuracy) raise inside the suite.
from __future__ import annotations

import argparse
import time


def main() -> None:
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (hours on CPU); default is reduced")
    ap.add_argument("--only", default=None,
                    help="comma list: table1,fig2,fig3,fig4,roofline,timeacc,"
                         "participation,population,asyncfl,lmscale")
    ap.add_argument("--profile", nargs="?", const="fed_chs", default=None,
                    metavar="ALGO",
                    help="run one short instrumented run (telemetry taps + "
                         "spans + netsim replay) and write the merged "
                         "Perfetto trace / metrics / summary to "
                         "experiments/obs/ instead of the benchmark suites")
    args = ap.parse_args()
    quick = not args.full

    if args.profile is not None:
        from benchmarks import profile_obs

        profile_obs.run_profile(args.profile, quick=quick)
        return

    from benchmarks import fig2_comm, fig3_hparams, fig4_partial_het, fig_async
    from benchmarks import fig_lm_scale, fig_participation, fig_population
    from benchmarks import fig_time_to_acc, roofline, table1_accuracy

    suites = {
        "table1": table1_accuracy.run,
        "fig2": fig2_comm.run,
        "fig3": fig3_hparams.run,
        "fig4": fig4_partial_het.run,
        "roofline": roofline.run,
        "timeacc": fig_time_to_acc.run,  # netsim smoke: wall-clock time-to-Γ
        "participation": fig_participation.run,  # churn: bits + deadline replay
        "population": fig_population.run,  # device-mesh sharded client axis
        "asyncfl": fig_async.run,  # async event-loop vs sync barrier chain
        "lmscale": fig_lm_scale.run,  # microbatch peak memory + bf16 wire
    }
    selected = args.only.split(",") if args.only else list(suites)

    all_rows = []
    for name in selected:
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        rows = suites[name](quick=quick)
        dt = time.time() - t0
        all_rows.extend(rows)
        print(f"[{name} done in {dt:.1f}s]", flush=True)

    print("\nname,us_per_call,derived")
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()

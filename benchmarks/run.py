# One function per paper table. Print ``name,us_per_call,derived`` CSV and
# optionally (--json) write machine-readable results to BENCH_core.json so
# the perf trajectory is tracked across PRs.
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

BENCH_JSON = os.path.join(os.path.dirname(__file__), "..", "BENCH_core.json")


def _speedup(derived: str) -> float | None:
    """Parse the leading '<x>x_vs_<ref>' speedup factor from a derived field."""
    m = re.match(r"([\d.]+)x", derived)
    return float(m.group(1)) if m else None


def main() -> None:
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (hours on CPU); default is reduced")
    ap.add_argument("--only", default=None,
                    help="comma list: table1,fig2,fig3,fig4,kernels,roofline,"
                         "engine,timeacc,participation,population,asyncfl,"
                         "lmscale")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_core.json (suite, rows, wall-clock; for the "
                         "engine suite also the scanned-vs-looped speedups) and "
                         "fail if the scanned whole-run driver is slower than "
                         "the looped one or a packed-QSGD round is slower than "
                         "the dense-code baseline")
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

    from benchmarks import engine_speedup, fig2_comm, fig3_hparams, fig4_partial_het
    from benchmarks import fig_async, fig_lm_scale, fig_participation, fig_population
    from benchmarks import fig_time_to_acc, kernels_micro, roofline, table1_accuracy

    suites = {
        "table1": table1_accuracy.run,
        "fig2": fig2_comm.run,
        "fig3": fig3_hparams.run,
        "fig4": fig4_partial_het.run,
        "kernels": kernels_micro.run,
        "roofline": roofline.run,
        "engine": engine_speedup.run,
        "timeacc": fig_time_to_acc.run,  # netsim smoke: wall-clock time-to-Γ
        "participation": fig_participation.run,  # churn: bits + deadline replay
        "population": fig_population.run,  # device-mesh sharded client axis
        "asyncfl": fig_async.run,  # async event-loop vs sync barrier chain
        "lmscale": fig_lm_scale.run,  # microbatch peak memory + bf16 wire
    }
    selected = args.only.split(",") if args.only else list(suites)

    all_rows = []
    suite_results = {}
    for name in selected:
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        rows = suites[name](quick=quick)
        dt = time.time() - t0
        suite_results[name] = {
            "wall_s": round(dt, 1),
            "rows": [
                {"name": n, "us_per_call": round(us, 1), "derived": d}
                for n, us, d in rows
            ],
        }
        all_rows.extend(rows)
        print(f"[{name} done in {dt:.1f}s]", flush=True)

    print("\nname,us_per_call,derived")
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")

    if not args.json:
        return

    payload = {"quick": quick, "suites": suite_results}
    failures = []
    if "engine" in suite_results:
        headline = {}
        for row in suite_results["engine"]["rows"]:
            s = _speedup(row["derived"])
            if s is None:
                continue
            headline[row["name"]] = {"speedup": s, "ref": row["derived"]}
            # the perf gate: the scanned whole-run driver must not be slower
            # than the looped driver it replaces.  Only the HOST-BOUND arms
            # are gated (their structural speedup is ~1.2-1.4x, leaving real
            # margin above the 0.9 noise floor on shared 2-core runners);
            # compute-bound arms sit at ~1.0x by construction — the scan
            # cannot beat the FLOP floor — so gating them would only convert
            # timing noise into red CI.  They are still recorded in the JSON.
            gated = ("scanned_fed_chs_grad", "scanned_wrwgd")
            if row["name"] in gated and "vs_looped_driver" in row["derived"]:
                if s < 0.9:
                    failures.append(
                        f"{row['name']}: {s:.2f}x < 0.90x vs looped driver")
            # the telemetry gate: in-graph taps + host spans must cost < 10%
            # wall-clock vs the identical untapped scanned run (0.91x ~=
            # 1/1.10) — observability has to be cheap enough to leave on
            if (row["name"] == "scanned_fed_chs_telemetry"
                    and "vs_untapped" in row["derived"] and s < 0.91):
                failures.append(
                    f"{row['name']}: {s:.2f}x < 0.91x vs untapped "
                    "(taps cost >10% wall-clock)")
        payload["engine_headline"] = headline
    if "kernels" in suite_results:
        # the packed-wire gate: a Fed-CHS round on the packed QSGDChannel
        # must not regress below the dense-f32-code baseline.  0.8, not 1.0:
        # the structural claim is parity (packing arithmetic hides under the
        # training compute), and few-ms rounds on shared runners carry real
        # timing noise; the wire-size win itself is exact and ledger-pinned.
        for row in suite_results["kernels"]["rows"]:
            if row["name"] != "round/fed_chs_packed_qsgd":
                continue
            s = _speedup(row["derived"])
            payload["kernels_headline"] = {row["name"]: {
                "speedup": s, "ref": row["derived"]}}
            if s is not None and s < 0.8:
                failures.append(
                    f"{row['name']}: {s:.2f}x < 0.80x vs dense-code QSGD")
    if "population" in suite_results:
        # the sharding gate: the device-mesh sharded round must stay within
        # 10% of the unsharded run.  On forced host devices (one physical
        # core) the claim is structural parity — identical total FLOPs, the
        # mesh collectives must hide under the compute; the fleet-level win
        # is the per-device memory scaling recorded in the staged_batch rows.
        # Single-device fallback rows carry no '<x>x' prefix and gate nothing.
        for row in suite_results["population"]["rows"]:
            if row["name"] != "population/fedavg_round_sharded":
                continue
            s = _speedup(row["derived"])
            payload["population_headline"] = {row["name"]: {
                "speedup": s, "ref": row["derived"]}}
            if s is not None and s < fig_population.GATE:
                failures.append(
                    f"{row['name']}: {s:.2f}x < {fig_population.GATE:.2f}x "
                    "vs unsharded")
    if "asyncfl" in suite_results:
        # the async gate: the event-driven Fed-CHS service must reach the
        # target accuracy in less SIMULATED wall-clock than the synchronous
        # chain in at least one churn/straggler scenario — that is the whole
        # claim of the async service (the arithmetic itself is anchored
        # bit-exactly to sync in tests/test_async_fl.py, so this gate is
        # about the timing model, not correctness)
        headline = {}
        best = 0.0
        for row in suite_results["asyncfl"]["rows"]:
            if not row["name"].endswith("-fedchs_async"):
                continue
            s = _speedup(row["derived"])
            headline[row["name"]] = {"speedup": s, "ref": row["derived"]}
            if s is not None:
                best = max(best, s)
        payload["asyncfl_headline"] = headline
        if headline and best <= 1.0:
            failures.append(
                f"asyncfl: async Fed-CHS beat sync in no scenario "
                f"(best {best:.2f}x <= 1.00x simulated time-to-accuracy)")
    if "lmscale" in suite_results:
        # the memory gate: client_microbatch=1 must at least HALVE the
        # compiled peak-live bytes of the n=8 round vs the all-clients vmap —
        # XLA's own memory analysis, so the number is structural, not timing
        # noise.  The wire gate is exact arithmetic: the bf16 dense uplink is
        # half the f32 message bit-for-bit or the ledger is lying.
        headline = {}
        for row in suite_results["lmscale"]["rows"]:
            s = _speedup(row["derived"])
            if s is not None:
                headline[row["name"]] = {"ratio": s, "ref": row["derived"]}
            if row["name"] == "lmscale/peak_bytes_mb1" and s is not None:
                if s < fig_lm_scale.GATE_PEAK:
                    failures.append(
                        f"{row['name']}: {s:.2f}x < "
                        f"{fig_lm_scale.GATE_PEAK:.2f}x peak reduction "
                        "vs vmapped")
            if (row["name"] == "lmscale/dense_wire_bf16"
                    and not row["derived"].endswith("_exact")):
                failures.append(
                    f"{row['name']}: bf16 wire not exactly half the f32 "
                    f"dense message ({row['derived']})")
        payload["lmscale_headline"] = headline
    with open(BENCH_JSON, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"\nwrote {os.path.normpath(BENCH_JSON)}")
    if failures:
        print("PERF REGRESSION: " + "; ".join(failures), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

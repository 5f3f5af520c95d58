"""One run of one cell: set-up, the measured window, the traced reading of
the layers, and the check of what the window's driver produced.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the data and weights from the seed, builds the program, and
runs its first call of `run_fed_chs`, which compiles (or loads from the
persistent cache) every program the window runs and is what the check
compares.  The window then runs the same call back to back until
`--seconds` have passed; it may not compile anything, and each of its calls
must log what set-up's call logged.  With `--trace 1`
the window runs under the profiler and the per-layer metrics are read from
the trace.  The last line of standard output is the result, as JSON.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
import time

from chipbench import catalog, correct, device, program, traffic

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the host spans every traced run keeps: the harness's and the driver call's
# own; a metric reader adds the ones it reads as `SPANS`
HOST_SPANS = ("precompute", "stage", "scan_chunk", "eval", "materialize",
              "bench_window", "bench_call", "call", "schedule", "model_init", "draw",
              "device_put", "loss_fetch")


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    trace: object          # chipbench.trace.Trace of the window
    devices: list          # ids of the devices the cell uses
    window_ps: tuple       # (start, end) of the window on the trace's clock
    window_s: float        # the window on the host clock
    rounds: int            # rounds completed in the window
    config: dict
    mix: dict
    peaks: dict            # the peak table's row of the device
    counts: dict = dataclasses.field(default_factory=dict)  # the window's counter deltas


class CompileCounter:
    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


def enable_cache() -> str:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR` when
    set, else `.jax_cache` at the root of the checkout; every program is
    kept, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(catalog.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def failed_rounds(result, eval_every: int) -> int:
    """Rounds whose logged loss is not finite: round 0, then each eval
    period of `eval_every` rounds, is judged by the loss logged at its end."""
    return sum((1 if i == 0 else eval_every)
               for i, loss in enumerate(result.train_loss) if not math.isfinite(loss))


def read_layers(cell, ctx: Context, bench_dir: str = catalog.BENCH_DIR) -> dict:
    out = {}
    for m in cell.per_layer:
        value = catalog.metric_reader(m["name"], bench_dir)(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def host_spans(cell, bench_dir: str = catalog.BENCH_DIR) -> tuple:
    """`HOST_SPANS` and every span a reader of the cell's metrics declares."""
    declared = (s for m in cell.per_layer
                for s in getattr(catalog.metric_module(m["name"], bench_dir), "SPANS", ()))
    return tuple(dict.fromkeys((*HOST_SPANS, *declared)))


def logged(result) -> tuple:
    """The losses and metrics one call of `run_fed_chs` logged."""
    return list(result.train_loss), list(result.test_acc)


@dataclasses.dataclass
class Setup:
    fed: traffic.Federation
    prog: program.Program | None
    w0: dict             # the initial weights, on the host
    states: dict         # what the check compares (`correct.program_states`)
    logged: tuple        # what the first call logged
    failed: int          # its rounds with a non-finite loss


def first_call(cell, seed: int, tracer) -> Setup:
    """Set-up of one seed: the data and weights, the program, and its first
    call of `run_fed_chs`, which compiles (or loads) every program the
    window runs."""
    import jax

    from repro.core import run_fed_chs

    fed = traffic.build(cell.mix, cell.config, seed)
    prog = program.build(cell.config, fed, seed, tracer)
    w0 = jax.device_get(prog.weights())
    prog.model.capture.on = True
    first = run_fed_chs(prog.task, prog.config)
    prog.model.capture.on = False
    states = correct.program_states(prog.model.capture, first, fed.eval_every)
    prog.model.capture.params.clear()
    return Setup(fed, prog, w0, states, logged(first), failed_rounds(first, fed.eval_every))


def run(args, t0: float) -> dict:
    cell = catalog.find_cell(args.workload)
    info, devs = device.require_chips(cell.chips)
    import jax

    from repro.core import run_fed_chs
    from repro.obs.trace import SpanTracer

    print(f"compile cache: {enable_cache()}", file=sys.stderr, flush=True)
    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    # a non-finite loss in set-up's call fails the check (its loss gap is not finite)
    setup = first_call(cell, args.seed, SpanTracer(profiler=bool(args.trace)))
    fed, prog, E = setup.fed, setup.prog, setup.fed.eval_every
    setup_s = time.perf_counter() - t0
    print(f"setup: {setup_s:.3f} s, {compiles.count} backend compiles "
          f"({compiles.seconds:.3f} s)", file=sys.stderr, flush=True)

    # the window
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiled_before, rounds, failed, calls = compiles.count, 0, 0, []
    counts_before = dict(prog.config.obs.counts)
    t_w = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_window"):
        while True:
            with jax.profiler.TraceAnnotation("bench_call"):
                res = run_fed_chs(prog.task, prog.config)
            rounds += fed.rounds
            failed += failed_rounds(res, E)
            calls.append(logged(res))
            del res
            if time.perf_counter() - t_w >= args.seconds:
                break
    window_s = time.perf_counter() - t_w
    if trace_dir:
        jax.profiler.stop_trace()
    window_compiles = compiles.count - compiled_before
    # what the window's calls added to the program's host counters
    counts = {k: v - counts_before.get(k, 0) for k, v in prog.config.obs.counts.items()}
    peak = device.memory_peak(devs)
    print(f"window: {len(calls)} calls, {rounds} rounds in {window_s:.3f} s, "
          f"{window_compiles} backend compiles", file=sys.stderr, flush=True)
    print(f"window counters: {json.dumps(counts, sort_keys=True)}", file=sys.stderr, flush=True)

    result = {"correct": False, "attempted": rounds, "failed": failed}
    if args.trace:
        from chipbench import trace as tr

        path = next(os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                    for f in fs if f.endswith(".xplane.pb"))
        t = tr.load(path, host_spans(cell))
        shutil.rmtree(trace_dir, ignore_errors=True)
        (lo, hi), = t.spans("bench_window")
        used = sorted(t.devices)[: cell.chips]
        ctx = Context(t, used, (lo, hi), window_s, rounds, cell.config, cell.mix,
                      device.peaks(info["kind"]), counts)
        busy = [tr.busy_ps(t.devices[d], lo, hi) for d in used]
        info = dict(info, memory_peak_bytes=peak,
                    busy_s=sum(busy) / len(busy) * 1e-12, window_s=(hi - lo) * 1e-12)
        ops0 = tr.clip(t.devices[used[0]], lo, hi)
        result["breakdown"] = {"device_ops": tr.top_ops(ops0),
                               "idle_gaps": tr.idle_gaps(ops0, lo, hi, t.host)}
        result["metrics"] = read_layers(cell, ctx)
        unattributed = sum(int(e - s) for s, e, p in zip(ops0.start, ops0.end, ops0.scope)
                           if not any(tr.in_scope(p, sc) for sc in
                                      ("local_train", "uplink", "intra_agg", "precision_cast",
                                       "master_accumulate")))
        print(f"trace: {len(ops0.start)} device ops; {unattributed * 1e-12:.6f} s of "
              f"{sum(busy) / len(busy) * 1e-12:.6f} s busy outside the round scopes",
              file=sys.stderr, flush=True)
        del t, ctx, ops0
    else:
        info = dict(info, memory_peak_bytes=peak)
        values = {"round_ms": window_s * 1e3 / rounds, "peak_hbm_gb": peak / 1e9,
                  "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in cell.end_to_end}
    result["device"] = info

    # the check, after the program's state is freed
    del prog
    setup.prog = None
    jax.clear_caches()
    gc.collect()
    t_ref, compiled_before = time.perf_counter(), compiles.count
    ref = correct.reference(cell.config, fed, setup.w0)
    values = correct.numbers(setup.w0, setup.states, ref, 0, E)
    values["window_compiles"] = window_compiles
    values["window_mismatch"] = correct.mismatches(setup.logged, calls)
    ok, checks = correct.judge(values, dict(cell.limits, window_compiles=0, window_mismatch=0))
    print(f"reference: {time.perf_counter() - t_ref:.3f} s, "
          f"{compiles.count - compiled_before} backend compiles", file=sys.stderr, flush=True)
    for name in values.keys() - checks.keys():
        print(f"reading {name} {values[name]!r}, no limit", file=sys.stderr, flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    result["correct"] = ok
    result["checks"] = checks
    return result


def main(argv, t0: float) -> int:
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json on this machine.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run(args, t0)
    print(json.dumps(result), flush=True)
    return 0

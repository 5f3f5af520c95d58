"""Record the small device trace the trace-reduction tests read.

    python3 benchmarks/chip/record_testdata.py --out benchmarks/chip/testdata/toy_qsgd.xplane.pb.gz

Runs on one TPU.  A toy Fed-CHS run goes through the harness's own program
path (`chipbench.program`, `run_fed_chs`) with a two-layer decoder at
d_model 256 (flash attention) and a packed QSGD uplink (s = 16), so the
trace holds every scope and kernel the per-layer metrics read: `local_train`,
`uplink`, `qsgd_encode`/`qsgd_decode`, `intra_agg`, the flash and QSGD
kernels, and the driver's `stage`, `scan_chunk` and `eval` spans inside the
harness's `bench_window` and `bench_call` spans.  One warm call compiles;
the second call is traced.  The trace is written gzipped.
"""
import argparse
import gzip
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]

TOY_CONFIG = dict(hidden_size=256, intermediate_size=512, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=64,
                  vocab_size=4096)
TOY_MIX = {
    "population": {"clusters": 2, "clients_per_cluster": 2},
    "data": {"kind": "tokens", "topics": 2, "dominance": 0.9, "branch": 4,
             "batch": 2, "seq": 256, "eval_batches": 2},
    "federation": {"local_steps": 2, "local_epochs": 1,
                   "lr": {"kind": "constant", "value": 0.3},
                   "channel": {"kind": "qsgd", "levels": 16}, "client_microbatch": 1,
                   "topology": "random_sparse", "eval_every": 2},
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--seed", type=int, default=12)
    args = ap.parse_args(argv)

    from chipbench import catalog, device, program, traffic

    device.require_chips(1)
    import jax

    from repro.core import run_fed_chs
    from repro.obs.trace import SpanTracer

    config = dict(catalog.load_json(os.path.join(HERE, "configs", "qwen3-0.6b.json")),
                  **TOY_CONFIG)
    fed = traffic.build(TOY_MIX, config, args.seed)
    prog = program.build(config, fed, args.seed, SpanTracer(profiler=True))
    run_fed_chs(prog.task, prog.config)  # compiles
    trace_dir = tempfile.mkdtemp(prefix="chipbench-testdata-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        with jax.profiler.TraceAnnotation("bench_call"):
            run_fed_chs(prog.task, prog.config)
    jax.profiler.stop_trace()
    path = next(os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
                for f in fs if f.endswith(".xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(path, "rb") as src, gzip.open(args.out, "wb", compresslevel=9) as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"wrote {args.out}: {os.path.getsize(args.out)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())

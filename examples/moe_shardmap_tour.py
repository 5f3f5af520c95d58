import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# must precede any jax import (the production mesh needs 512 host devices)

"""Tour of the MoE optimization stack (EXPERIMENTS.md §Perf pair 1).

Lowers deepseek-v3-671b x train_4k on the 256-chip production mesh twice:
  * paper-faithful baseline — global expert-choice routing, GSPMD infers all
    communication; the combine scatter-add resolves as operand-replicated +
    a full-activation all-reduce (~TB/device);
  * --opt configuration — group-limited routing + the `jax.shard_map`
    interior (models/moe_shardmap.py) whose only communication is a
    per-layer (n_loc, d) psum over `model`.
and prints the roofline terms + top collective sources of each.

Takes ~1 min (two AOT compiles of a 61-layer model).

  PYTHONPATH=src python examples/moe_shardmap_tour.py [--arch dbrx-132b]
"""
import argparse


def main():
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-v3-671b",
                    choices=["deepseek-v3-671b", "dbrx-132b"])
    args = ap.parse_args()

    from repro.configs.registry import get_config
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_lowering, lower_spec
    from repro.roofline.analysis import analyze_compiled, roofline_terms
    from repro.roofline.attribution import collective_breakdown

    cfg = get_config(args.arch)
    mesh = make_production_mesh()
    results = {}
    for name, optimized in (("baseline", False), ("+opt(shard_map)", True)):
        spec = build_lowering(cfg, "train_4k", mesh, optimized=optimized)
        compiled = lower_spec(spec, mesh).compile()
        rec = analyze_compiled(compiled)
        terms = roofline_terms(rec)
        results[name] = rec
        print(f"\n[{name}] bound={terms['bound']}  "
              f"compute={terms['compute_s']:.1f}s memory={terms['memory_s']:.1f}s "
              f"collective={terms['collective_s']:.1f}s")
        for row in collective_breakdown(compiled.as_text(), top=3):
            print(f"   {row['bytes']/1e9:8.1f} GB/dev  {row['op']:18s} "
                  f"{row['shape'][:40]:40s} <- ...{row['source'][-45:]}")

    ratio = (results["baseline"]["collective_bytes_per_device"]
             / max(results["+opt(shard_map)"]["collective_bytes_per_device"], 1))
    print(f"\nThe optimized interior moves {ratio:.1f}x fewer collective bytes per "
          "step (EXPERIMENTS.md §Perf, iterations 1-5).")


if __name__ == "__main__":
    main()

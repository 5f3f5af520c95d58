import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
# must precede any jax import (the production mesh needs 512 host devices)

"""Launcher API tour: lower one architecture onto the 2-pod production mesh
with the Fed-CHS pod-sequential variant AND the HFL baseline, and print the
collective-bytes difference — the paper's communication claim, visible in HLO.

  PYTHONPATH=src python examples/multipod_dryrun.py --arch qwen3-0.6b
"""
import argparse

from repro.configs.registry import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_lowering, lower_spec
from repro.roofline.analysis import analyze_compiled, roofline_terms


def main():
    from repro.utils import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=list(ARCH_IDS))
    args = ap.parse_args()

    cfg = get_config(args.arch)
    mesh = make_production_mesh(multi_pod=True)
    print(f"mesh: {dict(mesh.shape)} = {mesh.devices.size} chips")

    results = {}
    for variant in ("fedchs", "hfl"):
        spec = build_lowering(cfg, "train_4k", mesh, variant=variant)
        compiled = lower_spec(spec, mesh).compile()
        rec = analyze_compiled(compiled)
        terms = roofline_terms(rec)
        results[variant] = rec
        print(f"\n[{variant}] bound={terms['bound']}  "
              f"compute={terms['compute_s']:.3e}s memory={terms['memory_s']:.3e}s "
              f"collective={terms['collective_s']:.3e}s")
        for op, b in sorted(rec["collectives"].items()):
            print(f"   {op:20s} {b/1e9:10.3f} GB/device")

    saved = (results["hfl"]["collective_bytes_per_device"]
             - results["fedchs"]["collective_bytes_per_device"])
    print(f"\nFed-CHS saves {saved/1e9:.3f} GB/device of collective traffic per round "
          "vs star-aggregated HFL (the paper's §5.3 claim, in lowered XLA).")


if __name__ == "__main__":
    main()

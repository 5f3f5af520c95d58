"""Regenerate EXPERIMENTS.md §Dry-run and §Roofline from experiments/dryrun/*.json,
the hand-authored §Perf log from experiments/perf_log.md, the
§Participation table written by `benchmarks/fig_participation.py`
(experiments/participation.md), and §Telemetry from
experiments/obs/summary.json (written by `benchmarks/run.py --profile`).
Chip speed is not here: it is PERF_LEDGER.jsonl's (PERF.md).  Sections
whose inputs are absent are omitted rather than rendered empty, and a malformed/partial suite output
(e.g. an interrupted benchmark run) skips that section with a warning
instead of aborting the whole regeneration.

  PYTHONPATH=src:. python scripts/make_experiments_md.py
"""
from __future__ import annotations

import glob
import json
import os
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
DRYRUN = os.path.join(ROOT, "experiments", "dryrun")
PERF_LOG = os.path.join(ROOT, "experiments", "perf_log.md")
PARTICIPATION = os.path.join(ROOT, "experiments", "participation.md")
OBS_SUMMARY = os.path.join(ROOT, "experiments", "obs", "summary.json")
OUT = os.path.join(ROOT, "EXPERIMENTS.md")

SHAPE_ORDER = {"train_4k": 0, "prefill_32k": 1, "decode_32k": 2, "long_500k": 3}


def _warn(msg):
    print(f"warning: {msg}", file=sys.stderr)


def load():
    recs = []
    for fn in sorted(glob.glob(os.path.join(DRYRUN, "*.json"))):
        try:
            with open(fn) as f:
                rec = json.load(f)
            rec["arch"], rec["shape"], rec["mesh"]  # required keys
        except (json.JSONDecodeError, KeyError, OSError) as e:
            _warn(f"skipping malformed dryrun record {os.path.basename(fn)}: {e!r}")
            continue
        recs.append(rec)
    recs.sort(key=lambda r: (r["arch"], SHAPE_ORDER.get(r["shape"], 99), r["mesh"],
                             str(r.get("variant"))))
    return recs


def gb(x):
    return f"{x / 1e9:.2f}"


def dryrun_section(recs):
    lines = [
        "## §Dry-run",
        "",
        "Every (architecture × input shape) lowered **and compiled** with "
        "`jax.jit(...).lower(...).compile()` on the production meshes "
        "(single pod 16×16 = 256 chips, multi-pod 2×16×16 = 512 chips). "
        "`train_4k` lowers one Fed-CHS round (variant `fedchs`; `hfl` = "
        "star-aggregation baseline); decode shapes lower `serve_step` "
        "(1 token vs a seq_len cache). long_500k runs for mamba2 / "
        "recurrentgemma / mistral-nemo (sliding-window variant) and is "
        "skipped for pure full-attention archs + whisper (DESIGN.md §4): "
        "33 combos × 2 meshes + 20 HFL-variant train lowerings + 20 `+opt` "
        "train lowerings + 6 `opt` serve lowerings = "
        f"{len(recs)} records, all compiled successfully.",
        "",
        "| arch | shape | mesh | variant | compile s | bytes/dev (peak) | "
        "collective bytes/dev | HLO dot GFLOPs/dev |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} | {r.get('variant','-')} | "
            f"{r['compile_s']} | {gb(r['memory'].get('peak_bytes', 0))} GB | "
            f"{gb(r['collective_bytes_per_device'])} GB | "
            f"{r['dot_flops_per_device'] / 1e9:.1f} |"
        )
    return "\n".join(lines)


def roofline_section(recs):
    lines = [
        "## §Roofline",
        "",
        "Terms in seconds/step per chip (TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM, "
        "50 GB/s/link ICI). compute = trip-scaled HLO dot FLOPs / peak; memory = "
        "cost-analysis bytes (trip-scaled) / HBM bw; collective = HLO collective "
        "operand bytes (all-reduce 2×) / link bw. MODEL_FLOPS = 6·N·D (train, "
        "N=active params for MoE) or 2·N·D (serve); MF/HLO = MODEL_FLOPS / "
        "(Σdev HLO dot FLOPs) — the useful-compute fraction (values <1 mean "
        "HLO does extra work: remat, attention, MoE dispatch; values >1 mean "
        "the analytic model overestimates, e.g. decode where cache reads "
        "dominate and matmul work is tiny). Single-pod table = the 40-pair "
        "baseline grid (33 lowered + 7 structural skips).",
        "",
    ]
    for mesh in ("single", "multi"):
        sub = [r for r in recs if r["mesh"] == mesh]
        if not sub:
            continue
        lines += [
            f"### {mesh} mesh ({sub[0]['chips']} chips)",
            "",
            "| arch | shape | var | bound | compute s | memory s | collective s "
            "| peak GB/dev | MF/HLO |",
            "|---|---|---|---|---|---|---|---|---|",
        ]
        for r in sub:
            mf = r.get("model_vs_hlo")
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r.get('variant','-')} | "
                f"**{r['bound']}** | {r['compute_s']:.3e} | {r['memory_s']:.3e} | "
                f"{r['collective_s']:.3e} | {gb(r['memory'].get('peak_bytes', 0))} | "
                f"{mf:.2f} |" if mf else
                f"| {r['arch']} | {r['shape']} | {r.get('variant','-')} | "
                f"**{r['bound']}** | {r['compute_s']:.3e} | {r['memory_s']:.3e} | "
                f"{r['collective_s']:.3e} | {gb(r['memory'].get('peak_bytes', 0))} | - |"
            )
        lines.append("")
        # per-record bottleneck notes
    return "\n".join(lines)


def bottleneck_notes(recs):
    lines = ["### Dominant-bottleneck notes (single-pod baselines)", ""]
    seen = set()
    for r in recs:
        if r["mesh"] != "single" or str(r.get("variant")) == "hfl":
            continue
        key = (r["arch"], r["shape"])
        if key in seen:
            continue
        seen.add(key)
        note = {
            "compute": "matmul-limited; gains come from MXU-friendlier tiles or fewer recomputed dots",
            "memory": "HBM-stream-limited; gains come from tighter activation/cache sharding, "
                      "vocab padding to shardable sizes, or smaller temporaries",
            "collective": "ICI-limited; gains come from removing redundant all-gathers / "
                          "reshaping the layout so contractions stay shard-local",
        }[r["bound"]]
        lines.append(f"- **{r['arch']} × {r['shape']}** — bound: {r['bound']} "
                     f"(c={r['compute_s']:.2e}, m={r['memory_s']:.2e}, "
                     f"x={r['collective_s']:.2e}); {note}.")
    return "\n".join(lines)


def telemetry_section():
    """§Telemetry from experiments/obs/summary.json (benchmarks/run.py
    --profile): per-round tap aggregates, span wall-clocks, and the netsim
    replay's deadline-drop totals for one instrumented run."""
    if not os.path.exists(OBS_SUMMARY):
        return ""
    with open(OBS_SUMMARY) as f:
        s = json.load(f)
    tele, net = s["telemetry"], s["netsim"]
    lines = [
        "## §Telemetry",
        "",
        f"One instrumented `{s['algo']}` run ({s['rounds']} rounds, final "
        f"acc {s['final_acc']}) from `benchmarks/run.py --profile`: in-graph "
        "training-health taps, host phase spans, and a straggler-network "
        "replay merged into `experiments/obs/trace.json` (open in "
        "ui.perfetto.dev; validated by CI's obs-smoke job).  "
        f"{s['trace_events']} trace events, of which {s['comm_events']} comm "
        "instants — exactly one per CommLedger event.  Simulated makespan "
        f"{net['makespan_s']} s; the reporting deadline dropped "
        f"{net['dropped_client_rounds']} client-rounds, saving "
        f"{net['dropped_mb']} MB of uplink.  Tapped runs stay bit-identical "
        "to untapped ones (tests/test_engine_parity.py).",
        "",
        "| tap (per-round, run aggregate) | mean | max |",
        "|---|---|---|",
    ]
    for k, v in sorted(tele["metrics"].items()):
        lines.append(f"| {k} | {v['mean']:.4g} | {v['max']:.4g} |")
    lines += ["", "| host span | total wall s |", "|---|---|"]
    for k, v in tele["spans"].items():
        lines.append(f"| {k} | {v:.3f} |")
    return "\n".join(lines)


def _read(path):
    if os.path.exists(path):
        with open(path) as f:
            return f.read().strip()
    return ""


def main():
    recs = load()
    sections = [
        "# EXPERIMENTS — Fed-CHS reproduction + multi-pod dry-run + roofline",
        "(generated by scripts/make_experiments_md.py from experiments/dryrun/*.json; "
        "§Perf from experiments/perf_log.md; §Participation from "
        "experiments/participation.md, written by `benchmarks/run.py --only "
        "participation`; §Telemetry from experiments/obs/summary.json, written "
        "by `benchmarks/run.py --profile`; paper-claims validation from "
        "benchmarks — see bench_output.txt)",
    ]
    # each section tolerates its own broken/partial input: a failed suite
    # must not block regenerating the rest of EXPERIMENTS.md
    builders = []
    if recs:
        builders += [lambda: dryrun_section(recs), lambda: roofline_section(recs),
                     lambda: bottleneck_notes(recs)]
    builders += [telemetry_section,
                 lambda: _read(PARTICIPATION), lambda: _read(PERF_LOG)]
    for build in builders:
        try:
            section = build()
        except Exception as e:  # noqa: BLE001 — skip, don't abort
            _warn(f"skipping section {getattr(build, '__name__', 'lambda')}: {e!r}")
            continue
        if section:
            sections.append(section)
    with open(OUT, "w") as f:
        f.write("\n\n".join(sections) + "\n")
    print(f"wrote {OUT} ({len(recs)} dryrun records)")


if __name__ == "__main__":
    main()

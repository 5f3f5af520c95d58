"""Read the numbers `correct` compares, to set a cell's limits.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--out readings.jsonl]

Runs on the chips the cell asks for, at the cell's own size, in one process.
For each seed of `--seeds` the program's first call of `run_fed_chs` (the
one set-up checks) is compared with the plain reference: the lower readings.
For each seed of `--control-seeds` the control is: the program's first call
with its lower-precision path switched on (the configuration's `control`
policy), compared with the same reference.  For each seed of `--fault-seeds`
the fault "half of the batch left out, the mean taken over the rest" is:
the reference on half of every batch.  A step that returns its state
unchanged reads 1 on `update_gap` by construction and is not run.  Each
reading is one JSON line on standard output (and in `--out`), with the
verdict of the cell's own limits (`correct`), the losses logged for rounds
0 and E beside the reference's, and how many leaves the stand-in left
unmoved.  The benchmark's own runs never run this.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s.strip()]


def row(cell, kind: str, seed: int, t0: float, w0, states: dict, ref: dict,
        own_w0=None) -> dict:
    """One reading: the compared numbers, the verdict of the cell's limits,
    and what shows whether the stand-in trains (`own_w0`: the weights it
    started from, where they are stored in another dtype than `w0`)."""
    from chipbench import correct

    E = max(ref)
    values = correct.numbers(w0, states, ref, 0, E)
    ok, _ = correct.judge(values, cell.limits)
    own = w0 if own_w0 is None else own_w0
    unmoved = int(np.sum(correct.leaf_norms(states[0]["params"], own) == 0))
    return dict(workload=cell.name, kind=kind, seed=seed, seconds=time.perf_counter() - t0,
                correct=ok, **values, unmoved_leaves=unmoved,
                loss=[states[t]["loss"] for t in (0, E)],
                ref_loss=[ref[t]["loss"] for t in (0, E)])


def readings(cell, seed: int, kinds) -> list:
    """The rows of one seed for each of `kinds` ("program", "control",
    "half_batch"); the reference is run once and shared."""
    from chipbench import cli, correct
    from repro.obs.trace import SpanTracer

    t0 = time.perf_counter()
    setup = cli.first_call(cell, seed, SpanTracer())
    setup.prog = None
    ref = correct.reference(cell.config, setup.fed, setup.w0)
    rows = []
    if "program" in kinds:
        rows.append(row(cell, "program", seed, t0, setup.w0, setup.states, ref))
    if "control" in kinds:
        t0 = time.perf_counter()
        control = dataclasses.replace(cell, config=correct.control_config(cell.config))
        run = cli.first_call(control, seed, SpanTracer())
        run.prog = None
        rows.append(row(cell, "control", seed, t0, setup.w0, run.states, ref, run.w0))
        del run
    if "half_batch" in kinds:
        t0 = time.perf_counter()
        half = correct.reference(cell.config, setup.fed, setup.w0,
                                 batch_view=correct.half_batch)
        rows.append(row(cell, "half_batch", seed, t0, setup.w0, half, ref))
        del half
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from chipbench import catalog, cli, device

    cell = catalog.find_cell(args.workload)
    device.require_chips(cell.chips)
    cli.enable_cache()
    kinds = {"program": _seeds(args.seeds), "control": _seeds(args.control_seeds),
             "half_batch": _seeds(args.fault_seeds)}
    out = open(args.out, "a") if args.out else None
    for seed in dict.fromkeys(s for seeds in kinds.values() for s in seeds):
        for r in readings(cell, seed, [k for k, seeds in kinds.items() if seed in seeds]):
            line = json.dumps(r)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

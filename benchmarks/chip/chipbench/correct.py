"""How `correct` is decided for a training cell.

Set-up drives the program from the seed through its first call of the
driver, the same object and compiled rounds the window then runs, and
keeps host copies of the global weights the driver evaluates after round 0
and after round E (the first two eval rounds), with the losses and metric it
logged there.  After the window, once the program's state is freed, the
plain reference (`refs/fedchs.py` over the configuration's model in
`refs/`) trains the same rounds from the same weights on the same batches.

The numbers compared, each with its limit from `limits/<cell>.json`:
  loss_gap    the relative gap of the loss logged for round 0 (the loss of a
              later round swings with the trajectory);
  update_gap  the worst leaf's gap between the norms of the round-0 update
              (program against reference), over the larger of the
              reference's norm of that leaf and the median leaf's;
  change_gap  the same for the change of the weights after round E;
  metric_gap  the relative gap of the eval metric after round E.
Leaves whose reference update is under a thousandth of the median leaf's
(nought to rounding) are left out of the two norm gaps.  Every call of the
window repeats set-up's call on the same inputs, so the losses and metrics
it logs must equal set-up's bit for bit (`window_mismatch`, limit 0).

The control is the program itself with its lower-precision path switched
on: the configuration's `control` precision policy in place of its own.
"""
from __future__ import annotations

import numpy as np

import jax

from chipbench import catalog

KEEP = 1e-3   # leaves moved less than this share of the median leaf are left out


def leaf_norms(a, b) -> np.ndarray:
    """Per-leaf L2 norms of a - b, in float64."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return np.array([np.linalg.norm(np.asarray(x, np.float64) - np.asarray(y, np.float64))
                     for x, y in zip(la, lb)])


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref)[keep] / scale[keep]))


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def numbers(w0, prog: dict, ref: dict, first: int, second: int) -> dict:
    """The compared numbers; `prog`/`ref` map round -> {params, loss, metric}."""
    ref_up = leaf_norms(ref[first]["params"], w0)
    keep = ref_up >= KEEP * np.median(ref_up)
    return {
        "loss_gap": rel(prog[first]["loss"], ref[first]["loss"]),
        "update_gap": norm_gap(leaf_norms(prog[first]["params"], w0), ref_up, keep),
        "change_gap": norm_gap(leaf_norms(prog[second]["params"], w0),
                               leaf_norms(ref[second]["params"], w0), keep),
        "metric_gap": rel(prog[second]["metric"], ref[second]["metric"]),
    }


def reference(config: dict, fed, w0, *, batch_view=None) -> dict:
    """Run the plain reference over the first call's first two eval rounds."""
    model = catalog.reference(config["reference"]).make(config)
    p, E = config["precision"], fed.eval_every
    return catalog.reference("fedchs").run(
        model, w0, fed, E + 1, store=p["compute"], wire=p["wire"], record=(0, E),
        batch_view=batch_view)


def control_config(config: dict) -> dict:
    """The configuration with the program's lower-precision path switched on."""
    return dict(config, precision=config["control"])


def mismatches(logged, calls) -> int:
    """Calls whose logged (losses, metrics) differ from set-up's `logged`."""
    return sum(call != logged for call in calls)


def half_batch(batch: dict) -> dict:
    """The fault "half of the batch left out, the mean taken over the rest":
    the first half of every row's tokens (a batch of the cell is one row)."""
    return {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}


def program_states(capture, result, E: int) -> dict:
    """round -> {params, loss, metric} of the program's first call."""
    return {t: {"params": capture.params[i], "loss": result.train_loss[i],
                "metric": result.test_acc[i]}
            for i, t in enumerate((0, E))}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number that has a limit, beside it; a number without one (it
    separates no control or fault from the program) is only read."""
    checks = {k: {"value": float(values[k]), "limit": float(limits[k])} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks

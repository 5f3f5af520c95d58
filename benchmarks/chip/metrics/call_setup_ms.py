"""call_setup_ms: device milliseconds per round of a driver call's own
work apart from its rounds and its evals: the ops of every program other
than the round program (`jit_chunk`) that start in the window but outside
the driver's `eval` spans (the model's inits, the schedule's PRNG splits,
the loss fetch), averaged over the cell's devices.  With `eval_ms` and the
round program's device time it partitions the device's busy time."""
from chipbench import trace as tr


def read(ctx):
    lo, hi = ctx.window_ps
    evals = [(s, e) for s, e in ctx.trace.spans("eval") if lo <= s < hi]
    if not evals:
        return None
    per_device = [tr.during(ctx.trace.devices[d], [(lo, hi)], "jit_chunk")
                  - tr.during(ctx.trace.devices[d], evals, "jit_chunk") for d in ctx.devices]
    return sum(per_device) / len(per_device) * 1e-9 / ctx.rounds

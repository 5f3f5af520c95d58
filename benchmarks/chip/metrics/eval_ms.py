"""eval_ms: device milliseconds per round of the eval programs: the ops
that run inside the driver's `eval` spans, apart from the round program
(`jit_chunk`) they may wait on, averaged over the cell's devices."""
from chipbench import trace as tr


def read(ctx):
    lo, hi = ctx.window_ps
    spans = [(s, e) for s, e in ctx.trace.spans("eval") if lo <= s < hi]
    if not spans:
        return None
    per_device = [tr.during(ctx.trace.devices[d], spans, "jit_chunk") for d in ctx.devices]
    return sum(per_device) / len(per_device) * 1e-9 / ctx.rounds

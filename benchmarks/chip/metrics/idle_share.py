"""idle_share: the share of the window in which no op ran on the device:
1 - (union of the device's op intervals / window), averaged over the
cell's devices."""
from chipbench import trace as tr


def read(ctx):
    lo, hi = ctx.window_ps
    busy = [tr.busy_ps(ctx.trace.devices[d], lo, hi) for d in ctx.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))

"""stage_ms: host milliseconds per round the driver spends staging chunks
(gathering each chunk's batches and handing them to the device), summed
over its `stage` spans in the window."""


def read(ctx):
    lo, hi = ctx.window_ps
    spans = [(s, e) for s, e in ctx.trace.spans("stage") if lo <= s < hi]
    if not spans:
        return None
    return sum(e - s for s, e in spans) * 1e-9 / ctx.rounds

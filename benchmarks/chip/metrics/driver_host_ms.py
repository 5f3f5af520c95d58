"""driver_host_ms: host milliseconds per round the driver spends on its own
work: each `bench_call` span less the driver's `eval` spans inside it (an
eval waits on the device).  That is the driver's `precompute`, `stage`,
`scan_chunk`, `loss_fetch` and `materialize` spans and the call's own time
around them."""


def read(ctx):
    lo, hi = ctx.window_ps
    calls = [(s, e) for s, e in ctx.trace.spans("bench_call") if lo <= s < hi]
    if not calls:
        return None
    evals = [(s, e) for s, e in ctx.trace.spans("eval")
             if any(cs <= s < ce for cs, ce in calls)]
    host = sum(e - s for s, e in calls) - sum(e - s for s, e in evals)
    return host * 1e-9 / ctx.rounds

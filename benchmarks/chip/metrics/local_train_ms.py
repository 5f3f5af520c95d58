"""local_train_ms: device milliseconds per round under the engine's
`local_train` scope (the clients' forward, backward and SGD steps),
averaged over the cell's devices."""
from chipbench import trace as tr


def read(ctx):
    lo, hi = ctx.window_ps
    ps = [tr.scope_ps(tr.clip(ctx.trace.devices[d], lo, hi), "local_train")
          for d in ctx.devices]
    if not any(ps):
        return None
    return sum(ps) / len(ps) * 1e-9 / ctx.rounds

"""train_mfu: model operations of the trained work (forward and backward,
no recomputation, counted from the configuration's shapes) per second of
the window, over the chips' bf16 peak."""
from chipbench import counts


def read(ctx):
    flops = counts.train_flops_per_round(ctx.config, ctx.mix) * ctx.rounds
    return 100.0 * flops / ctx.window_s / (ctx.peaks["bf16_flops_per_s"] * len(ctx.devices))

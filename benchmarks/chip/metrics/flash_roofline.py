"""flash_roofline: the share of its compute roofline the flash attention
kernel reaches.  Each kernel event's least time is the causal forward's
operations, 2 * 2 * (B*H) * T * S * hd / 2 from the shapes in the event,
over the chip's bf16 peak; a windowed kernel (`flash_attention_w<w>`)
needs 2 * 2 * (B*H) * T * hd * (w - w^2 / (2S)) for w < S.  The share is
the sum of least times over the sum of the events' device times."""
import re

from chipbench import counts
from chipbench import trace as tr

SHAPE = re.compile(r"\w+\[(\d+),(\d+),(\d+)\]")


def read(ctx):
    lo, hi = ctx.window_ps
    need, took = 0.0, 0
    for d in ctx.devices:
        for dur, text in tr.kernel_events(tr.clip(ctx.trace.devices[d], lo, hi),
                                          "flash_attention"):
            out, _q, k = (tuple(map(int, m)) for m in SHAPE.findall(text)[:3])
            need += counts.flash_forward_flops(out[0], out[1], k[1], out[2],
                                               tr.kernel_window(text))
            took += dur
    if not took:
        return None
    return 100.0 * need / ctx.peaks["bf16_flops_per_s"] / (took * 1e-12)

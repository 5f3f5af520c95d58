"""flash_bwd_roofline: the share of its compute roofline the flash attention
backward reaches.  One layer's backward is a `flash_bwd_dq` and a
`flash_bwd_dkv` kernel; its least time is twice the causal forward's
operations, 2 * (2 * 2 * (B*H) * T * S * hd / 2), with B*H, T and hd from
the dq event's first shape (dq) and S from its third (k), over the chip's
bf16 peak; a windowed backward (`flash_bwd_dq_w<w>`) counts the windowed
forward's operations (`counts.flash_forward_flops`).  The recomputed
scores are not counted.  The share is the sum of least times over the sum
of both kernels' device times; None where the window holds no such
kernel."""
import re

from chipbench import counts
from chipbench import trace as tr

SHAPE = re.compile(r"\w+\[(\d+),(\d+),(\d+)\]")


def read(ctx):
    lo, hi = ctx.window_ps
    need, took = 0.0, 0
    for d in ctx.devices:
        for dur, text in tr.kernel_events(tr.clip(ctx.trace.devices[d], lo, hi), "flash_bwd"):
            if text.lstrip("%").startswith("flash_bwd_dq"):
                dq, _q, k = (tuple(map(int, m)) for m in SHAPE.findall(text)[:3])
                need += 2 * counts.flash_forward_flops(dq[0], dq[1], k[1], dq[2],
                                                       tr.kernel_window(text))
            took += dur
    if not took:
        return None
    return 100.0 * need / ctx.peaks["bf16_flops_per_s"] / (took * 1e-12)

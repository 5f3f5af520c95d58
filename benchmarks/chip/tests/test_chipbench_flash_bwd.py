"""The `flash_bwd_roofline` reader on a hand-made ops table: the operations
it counts from the event shapes, None without backward kernels, and the
forward's `flash_roofline` left to the forward kernel alone."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import catalog, cli, counts, device  # noqa: E402
from chipbench import trace as tr  # noqa: E402

BF16 = "{2,1,0:T(8,128)(2,1)S(1)}"
FWD = (f"%flash_attention.3 = (bf16[16,2048,128]{BF16}, f32[16,1,1,2048]{{3,2,1,0:T(1,128)}}) "
       f"custom-call(bf16[16,2048,128]{BF16} %a, bf16[8,2048,128]{BF16} %b, "
       f"bf16[8,2048,128]{BF16} %c), custom_call_target=\"tpu_custom_call\"")
DQ = (f"%flash_bwd_dq.1 = bf16[16,2048,128]{BF16} custom-call(bf16[16,2048,128]{BF16} %q, "
      f"bf16[8,2048,128]{BF16} %k, bf16[8,2048,128]{BF16} %v, bf16[16,2048,128]{BF16} %do, "
      f"f32[16,1,2048]{{2,1,0}} %lse, f32[16,1,2048]{{2,1,0}} %delta), "
      f"custom_call_target=\"tpu_custom_call\"")
DKV = (f"%flash_bwd_dkv.1 = (bf16[8,2048,128]{BF16}, bf16[8,2048,128]{BF16}) "
       f"custom-call(bf16[8,2,2048,128]{{3,2,1,0}} %q, bf16[8,2,2048,128]{{3,2,1,0}} %do, "
       f"f32[8,2,2048]{{2,1,0}} %lse, f32[8,2,2048]{{2,1,0}} %delta, bf16[8,2048,128]{BF16} %k, "
       f"bf16[8,2048,128]{BF16} %v), custom_call_target=\"tpu_custom_call\"")
PEAKS = device.peaks("TPU v5 lite")


def _ctx(events):
    """A one-device context over (start ps, end ps, text) custom calls and
    one ordinary fusion, in a window of 10 ms."""
    rows = sorted([(s, e, t.split(" ")[0].lstrip("%"), t, "custom-call") for s, e, t in events]
                  + [(0, 10, "fusion.1", "%fusion.1 = f32[8]", "loop fusion")])
    ops = tr.Ops(np.array([r[0] for r in rows], np.int64), np.array([r[1] for r in rows], np.int64),
                 [r[2] for r in rows], [r[3] for r in rows], [""] * len(rows),
                 [r[4] for r in rows], ["jit_chunk"] * len(rows))
    trace = tr.Trace({0: ops}, [])
    return cli.Context(trace, [0], (0, 10**10), 0.01, 1, {}, {}, PEAKS)


def _read(name, events):
    return catalog.metric_reader(name)(_ctx(events))


def test_least_time_is_twice_the_causal_forward_from_the_dq_shapes():
    # one layer's backward: dq 0.5 ms, dkv 0.7 ms
    value = _read("flash_bwd_roofline", [(100, 100 + 5 * 10**8, DQ),
                                         (10**9, 10**9 + 7 * 10**8, DKV)])
    need = 2 * counts.flash_forward_flops(16, 2048, 2048, 128)
    assert need == 2 * 2 * 2 * 16 * 2048 * 2048 * 128 / 2
    assert value == pytest.approx(100 * need / PEAKS["bf16_flops_per_s"] / 1.2e-3)


def test_every_layer_backward_counts_once():
    one = _read("flash_bwd_roofline", [(0, 5 * 10**8, DQ), (10**9, 2 * 10**9, DKV)])
    two = _read("flash_bwd_roofline", [(0, 5 * 10**8, DQ), (10**9, 2 * 10**9, DKV),
                                       (3 * 10**9, 3 * 10**9 + 5 * 10**8, DQ),
                                       (4 * 10**9, 5 * 10**9, DKV)])
    assert two == pytest.approx(one)


def test_none_without_backward_kernels():
    assert _read("flash_bwd_roofline", []) is None
    assert _read("flash_bwd_roofline", [(0, 10**9, FWD)]) is None


def test_the_forward_share_leaves_the_backward_kernels_out():
    assert _read("flash_roofline", [(0, 10**9, DQ), (10**9, 2 * 10**9, DKV)]) is None
    alone = _read("flash_roofline", [(0, 10**9, FWD)])
    mixed = _read("flash_roofline", [(0, 10**9, FWD), (2 * 10**9, 3 * 10**9, DQ),
                                     (4 * 10**9, 5 * 10**9, DKV)])
    assert mixed == pytest.approx(alone)
    # the forward's logsumexp output is not read as q: its work is (out, q, k)'s
    need = counts.flash_forward_flops(16, 2048, 2048, 128)
    assert alone == pytest.approx(100 * need / PEAKS["bf16_flops_per_s"] / 1e-3)

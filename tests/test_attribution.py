"""roofline.attribution on a hand-written post-optimization HLO module:
trip scaling through while bodies, the 2x all-reduce factor and the
skip-list; roofline.analysis's dtype-split dot FLOPs."""

from repro.roofline.attribution import collective_breakdown, top_output_bytes

# 8*4*4 = 128 B all-reduce inside a 48-trip while; 16*4 = 64 B permute outside
HLO = """\
HloModule test

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%body (p: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %p = (s32[], f32[8,4]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,4]{1,0} get-tuple-element(%p), index=1
  %ar = f32[8,4]{1,0} all-reduce(%x), to_apply=%add, metadata={op_name="jit(f)/psum"}
  %big = f32[64,64]{1,0} multiply(%ar, %ar)
  ROOT %t = (s32[], f32[8,4]) tuple(%i, %ar)
}

%cond (p: (s32[], f32[8,4])) -> pred[] {
  %p = (s32[], f32[8,4]) parameter(0)
  ROOT %lt = pred[] constant(true)
}

ENTRY %main (arg: f32[8,4]) -> f32[8,4] {
  %arg = f32[8,4]{1,0} parameter(0)
  %init = (s32[], f32[8,4]) tuple(%arg)
  %w = (s32[], f32[8,4]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"48"}}
  %cp = f32[16]{0} collective-permute(%arg), source_target_pairs={{0,1}}, metadata={op_name="jit(f)/ppermute"}
  ROOT %out = f32[8,4]{1,0} get-tuple-element(%w), index=1
}
"""


def test_collective_breakdown_trip_and_factor():
    rows = collective_breakdown(HLO)
    by_op = {r["op"]: r for r in rows}
    # all-reduce: 128 B * 2 (reduce+broadcast) * 48 trips
    assert by_op["all-reduce"]["bytes"] == 128 * 2 * 48
    assert "psum" in by_op["all-reduce"]["source"]
    # collective-permute: 64 B, once, factor 1
    assert by_op["collective-permute"]["bytes"] == 64
    # sorted descending
    assert rows[0]["op"] == "all-reduce"


def test_top_output_bytes_scaling_and_skips():
    rows = top_output_bytes(HLO)
    names = [r["name"] for r in rows]
    # bookkeeping excluded
    assert all(r["op"] not in ("parameter", "tuple", "get-tuple-element")
               for r in rows)
    # the in-loop 16 KiB multiply dominates (x48)
    assert rows[0]["name"] == "big"
    assert rows[0]["bytes"] == 64 * 64 * 4 * 48
    # the all-reduce output inside the loop is also trip-scaled
    ar = next(r for r in rows if r["name"] == "ar")
    assert ar["bytes"] == 128 * 48


def test_compute_seconds_prices_f32_dots_at_half_rate():
    from repro.roofline.analysis import HW, arithmetic_intensity, compute_seconds

    hw = HW(peak_flops=200e12, peak_flops_f32=100e12)
    rec = {"dot_flops_per_device": 3e12,
           "dot_flops_by_dtype": {"bf16": 2e12, "f32": 1e12},
           "scaled_bytes_per_device": 1.5e12}
    assert compute_seconds(rec, hw=hw) == 2e12 / 200e12 + 1e12 / 100e12
    assert arithmetic_intensity(rec) == 2.0
    # records without the breakdown (older artifacts) use the flat bf16 rate
    flat = {"dot_flops_per_device": 3e12}
    assert compute_seconds(flat, hw=hw) == 3e12 / 200e12


def test_analyze_hlo_dtype_breakdown():
    """dot flops are split by output dtype so mixed-precision graphs can be
    priced per MXU rate; a bf16 matmul lands under a low-precision key."""
    import jax
    import jax.numpy as jnp

    from repro.roofline.analysis import analyze_hlo_text

    def f(a, b):
        return (a @ b).astype(jnp.float32)

    a = jnp.zeros((64, 128), jnp.bfloat16)
    b = jnp.zeros((128, 32), jnp.bfloat16)
    rec = analyze_hlo_text(jax.jit(f).lower(a, b).compile().as_text())
    want = 2.0 * 64 * 32 * 128
    assert rec["dot_flops_per_device"] == want
    assert sum(rec["dot_flops_by_dtype"].values()) == want

"""Per-instruction HLO attribution — the 'profiler' of the dry-run workflow.

`benchmarks/roofline.py` reports the three aggregate terms; when a term
dominates, these helpers answer *which ops* are responsible (EXPERIMENTS.md
§Perf iterations were driven by them):

  * collective_breakdown — trip-scaled bytes per (collective op, shape,
    source op_name), e.g. "the MoE combine all-reduces f32[65536,7168]
    61 times from .../shard_map/psum".
  * top_output_bytes — trip-scaled output bytes per instruction, skipping
    bookkeeping ops; a proxy for which tensors stream through HBM.

All parse `compiled.as_text()` (post-optimization, post-SPMD HLO) so shapes
are per-device.
"""
from __future__ import annotations

import re
from collections import defaultdict

from repro.roofline.analysis import (
    _COLLECTIVES,
    _TRIP_RE,
    _instr_callees,
    _shape_bytes,
    parse_hlo,
)

_OPNAME_RE = re.compile(r'op_name="([^"]+)"')

# ops whose 'output' is bookkeeping, not data movement
_SKIP = {
    "parameter", "tuple", "get-tuple-element", "bitcast", "constant",
    "while", "conditional", "call",
}


def _trip_counts(comps) -> dict[str, float]:
    trip_of: dict[str, float] = {}
    for instrs in comps.values():
        for ins in instrs:
            if ins["op"] == "while":
                t = _TRIP_RE.search(ins["line"])
                for callee in _instr_callees(ins):
                    trip_of[callee] = float(t.group(1)) if t else 1.0
    return trip_of


def _walk(comps, entry, visit_instr):
    """DFS over the call graph, multiplying while-body trip counts."""
    trip_of = _trip_counts(comps)
    stack: list[str] = []

    def visit(cname: str, mult: float):
        if cname not in comps or cname in stack:
            return
        stack.append(cname)
        for ins in comps[cname]:
            visit_instr(ins, mult)
            for callee in _instr_callees(ins):
                m2 = mult * trip_of.get(callee, 1.0) if ins["op"] == "while" else mult
                visit(callee, m2)
        stack.pop()

    if entry:
        visit(entry, 1.0)


def collective_breakdown(hlo_text: str, *, top: int = 20) -> list[dict]:
    """Trip-scaled collective bytes grouped by (op, shape, source op_name)."""
    parsed = parse_hlo(hlo_text)
    agg: dict[tuple, float] = defaultdict(float)

    def on_instr(ins, mult):
        op = ins["op"]
        if not op.startswith(_COLLECTIVES):
            return
        m = _OPNAME_RE.search(ins["line"])
        tag = m.group(1)[-80:] if m else "?"
        key = (op.split(".")[0], ins["shape"][:64], tag)
        factor = 2.0 if key[0] == "all-reduce" else 1.0
        agg[key] += mult * _shape_bytes(ins["shape"]) * factor

    _walk(parsed["comps"], parsed["entry"], on_instr)
    rows = [
        {"op": op, "shape": shape, "source": tag, "bytes": b}
        for (op, shape, tag), b in sorted(agg.items(), key=lambda kv: -kv[1])
    ]
    return rows[:top]


def top_output_bytes(hlo_text: str, *, top: int = 25) -> list[dict]:
    """Largest instructions by trip-scaled output bytes (HBM-traffic proxy).

    Caveats: dynamic-update-slice is counted at full-buffer size although the
    hardware writes only the slice; fusion-internal tensors never reach HBM.
    Use for *ranking* suspects, not absolute bytes.
    """
    parsed = parse_hlo(hlo_text)
    rows: list[tuple[float, dict]] = []

    def on_instr(ins, mult):
        if ins["op"] in _SKIP:
            return
        b = mult * _shape_bytes(ins["shape"])
        rows.append((b, {"op": ins["op"], "name": ins["name"],
                         "shape": ins["shape"][:64], "bytes": b}))

    _walk(parsed["comps"], parsed["entry"], on_instr)
    rows.sort(key=lambda r: -r[0])
    return [r for _, r in rows[:top]]

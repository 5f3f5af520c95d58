"""Reduce a profiler trace to device time per scope and kernel, idle
intervals, and collective exposure.

A TPU trace holds, per device plane (`/device:TPU:<n>`), an "XLA Ops" line
of the operations the TensorCore ran and an "XLA Modules" line of the
programs they belong to.  Each op's metadata carries `tf_op`, the JAX
name-scope path of the HLO instruction (for a fusion, that of its root
instruction, so a fusion spanning two scopes is billed to its root's), and
`hlo_category`.  The host plane holds the `jax.profiler.TraceAnnotation`
spans of the driver and of the harness on the same clock.
"""
from __future__ import annotations

import dataclasses
import re

import numpy as np

from chipbench.xspace import read_xspace, stat_value

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# ops whose event spans the ops of their body: left out, the body is counted
CONTAINERS = ("while", "conditional", "call")
# a windowed kernel's instruction name: `<kernel>_w<window>`, with XLA's clone suffix
WINDOWED = re.compile(r"_w(\d+)(?:\.\d+)?$")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")


@dataclasses.dataclass
class Ops:
    """The ops one device ran, as parallel arrays sorted by start (ps)."""

    start: np.ndarray
    end: np.ndarray
    name: list          # HLO instruction name ("fusion.12", "flash_attention.3")
    text: list          # the instruction as the trace names it
    scope: list         # tf_op: the name-scope path
    category: list      # hlo_category
    module: list        # the program ("jit_chunk", ...)


@dataclasses.dataclass
class Trace:
    devices: dict       # device id -> Ops
    host: list          # (name, start_ps, end_ps) annotations of the host threads

    def spans(self, name: str) -> list:
        return [(s, e) for n, s, e in self.host if n == name]


def _stats(meta, names) -> dict:
    return {names.get(st.metadata_id, ""): stat_value(st, names) for st in meta.stats}


def _module_of(starts, mod_starts, mod_ends, mod_names):
    i = np.searchsorted(mod_starts, starts, side="right") - 1
    out = []
    for k, s in zip(i, starts):
        out.append(mod_names[k] if k >= 0 and s < mod_ends[k] else "")
    return out


def load(path: str, host_names=None) -> Trace:
    """Read an `.xplane.pb`; keep the host annotations named in `host_names`
    (all host events when None)."""
    space = read_xspace(path)
    devices, host = {}, []
    for plane in space.planes:
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        meta = plane.event_metadata
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = lines.get("XLA Modules")
            mod_rows = sorted(
                (mods.timestamp_ns * 1000 + e.offset_ps,
                 mods.timestamp_ns * 1000 + e.offset_ps + e.duration_ps,
                 meta[e.metadata_id].name.split("(")[0])
                for e in (mods.events if mods is not None else ()))
            ops = lines["XLA Ops"]
            base = ops.timestamp_ns * 1000
            rows = sorted((base + e.offset_ps, e.duration_ps, e.metadata_id)
                          for e in ops.events)
            info = {}
            for mid in {r[2] for r in rows}:
                em = meta[mid]
                st = _stats(em, names)
                info[mid] = (em.display_name or em.name.split(" ")[0].lstrip("%"), em.name,
                             str(st.get("tf_op", "")), str(st.get("hlo_category", "")))
            rows = [r for r in rows if info[r[2]][3] not in CONTAINERS]
            start = np.array([r[0] for r in rows], np.int64)
            end = start + np.array([r[1] for r in rows], np.int64)
            module = _module_of(start, np.array([r[0] for r in mod_rows], np.int64),
                                np.array([r[1] for r in mod_rows], np.int64),
                                [r[2] for r in mod_rows])
            devices[int(m.group(1))] = Ops(
                start, end, [info[r[2]][0] for r in rows], [info[r[2]][1] for r in rows],
                [info[r[2]][2] for r in rows], [info[r[2]][3] for r in rows], module)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                base = line.timestamp_ns * 1000
                for e in line.events:
                    name = meta[e.metadata_id].name
                    if host_names is None or name in host_names:
                        host.append((name, base + e.offset_ps, base + e.offset_ps + e.duration_ps))
    host.sort(key=lambda r: r[1])
    return Trace(devices, host)


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------


def clip(ops: Ops, lo: int, hi: int) -> Ops:
    """The ops that start inside [lo, hi)."""
    i, j = np.searchsorted(ops.start, [lo, hi])
    return Ops(ops.start[i:j], ops.end[i:j], ops.name[i:j], ops.text[i:j],
               ops.scope[i:j], ops.category[i:j], ops.module[i:j])


def union(intervals) -> list:
    """Merge (start, end) intervals; returns the disjoint union, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ps(ops: Ops, lo: int, hi: int) -> int:
    """Picoseconds of [lo, hi) in which some op ran."""
    return sum(min(e, hi) - max(s, lo) for s, e in
               union(zip(ops.start.tolist(), ops.end.tolist())) if e > lo and s < hi)


def in_scope(scope_path: str, scope: str) -> bool:
    """Whether a `tf_op` path lies under the name scope `scope`."""
    return scope in scope_path.replace(":", "/").split("/")


def scope_ps(ops: Ops, scope: str) -> int:
    return int(sum(int(e - s) for s, e, p in zip(ops.start, ops.end, ops.scope)
                   if in_scope(p, scope)))


def kernel_events(ops: Ops, prefix: str) -> list:
    """(duration ps, instruction text) of the custom calls named `prefix*`."""
    return [(int(e - s), t) for s, e, n, t, c in
            zip(ops.start, ops.end, ops.name, ops.text, ops.category)
            if n.startswith(prefix) and c == "custom-call"]


def kernel_window(text: str) -> int | None:
    """The attention window a kernel's name states (`flash_attention_w2048.3`
    -> 2048); None for a kernel named without one."""
    m = WINDOWED.search(text.split(" ", 1)[0])
    return int(m.group(1)) if m else None


def during(ops: Ops, spans: list, exclude_module: str = "") -> int:
    """Device picoseconds of ops that start inside any of `spans`, leaving
    out the ops of programs whose name starts with `exclude_module`."""
    total = 0
    for lo, hi in spans:
        part = clip(ops, lo, hi)
        total += sum(int(e - s) for s, e, m in zip(part.start, part.end, part.module)
                     if not (exclude_module and m.startswith(exclude_module)))
    return total


def collective_exposed_ps(ops: Ops) -> int:
    """Collective time during which no compute op runs on the same device."""
    coll = [(s, e) for s, e, c in zip(ops.start.tolist(), ops.end.tolist(), ops.category)
            if c in COLLECTIVES]
    compute = union((s, e) for s, e, c in zip(ops.start.tolist(), ops.end.tolist(),
                                              ops.category) if c not in COLLECTIVES)
    exposed = 0
    for s, e in union(coll):
        covered = sum(max(0, min(e, ce) - max(s, cs)) for cs, ce in compute
                      if ce > s and cs < e)
        exposed += (e - s) - covered
    return exposed


def top_ops(ops: Ops, n: int = 10) -> list:
    """[name, seconds] of the ops that took most device time, clones merged."""
    acc: dict = {}
    for s, e, name in zip(ops.start, ops.end, ops.name):
        key = re.sub(r"\.\d+$", "", name)
        acc[key] = acc.get(key, 0) + int(e - s)
    return [[k, v * 1e-12] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops: Ops, lo: int, hi: int, host: list, n: int = 10) -> list:
    """[label, seconds] of the longest idle gaps in [lo, hi): each labelled
    with the innermost host span open at the gap's middle."""
    busy = [(max(s, lo), min(e, hi)) for s, e in union(zip(ops.start.tolist(),
                                                           ops.end.tolist()))
            if e > lo and s < hi]
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) // 2
        open_spans = [(hs, name) for name, hs, he in host if hs <= mid < he]
        label = max(open_spans)[1] if open_spans else "host:none"
        out.append([label, (e - s) * 1e-12])
    return out

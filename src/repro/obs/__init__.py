"""Unified run observability: in-graph telemetry taps, host span tracing,
and the merged Chrome-trace/Perfetto exporter.

Usage — pass a `RunTelemetry` to any driver config::

    from repro.obs import RunTelemetry
    obs = RunTelemetry()                      # taps + spans
    res = run_fed_chs(task, replace(cfg, obs=obs))
    res.telemetry is obs                      # attached to the RunResult

`obs=None` (the default everywhere) is the fast path: the compiled graphs,
scan bodies, and driver hot loops are byte-for-byte the current code — the
taps exist only as separately-cached jit variants (see core/engine.py).

Telemetry crosses to the host only at scan-chunk boundaries (the same
places losses already cross), so `transfer_guard("disallow")` holds on
the hot loop and scanned==looped parity is preserved.  By default the
crossing is LAZY: `record_stacked` stashes the stacked device arrays and
materializes them on first read, so the scanned driver keeps its
async-dispatch pipelining (the host stages chunk k+1 while the device is
still executing chunk k); `sync_chunks=True` restores the eager blocking
transfer so host spans measure real device execution per chunk.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import numpy as np

from repro.obs.export import (
    build_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
    write_metrics_jsonl,
)
from repro.obs.trace import SpanTracer, maybe_span

__all__ = [
    "RunTelemetry",
    "SpanTracer",
    "maybe_span",
    "build_chrome_trace",
    "validate_chrome_trace",
    "write_chrome_trace",
    "write_metrics_jsonl",
]


@dataclass
class RunTelemetry:
    """Carrier for one run's observability state.

    taps        — compute in-graph training-health metrics (update_norm,
                  drift, comp_err, mass) per round; False keeps spans only.
    profiler    — also wrap spans in jax.profiler.TraceAnnotation.
    sync_chunks — block on each chunk's tele transfer inside
                  `record_stacked`, so the enclosing scan_chunk span covers
                  the chunk's real device execution (accurate `--profile`
                  timelines).  False (default) defers materialization to
                  first read, keeping the scanned driver's async-dispatch
                  pipelining (the taps' cost on the chip is not measured).
    counts      — host-side counters the drivers bump at the same
                  boundaries as their spans (`count`): `staged_bytes`
                  (bytes of the staged scan inputs handed to the device)
                  and `trained_rounds`.
    """

    taps: bool = True
    profiler: bool = False
    sync_chunks: bool = False
    tracer: SpanTracer = None  # type: ignore[assignment]
    _rounds: list[int] = field(default_factory=list, repr=False)
    _metrics: dict[str, list[Any]] = field(default_factory=dict, repr=False)
    _pending: list = field(default_factory=list, repr=False)
    counts: dict[str, int] = field(default_factory=dict)

    def __post_init__(self):
        if self.tracer is None:
            self.tracer = SpanTracer(profiler=self.profiler)

    def span(self, name: str):
        return self.tracer.span(name)

    def count(self, name: str, n: int = 1) -> None:
        """Add `n` to the host counter `name`."""
        self.counts[name] = self.counts.get(name, 0) + int(n)

    # -- tele ingestion ----------------------------------------------------
    @property
    def rounds(self) -> list[int]:
        """Round indices with recorded taps (flushes pending chunks)."""
        self._flush()
        return self._rounds

    @property
    def metrics(self) -> dict[str, list[Any]]:
        """Per-tap value lists aligned with `rounds` (flushes pending)."""
        self._flush()
        return self._metrics

    def _append(self, t: int, tele: dict) -> None:
        self._rounds.append(int(t))
        for k, v in tele.items():
            a = np.asarray(v)
            self._metrics.setdefault(k, []).append(
                float(a) if a.ndim == 0 else a.astype(np.float64))

    def _flush(self) -> None:
        while self._pending:
            rounds, tele = self._pending.pop(0)
            host = {k: np.asarray(v) for k, v in tele.items()}
            for i, t in enumerate(rounds):
                self._append(int(t), {k: v[i] for k, v in host.items()})

    def record_round(self, t: int, tele: dict) -> None:
        """One round's tele dict (looped drivers; device scalars fine)."""
        self._flush()
        self._append(t, tele)

    def record_stacked(self, rounds, tele: dict) -> None:
        """A chunk of stacked tele (scanned drivers): leaves have a leading
        round axis aligned with `rounds`.  Default: stash the device arrays
        and materialize lazily on first read, so the driver's dispatch loop
        never blocks here.  With `sync_chunks` the np.asarray happens
        inline — it blocks on the device, so the enclosing scan_chunk span
        covers the chunk's real execution time."""
        self._pending.append((list(rounds), dict(tele)))
        if self.sync_chunks:
            self._flush()

    # -- views -------------------------------------------------------------
    def metrics_rows(self) -> list[dict]:
        """One flat dict per recorded round (JSONL-ready)."""
        rows = []
        for i, t in enumerate(self.rounds):
            row: dict[str, Any] = {"round": t}
            for k, vs in self.metrics.items():
                v = vs[i]
                row[k] = v.tolist() if isinstance(v, np.ndarray) else v
            rows.append(row)
        return rows

    def summary(self) -> dict:
        """Per-metric mean/max over the run (scalarizing vector taps)."""
        out: dict[str, dict[str, float]] = {}
        for k, vs in self.metrics.items():
            flat = np.concatenate([np.atleast_1d(np.asarray(v, np.float64))
                                   for v in vs]) if vs else np.zeros(0)
            if flat.size:
                out[k] = {"mean": float(flat.mean()), "max": float(flat.max())}
        return {"rounds": len(self.rounds), "metrics": out,
                "spans": {name: self.tracer.wall(name)
                          for _, name, _ in self.tracer.events
                          if name},
                "counts": dict(self.counts)}

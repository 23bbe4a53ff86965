"""Host-side profiling spans + Perfetto trace capture (DESIGN.md §11).

``span("name")`` wraps a host-side region in both a wall-clock timer
(``time.perf_counter``) and a ``jax.profiler.TraceAnnotation``, so the same
name shows up (a) in the in-process span ledger this module keeps, (b) in
the JSONL event log when a sink is installed, and (c) on the Perfetto
timeline when a ``profile(...)`` capture is active.  The launch layer wraps
the train loop's compile, input, dispatch and loss read-back
(``train/*``), the sweep CLI wraps lower/compile/run, and the mesh
benchmarks wrap ring steps — one vocabulary everywhere.

The record list is capped (the oldest half goes when it is full); per-name
counts and totals are kept beside it, exact for any run length, and are
what ``total`` and ``summarize_spans()`` read.

These are HOST spans: they never appear inside a traced function.  For
in-trace annotation (visible in XLA op names / the profiler's device
timeline, metadata-only and DCE-safe) use ``jax.named_scope`` directly —
``core/dist.py`` and the kernels do.

``profile(log_dir)`` wraps ``jax.profiler.trace`` and returns the
``.trace.json.gz`` artifacts it produced (Perfetto/Chrome ``chrome://
tracing`` compatible) via ``perfetto_artifacts``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import pathlib
import time
from typing import Dict, List, Optional

import jax

_MAX_SPANS = 4096


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    name: str
    t0: float        # perf_counter at entry (monotonic; deltas only)
    dur_s: float
    depth: int       # nesting level at entry


_SPANS: List[SpanRecord] = []
_TOTALS: Dict[str, List[float]] = {}   # name -> [count, total_s, max_s]
_DEPTH = [0]
_SINK = [None]      # optional EventLog; list cell so tests can swap it


def install_sink(log) -> None:
    """Mirror every closed span into ``log`` (an ``events.EventLog``)."""
    _SINK[0] = log


def uninstall_sink() -> None:
    _SINK[0] = None


def reset() -> None:
    _SPANS.clear()
    _TOTALS.clear()


def records() -> List[SpanRecord]:
    return list(_SPANS)


def total(name: str) -> float:
    """Summed duration of every closed span called ``name`` (seconds)."""
    return _TOTALS.get(name, [0, 0.0, 0.0])[1]


@contextlib.contextmanager
def span(name: str, **attrs):
    """Time a host-side region; mirrors into the profiler timeline + sink."""
    t0 = time.perf_counter()
    _DEPTH[0] += 1
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        _DEPTH[0] -= 1
        dur = time.perf_counter() - t0
        if len(_SPANS) >= _MAX_SPANS:      # bounded: drop oldest
            del _SPANS[: _MAX_SPANS // 2]
        _SPANS.append(SpanRecord(name, t0, dur, _DEPTH[0]))
        _add(_TOTALS, name, dur)
        if _SINK[0] is not None:
            _SINK[0].emit("span", name=name, dur_s=dur, depth=_DEPTH[0],
                          **attrs)


@contextlib.contextmanager
def profile(log_dir: str):
    """Capture a Perfetto/Chrome trace of the wrapped region into
    ``log_dir`` (``jax.profiler.trace``); yields the directory path."""
    with jax.profiler.trace(str(log_dir)):
        yield log_dir


def perfetto_artifacts(log_dir: str) -> List[str]:
    """The ``.trace.json.gz`` files a ``profile`` capture wrote (possibly
    several across nested date directories), newest first."""
    root = pathlib.Path(log_dir)
    if not root.is_dir():
        return []
    hits = sorted(root.rglob("*.trace.json.gz"),
                  key=lambda p: p.stat().st_mtime, reverse=True)
    return [str(p) for p in hits]


def _add(totals: Dict[str, List[float]], name: str, dur: float) -> None:
    agg = totals.setdefault(name, [0, 0.0, 0.0])
    agg[0] += 1
    agg[1] += dur
    agg[2] = max(agg[2], dur)


def summarize_spans(recs: Optional[List[SpanRecord]] = None) -> List[dict]:
    """Aggregate by name: count, total, mean, max (sorted by total desc).
    Without ``recs``: every span closed since ``reset``, from the exact
    per-name totals."""
    totals = _TOTALS
    if recs is not None:
        totals = {}
        for r in recs:
            _add(totals, r.name, r.dur_s)
    out = [{"name": n, "count": int(c), "total_s": t, "mean_s": t / c,
            "max_s": m} for n, (c, t, m) in totals.items()]
    return sorted(out, key=lambda a: -a["total_s"])

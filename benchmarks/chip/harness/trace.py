"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

The TPU trace gives, per device plane ``/device:TPU:<i>``, a line
``XLA Ops`` whose events are HLO instructions (``"%fusion.12 = ..."``) with
their device start and duration, nested: a ``while`` event spans the ops of
its body.  Scopes (``jax.named_scope``) are not on the events; they are in
the compiled HLO's ``metadata={op_name=".../scope/..."}``, so ops are
matched to scopes through the instruction name.  The host plane
``/host:CPU`` carries the harness's own spans (``TraceAnnotation`` names
starting with ``bench/``) on the same clock.

All times here are in seconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"

_INST = re.compile(r"^\s*%?([\w.\-]+)\s*=")
_META = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=.*?metadata=\{[^}]*?"
                   r"op_name=\"([^\"]*)\"")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> its ``op_name`` metadata, over a compiled HLO."""
    out = {}
    for line in hlo_text.splitlines():
        m = _META.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def inst_name(event_name: str) -> str:
    m = _INST.match(event_name)
    return m.group(1) if m else event_name.strip()[:80]


@dataclasses.dataclass
class Op:
    start: float
    end: float
    name: str       # instruction name, e.g. "fusion.12"
    scope: str      # op_name metadata ("" when unknown)
    self_s: float = 0.0


@dataclasses.dataclass
class Span:
    start: float
    end: float
    name: str


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]       # the harness's traced window
    devices: List[List[Op]]           # per device, ops inside the window
    spans: List[Span]                 # harness spans (bench/...)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(intervals: Iterable[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merge(intervals))


def _self_times(ops: List[Op]) -> None:
    """Self time of nested events: an event's duration less the time its
    directly nested events cover (a ``while`` holds its body's ops)."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    child: Dict[int, List[Tuple[float, float]]] = {}
    for i, o in enumerate(ops):
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            child.setdefault(id(stack[-1]), []).append((o.start, o.end))
        stack.append(o)
    for o in ops:
        o.self_s = (o.end - o.start) - covered(child.get(id(o), []))


def reduce_profile(pd, scopes: Dict[str, str],
                   window: Optional[Tuple[float, float]] = None) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``.

    ``scopes``: instruction name -> op_name (``op_names`` of the compiled
    programs the window ran).  ``window``: (start, end) in seconds on the
    trace clock; by default the ``bench/window`` span."""
    spans: List[Span] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.start_ns * 1e-9, e.end_ns * 1e-9,
                                          e.name))
    if window is None:
        wins = [s for s in spans if s.name == WINDOW_SPAN]
        if not wins:
            raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
        window = (wins[0].start, wins[0].end)
    w0, w1 = window
    for plane in sorted(pd.planes, key=lambda p: p.name):
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                a, b = e.start_ns * 1e-9, e.end_ns * 1e-9
                if b <= w0 or a >= w1:
                    continue
                n = inst_name(e.name)
                ops.append(Op(max(a, w0), min(b, w1), n, scopes.get(n, "")))
        _self_times(ops)
        devices.append(ops)
    spans = [s for s in spans if s.end > w0 and s.start < w1]
    return Reduced(window=(w0, w1), devices=devices, spans=spans)


def load(trace_dir: str):
    from jax.profiler import ProfileData
    hits = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(hits, key=os.path.getmtime))


# ---------------------------------------------------------------------------
# readings over a reduced trace
# ---------------------------------------------------------------------------

def busy_s(ops: Sequence[Op]) -> float:
    """Seconds in which some op ran on this device."""
    return covered((o.start, o.end) for o in ops)


def mean_busy_s(red: Reduced) -> float:
    if not red.devices:
        return 0.0
    return sum(busy_s(d) for d in red.devices) / len(red.devices)


def scope_s(ops: Sequence[Op], scope: str) -> float:
    """Device seconds covered by ops whose op_name holds ``/<scope>/`` (or
    ends in ``/<scope>``)."""
    return covered((o.start, o.end) for o in ops if in_scope(o.scope, scope))


def in_scope(op_name: str, scope: str) -> bool:
    parts = op_name.split("/")
    return scope in parts


def mean_scope_s(red: Reduced, scope: str) -> float:
    if not red.devices:
        return 0.0
    return sum(scope_s(d, scope) for d in red.devices) / len(red.devices)


def count_ops(ops: Sequence[Op], scope: str, name_prefix: str) -> int:
    return sum(1 for o in ops
               if in_scope(o.scope, scope) and o.name.startswith(name_prefix))


def _label(op: Op) -> str:
    tail = "/".join(p for p in op.scope.split("/")[-3:] if p)
    return f"{op.name} {tail}".strip()[:120]


def top_ops(red: Reduced, k: int = 10) -> List[List]:
    """The ``k`` device ops with the most self time, averaged over chips."""
    tot: Dict[str, float] = {}
    for ops in red.devices:
        for o in ops:
            key = _label(o)
            tot[key] = tot.get(key, 0.0) + o.self_s
    n = max(len(red.devices), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, s / n] for name, s in best]


def idle_gaps(red: Reduced, k: int = 10) -> List[List]:
    """The ``k`` longest idle stretches of device 0 inside the window, each
    labelled by the innermost harness span open on the host at its middle
    (``host`` when none is)."""
    if not red.devices:
        return []
    w0, w1 = red.window
    busy = merge((o.start, o.end) for o in red.devices[0])
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    inner = [s for s in red.spans if s.name != WINDOW_SPAN]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = 0.5 * (a + b)
        open_ = [s for s in inner if s.start <= mid < s.end]
        label = (min(open_, key=lambda s: s.end - s.start).name
                 if open_ else "host")
        out.append([label, b - a])
    return out

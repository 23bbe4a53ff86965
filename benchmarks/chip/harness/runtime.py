"""What every driver shares: harness spans, the compile counter, seeds,
the profiler capture and the device summary.

Harness spans time the calls the benchmark makes into each layer with the
host clock and, in a traced run, also mark them on the profiler's host
timeline (``jax.profiler.TraceAnnotation``), so idle stretches of the
device can be labelled by what the host was doing.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List

import numpy as np

class Spans:
    """Host-clock totals and counts of named harness spans; with
    ``annotate`` each span is also a ``TraceAnnotation`` on the profiler's
    host timeline."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.totals[name] = self.totals.get(name, 0.0) + (
            time.perf_counter() - t0)
        self.counts[name] = self.counts.get(name, 0) + 1

    def reset(self) -> None:
        self.totals.clear()
        self.counts.clear()


class CompileCounter:
    """Counts lowerings and backend compiles while it is active: a program
    that is new to this process shows up here whether or not the persistent
    cache then serves it."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.count = 0
        self.active = False

    def _listen(self, event, duration, **kw):
        if self.active and event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self.active = True
        return self

    def __exit__(self, *exc):
        import jax
        self.active = False
        jax.monitoring.unregister_event_duration_listener(self._listen)


def sub_seeds(seed: int, n: int) -> List[int]:
    """``n`` independent 31-bit seeds drawn from ``seed`` (any size)."""
    st = np.random.SeedSequence(int(seed)).generate_state(n, dtype=np.uint32)
    return [int(x) & 0x3FFFFFFF for x in st]


def device_summary() -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


@contextlib.contextmanager
def profiled(trace_dir: str, on: bool):
    """A profiler capture of the wrapped block when ``on``."""
    if not on:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(trace_dir, profiler_options=opts):
        yield

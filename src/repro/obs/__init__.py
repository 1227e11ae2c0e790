"""repro.obs — the serving observability subsystem.

One ``Observability`` object bundles the four pieces the engine threads
together (``docs/observability.md``):

  * ``metrics``  — a ``MetricsRegistry`` of counters/gauges/histograms/
                   per-tick series (host-side, dependency-free).
  * ``recorder`` — a bounded ``FlightRecorder`` of lifecycle events and
                   completed request ``Trace`` objects.
  * ``clock``    — the monotonic ``Clock`` seam every timestamp reads
                   through (injectable; ``FakeClock`` for tests).
  * ``lane_accumulator()`` — factory for per-session on-device counter
                   accumulation that adds zero host syncs.

Beside the bundle, ``span`` opens a named host span on the JAX
profiler's clock. The engine opens these at its layer boundaries
whatever ``obs`` is, since they record only while a profiler session
runs; they name the host's share of a device trace.

The cardinal rule: constructing or enabling observability must never
change a traced program or add a device sync to the serving path.
``SpeCaEngine(obs=False)`` records no metric, event or trace and keeps
no per-tick clock stamps; its only observability code is the profiler
spans (pinned bitwise against ``obs=True`` in ``tests/test_obs.py``),
and ``obs=True`` only ever (a) runs host-side Python, (b) dispatches the
async accumulator update.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

from .clock import Clock, FakeClock, MonotonicClock, resolve_clock
from .exporters import chrome_trace, prometheus_text, to_jsonl
from .lane_metrics import DEFAULT_ERR_EDGES, LaneAccumulator
from .registry import (Counter, Gauge, Histogram, MetricsRegistry, Series)
from .trace import (SCOPE_NAMES, SPAN_NAMES, FlightRecorder, Span, Timings,
                    Trace, build_trace, span)

__all__ = [
    "Clock", "MonotonicClock", "FakeClock", "resolve_clock",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "Series",
    "Timings", "Span", "Trace", "FlightRecorder", "build_trace",
    "span", "SPAN_NAMES", "SCOPE_NAMES",
    "LaneAccumulator", "DEFAULT_ERR_EDGES",
    "to_jsonl", "prometheus_text", "chrome_trace",
    "Observability",
]


class Observability:
    """The bundle ``SpeCaEngine(obs=...)`` owns (see module docstring).

    ``event_capacity``/``trace_capacity`` bound the flight recorder;
    ``err_edges`` sets the device-binned chain-err histogram grid.
    A caller may pass a pre-built ``Observability`` to share one
    registry across several engines (the sweep benchmark does not —
    it wants per-run isolation).
    """

    def __init__(self, *, clock: Optional[Clock] = None,
                 event_capacity: int = 4096, trace_capacity: int = 256,
                 err_edges: Tuple[float, ...] = DEFAULT_ERR_EDGES) -> None:
        self.clock: Clock = resolve_clock(clock)
        self.metrics = MetricsRegistry()
        self.recorder = FlightRecorder(capacity=event_capacity,
                                       trace_capacity=trace_capacity)
        self.err_edges = tuple(float(e) for e in err_edges)

    def lane_accumulator(self) -> LaneAccumulator:
        return LaneAccumulator(err_edges=self.err_edges)

    # -- convenience export surface -------------------------------------
    def snapshot(self) -> Any:
        return self.metrics.snapshot()

    def prometheus(self) -> str:
        return prometheus_text(self.metrics.snapshot())

    def events_jsonl(self, fp: Any = None) -> str:
        return to_jsonl(self.recorder.events(), fp)

    def chrome_trace(self, fp: Any = None) -> Any:
        return chrome_trace(self.recorder.traces(), fp)

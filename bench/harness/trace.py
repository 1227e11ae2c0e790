"""From a profiler trace to device busy time, kernel time and gaps.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into a flat
list of events ``[plane, line, name, start_ns, duration_ns]``: the
operations on each TPU device's ``XLA Ops`` line (named by their HLO
instruction, e.g. ``%taylor_predict_lanes.1 = bf16[...] custom-call(...)``;
a Pallas kernel keeps its function's name), the host spans the
benchmark opens itself (``bench.``) and the serving engine's Python
frames the profiler's Python tracer records (``$engine.py:335 _fill``,
``$workload.py:414 fill_payload``). ``reduce`` works on that list alone,
so a small recorded list checks it without a chip
(``bench/tests/data/trace_small.json``).
"""
from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
# Python frames of the serving layers that name an idle gap
HOST_FRAMES = ("$engine.py", "$workload.py", "$lane_step.py")


def load(trace_dir: str) -> List[list]:
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    out = []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not dev and not ev.name.startswith(
                        (HOST_PREFIX,) + HOST_FRAMES):
                    continue
                out.append([plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns)])
    return out


def read(path: str) -> List[list]:
    with open(path) as f:
        return json.load(f)["events"]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclass
class Reduced:
    window_s: float
    ticks: int
    devices: List[str]
    busy_s: float                       # mean over devices
    op_seconds: Dict[str, float]        # device 0, self time by op
    kernel_seconds: Dict[str, float] = field(default_factory=dict)
    kernel_calls: Dict[str, int] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10):
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]


def op_name(event_name: str) -> str:
    """``%fusion.391 = (bf16[...]) fusion(...)`` -> ``fusion.391``."""
    return event_name.split(" = ")[0].lstrip("%")


def _clip(events: List[list], w0: int, w1: int) -> List[list]:
    """The events cut to the window [w0, w1); those outside dropped."""
    out = []
    for e in events:
        a, b = max(e[3], w0), min(e[3] + e[4], w1)
        if b > a:
            out.append(e[:3] + [a, b - a])
    return out


def _self_times(events: List[list]) -> Dict[str, float]:
    """Seconds by op name, less the time of the ops nested inside
    (a conditional or loop holds the ops of its body)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []        # [end, name, child time, duration]
    for e in sorted(events, key=lambda e: (e[3], -e[4])):
        a, b = e[3], e[3] + e[4]
        while stack and stack[-1][0] <= a:
            end, name, child, dur = stack.pop()
            out[name] += (dur - child) * 1e-9
        if stack:
            stack[-1][2] += min(b, stack[-1][0]) - a
        stack.append([b, op_name(e[2]), 0, e[4]])
    for end, name, child, dur in stack:
        out[name] += (dur - child) * 1e-9
    return dict(out)


def _name_gap(a: int, b: int, spans: List[list]) -> str:
    """The most specific host span covering at least half of [a, b):
    the shortest such span, else the one covering most."""
    best, cover, short = None, 0, None
    for e in spans:
        c = min(b, e[3] + e[4]) - max(a, e[3])
        if c <= 0:
            continue
        if 2 * c >= b - a and (short is None or e[4] < short[4]):
            short = e
        if c > cover:
            best, cover = e, c
    pick = short or best
    return pick[2] if pick else "none"


def reduce(events: Sequence[list], kernels: Dict[str, str],
           tick_span: str = "bench.tick") -> Reduced:
    """Busy time, kernel time and idle gaps inside the traced window.

    The window runs from the start of the first ``tick_span`` host span
    to the end of the last. Busy time is the union of the operation
    intervals on each device, clipped to the window, averaged over the
    devices. ``kernels`` maps a short name to a substring of the
    operation names of that kernel; its seconds and calls are counted
    on the first device. Each idle gap of the first device is named by
    the most specific host span that covers it."""
    ticks = [(e[3], e[3] + e[4]) for e in events if e[2] == tick_span]
    if not ticks:
        raise ValueError(f"no {tick_span!r} spans in the trace")
    w0, w1 = min(a for a, _ in ticks), max(b for _, b in ticks)
    by_dev: Dict[str, List[list]] = defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e[0]):
            by_dev[e[0]].append(e)
    devices = sorted(by_dev, key=lambda p: int(DEVICE_PLANE.match(p)[1]))
    busy = []
    for dev in devices:
        iv = [(max(e[3], w0), min(e[3] + e[4], w1)) for e in by_dev[dev]]
        busy.append(sum(b - a for a, b in _union([x for x in iv
                                                  if x[1] > x[0]])))
    first = by_dev[devices[0]] if devices else []
    inside = [e for e in first if w0 <= e[3] < w1]
    k_s = {k: 0.0 for k in kernels}
    k_n = {k: 0 for k in kernels}
    for e in inside:
        name = op_name(e[2])
        for k, pat in kernels.items():
            if name.startswith(pat):
                k_s[k] += e[4] * 1e-9
                k_n[k] += 1
    gaps = []
    cur = w0
    for a, b in _union([(e[3], e[3] + e[4]) for e in first]) + [(w1, w1)]:
        a, b = max(a, w0), min(b, w1)
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    spans = [e for e in events if not DEVICE_PLANE.match(e[0])]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    named = [(_name_gap(a, b, spans), (b - a) * 1e-9) for a, b in longest]
    return Reduced(window_s=(w1 - w0) * 1e-9, ticks=len(ticks),
                   devices=devices,
                   busy_s=(sum(busy) / len(busy) * 1e-9) if busy else 0.0,
                   op_seconds=_self_times(_clip(first, w0, w1)),
                   kernel_seconds=k_s,
                   kernel_calls=k_n, idle_gaps=named)

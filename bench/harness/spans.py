"""The serving engine's own spans and the lane step's scopes in a
profiler trace.

The engine opens host spans named ``speca.*`` at its layer boundaries
(``repro.obs.SPAN_NAMES``: tick, admit, dispatch, harvest, release and
the blocking reads ``speca.sync.flags|emit|prefill``), and the lane step
runs its phases under named scopes (``speca.draft`` with ``speca.verify``
inside it, ``speca.full`` with ``speca.update`` inside it,
``speca.rollback``), which each device operation keeps in its name
stack. Both go into the profiler's own trace, so they share the device's
clock.

A TPU trace names each operation by its HLO instruction and carries no
name stack, and instruction names repeat from one program to the next.
So ``load`` places each operation in the program that ran it (the
``XLA Modules`` event it lies in, on the same device), and takes its
scope path from that program's compiled HLO text, which
``lane_step_scopes`` compiles again from the engine's live state (a hit
in the persistent compilation cache). It returns rows ``[plane, line,
name, start_ns, duration_ns, stack]``: the operations of each TPU
device's ``XLA Ops`` line with their scope path ("" outside the lane
step, or where the instruction has none), and the host spans named
``speca.`` or ``bench.`` with an empty stack. ``reduce`` works on those
rows alone, so a small recorded list checks it without a chip
(``bench/tests/data/trace_spans.json``).

The readers take a ``Context`` that carries the reduction as ``spans``
and return None where the trace holds no such span or scope, as a trace
of a program without them does.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from bench.harness import trace as TR

SPAN_PREFIX = "speca."
SYNC_PREFIX = "speca.sync."
# the lane step's phases, each named by its outermost scope; an
# operation under none of them is the step's other work
PHASES = ("speca.draft", "speca.full", "speca.rollback")
MODULES_LINE = "XLA Modules"
_COMPUTATION = re.compile(r"^(ENTRY )?%[\w.\-]+ .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def phase_of(stack: str) -> Optional[str]:
    """The outermost lane-step phase scope in a name stack, or None."""
    for part in stack.split("/"):
        if part in PHASES:
            return part
    return None


def program_scopes(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """The module name and ``{instruction: scope path}`` of a compiled
    program's HLO text. The path is the instruction's ``op_name``. An
    instruction the compiler added (a copy, a tuple) has none; inside a
    branch or loop body whose instructions lie in one phase and no other
    it takes that phase's path, since it runs only when the phase
    does."""
    lines = hlo_text.splitlines()
    module = lines[0].split()[1].rstrip(",") if lines else ""
    out: Dict[str, str] = {}
    body: List[str] = []
    entry = False

    def close():
        paths = set()
        for st in (out[i] for i in body):
            parts = st.split("/")
            ph = next((k for k, p in enumerate(parts) if p in PHASES), None)
            if ph is not None:
                paths.add("/".join(parts[:ph + 1]))
        if not entry and len(paths) == 1:
            fill = paths.pop()
            for i in body:
                out[i] = out[i] or fill

    for line in lines[1:]:
        if _COMPUTATION.match(line):
            close()
            body, entry = [], line.startswith("ENTRY")
            continue
        m = _INSTRUCTION.match(line)
        if m:
            op = _OP_NAME.search(line)
            out[m.group(1)] = op.group(1) if op else ""
            body.append(m.group(1))
    close()
    return module, out


def lane_step_scopes(system) -> Dict[str, Dict[str, str]]:
    """``program_scopes`` of each lane-step program the system's engine
    runs, compiled again for its live state, by module name."""
    out = {}
    for sess in system.engine._sessions.values():
        if sess.state is None:
            continue
        step = sess.step_fn            # partial(jitted, params)
        module, scopes = program_scopes(
            step.func.lower(*step.args, sess.state).compile().as_text())
        out[module] = scopes
    return out


def _in_modules(ops, modules):
    """The module name (without its id) each op event starts inside."""
    modules = sorted(modules, key=lambda e: e.start_ns)
    starts = [m.start_ns for m in modules]
    for ev in ops:
        k = bisect.bisect_right(starts, ev.start_ns) - 1
        m = modules[k] if k >= 0 else None
        yield (m.name.split("(")[0]
               if m and ev.start_ns < m.start_ns + m.duration_ns else "")


def load(trace_dir: str,
         scopes: Optional[Dict[str, Dict[str, str]]] = None) -> List[list]:
    """The rows of the trace under ``trace_dir``; ``scopes`` (from
    ``lane_step_scopes``) gives the device operations their paths."""
    import jax
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[0])
    scopes = scopes or {}
    out = []
    for plane in pd.planes:
        if TR.DEVICE_PLANE.match(plane.name):
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            ops = lines.get(TR.OPS_LINE, [])
            for ev, mod in zip(ops, _in_modules(
                    ops, lines.get(MODULES_LINE, []))):
                out.append([plane.name, TR.OPS_LINE, ev.name,
                            int(ev.start_ns), int(ev.duration_ns),
                            scopes.get(mod, {}).get(TR.op_name(ev.name),
                                                    "")])
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith((SPAN_PREFIX, TR.HOST_PREFIX)):
                    out.append([plane.name, line.name, ev.name,
                                int(ev.start_ns), int(ev.duration_ns), ""])
    return out


@dataclass
class Spans:
    ticks: int                      # tick spans in the window
    window_s: float
    host_s: Dict[str, List[float]]  # speca.* span seconds by name
    phase_s: Dict[str, float]       # device-0 self seconds by phase scope
    scoped_ops: int                 # device-0 operations under a phase
    idle_s: float                   # device-0 idle seconds in the window
    # idle seconds by the narrowest span open at the time: a speca.*
    # span, the tick span, or "" between ticks
    idle_by_span: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def span_sum(self, prefix: str) -> float:
        return sum(sum(v) for k, v in self.host_s.items()
                   if k.startswith(prefix))

    @property
    def idle_in_span_s(self) -> float:
        return sum(v for k, v in self.idle_by_span.items()
                   if k.startswith(SPAN_PREFIX))


def _idle_by_span(gaps: List[Tuple[int, int]],
                  spans: List[list]) -> Dict[str, float]:
    """Seconds of ``gaps`` by the narrowest span open at the time ("" for
    none). The spans nest, as one thread's spans do, so the narrowest
    open span is the one opened last."""
    marks = [(e[3], 1, e[2]) for e in spans]
    marks += [(e[3] + e[4], -1, e[2]) for e in spans]
    marks += [(a, 2, "") for a, _ in gaps] + [(b, -2, "") for _, b in gaps]
    marks.sort(key=lambda m: (m[0], m[1]))     # ends before starts
    out: Dict[str, float] = defaultdict(float)
    stack: List[str] = []
    idle, prev = False, 0
    for t, kind, name in marks:
        if idle and t > prev:
            out[stack[-1] if stack else ""] += (t - prev) * 1e-9
        prev = t
        if kind == 1:
            stack.append(name)
        elif kind == -1:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        else:
            idle = kind == 2
    return dict(out)


def reduce(events: Sequence[list],
           tick_span: str = "bench.tick") -> Spans:
    """Engine span durations, device time by lane-step phase and the
    idle time the engine's spans cover, inside the window from the first
    ``tick_span`` to the end of the last (the window ``trace.reduce``
    uses). Phase seconds are self times on the first device: an
    operation nested in another (a conditional's body) counts once."""
    ticks = [(e[3], e[3] + e[4]) for e in events if e[2] == tick_span]
    if not ticks:
        raise ValueError(f"no {tick_span!r} spans in the trace")
    w0, w1 = min(a for a, _ in ticks), max(b for _, b in ticks)
    host = [e for e in events if not TR.DEVICE_PLANE.match(e[0])
            and e[2].startswith(SPAN_PREFIX) and w0 <= e[3] < w1]
    host_s: Dict[str, List[float]] = defaultdict(list)
    for e in host:
        host_s[e[2]].append(e[4] * 1e-9)
    devs = sorted({e[0] for e in events if TR.DEVICE_PLANE.match(e[0])},
                  key=lambda p: int(TR.DEVICE_PLANE.match(p)[1]))
    first = [e for e in events if devs and e[0] == devs[0]]
    # self time per event: trace._self_times keys by operation name, so
    # each clipped event is named by its index
    clipped = TR._clip([e[:2] + [str(i)] + e[3:5]
                        for i, e in enumerate(first)], w0, w1)
    self_s = TR._self_times(clipped)
    phase_s: Dict[str, float] = defaultdict(float)
    scoped = 0
    for i, s in self_s.items():
        ph = phase_of(first[int(i)][5])
        if ph is not None:
            phase_s[ph] += s
            scoped += 1
    busy = TR._union([(max(e[3], w0), min(e[3] + e[4], w1))
                      for e in first if e[3] < w1 and e[3] + e[4] > w0])
    gaps, cur = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    tick_spans = [e for e in events if e[2] == tick_span]
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    return Spans(
        ticks=len(ticks), window_s=(w1 - w0) * 1e-9, host_s=dict(host_s),
        phase_s=dict(phase_s), scoped_ops=scoped,
        idle_s=sum(b - a for a, b in gaps) * 1e-9,
        idle_by_span=_idle_by_span(gaps, tick_spans + host),
        idle_gaps=[(TR._name_gap(a, b, host), (b - a) * 1e-9)
                   for a, b in longest])


# --- readers: each takes a Context whose ``spans`` is a ``Spans`` ------

def _spans(ctx) -> Optional[Spans]:
    s = getattr(ctx, "spans", None)
    return s if s is not None and s.ticks else None


def admit_host_ms(ctx) -> Optional[float]:
    """Median host time of one admission (``speca.admit``: lane fill and,
    for decode, the prompt prefill), in ms."""
    s = _spans(ctx)
    v = s.host_s.get("speca.admit") if s else None
    return 1e3 * statistics.median(v) if v else None


def sync_wait_ms_per_tick(ctx) -> Optional[float]:
    """Host time blocked on device-to-host reads (``speca.sync.*``) per
    traced tick, in ms."""
    s = _spans(ctx)
    if s is None or not any(k.startswith(SYNC_PREFIX) for k in s.host_s):
        return None
    return 1e3 * s.span_sum(SYNC_PREFIX) / s.ticks


def draft_ms_per_tick(ctx) -> Optional[float]:
    """Device-0 time of the operations under ``speca.draft`` or
    ``speca.rollback`` per traced tick, in ms."""
    s = _spans(ctx)
    if s is None or not s.scoped_ops:
        return None
    return 1e3 * (s.phase_s.get("speca.draft", 0.0)
                  + s.phase_s.get("speca.rollback", 0.0)) / s.ticks


def full_ms_per_tick(ctx) -> Optional[float]:
    """Device-0 time of the operations under ``speca.full`` (the full
    forward and the table refresh) per traced tick, in ms."""
    s = _spans(ctx)
    if s is None or not s.scoped_ops:
        return None
    return 1e3 * s.phase_s.get("speca.full", 0.0) / s.ticks

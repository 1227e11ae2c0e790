"""The measured run: warm-up, the window, and what the window saw.

One process drives the engine through ``submit`` / ``tick`` /
``result``. Clients follow the traffic generator; each request's latency
runs from its due time (the moment its client issued it) to the moment
its result was taken after a tick. Warm-up runs the same traffic until
every lane has served and released requests, so that nothing compiles
inside the window; the window then continues that traffic unchanged.
"""
from __future__ import annotations

import time
from contextlib import nullcontext
from dataclasses import dataclass, field
from typing import List, Optional

import jax


@dataclass
class Window:
    t0: float = 0.0
    t1: float = 0.0
    ticks: List[tuple] = field(default_factory=list)      # (start, end)
    occupancy: List[float] = field(default_factory=list)
    done: list = field(default_factory=list)
    compiles: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class CompileCounter:
    """Counts XLA backend compilations while ``on``."""

    def __init__(self) -> None:
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, *args, **kwargs):
        if self.on and "backend_compile" in name:
            self.n += 1


class Driver:
    def __init__(self, system, traffic) -> None:
        self.sys = system
        self.gen = traffic
        self.eng = system.engine
        self.tick_no = 0
        self.live = {}           # ticket id -> (client, spec, due_s, ticket)
        self.finished = 0
        self.served_clients = set()
        self.next_due = {}       # client id -> when its next batch is due

    def _issue(self) -> None:
        for c in self.gen.due(self.tick_no):
            # due when the client's previous batch came back, or now for
            # its first batch (at its start tick)
            now = self.next_due.pop(c.cid, None) or time.perf_counter()
            for _ in range(self.gen.batch):
                spec = self.gen.spec(c.cid)
                t = self.eng.submit(self.sys.request(spec))
                self.live[t.ticket_id] = (c, spec, now, t)
                c.outstanding[t.ticket_id] = now

    def step(self, window: Optional[Window], annotate: bool) -> None:
        """Issue what is due, run one engine tick, take its results."""
        self._issue()
        ctx = jax.profiler.TraceAnnotation("bench.tick") if annotate \
            else nullcontext()
        a = time.perf_counter()
        with ctx:
            results = self.eng.tick()
        b = time.perf_counter()
        self.tick_no += 1
        for res in results:
            c, spec, due, ticket = self.live.pop(res.ticket_id)
            res = self.eng.result(ticket)
            self.eng.release(ticket)
            now = time.perf_counter()
            del c.outstanding[res.ticket_id]
            if not c.outstanding:
                c.next_tick = self.tick_no
                self.next_due[c.cid] = now
            self.finished += 1
            self.served_clients.add(c.cid)
            if window is not None:
                window.done.append(self.sys.done(spec, res, now - due))
        if window is not None:
            window.ticks.append((a, b))
            window.occupancy.append(self.eng.in_flight() / self.sys.lanes)

    def warm_up(self, min_ticks: int) -> None:
        """Run the traffic until every client has finished a request, at
        least two lane-widths of requests have finished, and ``min_ticks``
        ticks have passed."""
        while not (self.tick_no >= min_ticks
                   and len(self.served_clients) == len(self.gen.clients)
                   and self.finished >= 2 * self.sys.lanes):
            self.step(None, annotate=False)
        jax.block_until_ready(self.sys.table())

    def measure(self, seconds: float, *, annotate: bool = False,
                counter: Optional[CompileCounter] = None) -> Window:
        w = Window()
        if counter is not None:
            counter.on, counter.n = True, 0
        w.t0 = time.perf_counter()
        while True:
            self.step(w, annotate)
            if time.perf_counter() - w.t0 >= seconds:
                break
        w.t1 = time.perf_counter()
        if counter is not None:
            counter.on = False
            w.compiles = counter.n
        return w

"""The one generator of client behaviour. A traffic mix is a JSON file of
parameters under ``bench/traffic/``; this module turns it and a seed into
closed-loop clients.

Parameters (every key optional unless noted):

``clients``       number of clients, or ``"lanes"`` for one per lane
``batch``         requests a client submits at once and waits for, or
                  ``"lanes"`` (default 1)
``start_span``    first requests are due at distinct ticks spread evenly
                  over ``[0, start_span)``, in a seeded order (default 0)
``steps``         schedule length of each request: absent = the
                  workload's whole schedule, or ``{"lognormal": {"median",
                  "sigma"}, "min", "max"}``
``prompt_lens``   prompt lengths drawn equally often (decode)
``pool``          size of the pools sizes are dealt from (default 512)

Every seed is dealt the same multiset of sizes and start offsets in a
different order, so a seed changes which request gets which size, not
how much work a run holds.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclass
class RequestSpec:
    """What a client asks for; the configuration turns it into a
    request of its own kind."""
    rid: int
    client: int
    seed: int
    steps: Optional[int] = None
    prompt_len: Optional[int] = None


@dataclass
class Client:
    cid: int
    start_tick: int
    outstanding: Dict[int, float] = field(default_factory=dict)
    next_tick: int = 0


def _resolve(value, lanes: int) -> int:
    return lanes if value == "lanes" else int(value)


def lognormal_pool(n: int, median: float, sigma: float, lo: int,
                   hi: int) -> List[int]:
    """``n`` stratified draws: the distribution's quantiles at
    (i + 1/2)/n, clipped to [lo, hi] and rounded."""
    nd = NormalDist()
    return [int(min(max(round(median * math.exp(sigma * nd.inv_cdf(
        (i + 0.5) / n))), lo), hi)) for i in range(n)]


class ClosedLoop:
    """Clients that each submit ``batch`` requests, wait for all of them,
    and submit the next batch at once."""

    def __init__(self, params: dict, lanes: int, seed: int) -> None:
        self.params = params
        self.rng = np.random.default_rng(seed)
        n = _resolve(params.get("clients", "lanes"), lanes)
        self.batch = _resolve(params.get("batch", 1), lanes)
        span = int(params.get("start_span", 0))
        offsets = [(i * span) // n for i in range(n)]
        self.rng.shuffle(offsets)
        self.clients = [Client(cid=i, start_tick=int(o), next_tick=int(o))
                        for i, o in enumerate(offsets)]
        pool = int(params.get("pool", 512))
        st = params.get("steps")
        if st:
            ln = st["lognormal"]
            self._steps = self._dealer(lognormal_pool(
                pool, ln["median"], ln["sigma"], st["min"], st["max"]))
        else:
            self._steps = None
        pl = params.get("prompt_lens")
        if pl:
            self._prompts = self._dealer([pl[i % len(pl)]
                                          for i in range(pool)])
        else:
            self._prompts = None
        self._rid = 0

    def _dealer(self, pool):
        """An endless stream dealing ``pool`` out in seeded orders."""
        def gen():
            while True:
                for v in self.rng.permutation(np.asarray(pool)):
                    yield int(v)
        return gen()

    @property
    def prompt_lens(self) -> List[int]:
        return list(self.params.get("prompt_lens") or [])

    def spec(self, client: int) -> RequestSpec:
        rid = self._rid
        self._rid += 1
        return RequestSpec(
            rid=rid, client=client,
            seed=int(self.rng.integers(0, 2 ** 31 - 1)),
            steps=next(self._steps) if self._steps else None,
            prompt_len=next(self._prompts) if self._prompts else None)

    def due(self, tick: int) -> List[Client]:
        """Clients with nothing outstanding whose next batch is due."""
        return [c for c in self.clients
                if not c.outstanding and c.next_tick <= tick]

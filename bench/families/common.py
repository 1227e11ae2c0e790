"""What the diffusion and decode configurations share: building the
program's configuration from the file's sizes, the lane mesh, and the
record of a finished request."""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional

import numpy as np


def model_config(conf: dict):
    """The program's ``ModelConfig`` for the file's ``model`` with every
    size the file states; raises if one of them is not a field."""
    from repro.configs import get_config
    cfg = get_config(conf["model"])
    fields = {f.name for f in dataclasses.fields(cfg)}
    sizes = {k: v for k, v in conf["sizes"].items() if k in fields}
    return dataclasses.replace(cfg, **sizes)


def speca_config(conf: dict):
    from repro.configs import SpeCaConfig
    return SpeCaConfig(**conf["speca"])


def lane_mesh(conf: dict, chips: int):
    """The engine's lane mesh over ``chips`` devices, or None."""
    if not conf["engine"].get("lane_mesh"):
        return None
    from repro.launch.mesh import make_lane_mesh
    return make_lane_mesh(chips)


def weight_seed(seed: int) -> int:
    """A 31-bit weight seed drawn from the run's seed."""
    return int(np.random.default_rng([seed, 1]).integers(0, 2 ** 31 - 1))


@dataclass
class Done:
    """One request finished inside the window."""
    spec: Any
    sample: np.ndarray
    num_full: int
    num_spec: int
    num_drafted: int
    latency_s: float
    units: int
    flops: float
    check: Optional[dict] = None


def expected_counters(steps: int, speca: dict):
    """(num_full, num_spec, num_drafted) of a request whose every draft
    is accepted."""
    from bench.reference.numerics import draft_schedule
    plan = draft_schedule(steps, speca["taylor_order"], speca["max_draft"])
    n = sum(plan)
    return steps - n, n, n


def counter_mismatches(done, speca: dict, steps_of) -> int:
    bad = 0
    for d in done:
        want = expected_counters(steps_of(d), speca)
        if (d.num_full, d.num_spec, d.num_drafted) != want:
            bad += 1
    return bad


def base_checks(done, limits: dict, speca: dict, steps_of) -> dict:
    """Checks every configuration makes: requests finished in the window
    (at least the limit) and finished requests whose accept counters
    differ from the schedule (at most the limit)."""
    return {"requests_done": (len(done), limits["requests_done"], "min"),
            "counter_mismatches": (counter_mismatches(done, speca, steps_of),
                                   limits["counter_mismatches"], "max")}


def sample_requests(done, n: int, seed: int, longest_first=False):
    """``n`` requests of ``done`` drawn from ``seed``; with
    ``longest_first`` the longest is always among them."""
    if not done:
        return []
    rng = np.random.default_rng([seed, 2])
    idx = list(rng.permutation(len(done)))
    if longest_first:
        top = max(range(len(done)), key=lambda i: done[i].units)
        idx.remove(top)
        idx.insert(0, top)
    return [done[i] for i in idx[:n]]


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))

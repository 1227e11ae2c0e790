"""Closed-loop per-lane τ/depth/order controller (sample-adaptive SpeCa).

SpeCa's pitch (paper §3) is *sample-adaptive* computation allocation,
but τ0, draft depth and forecast order are static per-request knobs in
the base engine.  This module closes the loop: a request that carries a
``ControllerPolicy`` (``RequestPolicy.controller``) gets a per-lane
feedback controller that adapts those knobs IN-FLIGHT from the lane's
own accept statistics — entirely inside the traced step, as lane-local
``[W]`` state vectors, with zero extra host sync (the FREE direction,
PAPERS.md arxiv 2511.20390: an online uncertainty statistic chooses
speculation depth).

Two SLO modes:

``slo="accept"`` (default) — hold the lane's per-drafted-position
    accept rate at ``target_accept``.  Above target the lane is "easy":
    the draft horizon ``draft_k`` steps up (more speculation per verify)
    and τ0 relaxes back toward — never above — the request's base τ0.
    Below target the lane is "hard": ``draft_k`` steps down, τ0
    tightens multiplicatively by ``1 − gain·(target − rate)``, and the
    forecast order cap steps down (less aggressive extrapolation).
    Sustained rejects therefore monotonically REDUCE speculation
    (never raise ``draft_k``, never raise τ0) — the property suite pins
    this — and τ0 ≤ base always, so a controlled lane's acceptance
    gate is never laxer than the static request's: quality can only
    match or improve while ``draft_k`` adaptation buys the speedup.

``slo="deadline"`` — pace the lane to finish its schedule within
    ``deadline_ticks`` engine ticks.  When the needed steps-per-tick
    exceed the lane's achieved (EMA) pace the controller deliberately
    trades quality for pace: ``draft_k`` steps up and τ0 relaxes up to
    ``tau_max`` (which MAY exceed the base — that is the point of a
    deadline SLO).  When comfortably ahead it banks the slack as
    quality: τ0 tightens and ``draft_k`` steps down.

All adapted values are clamped to the policy's bounds every tick, and
lanes that are finished (``active=False``), controller-off, or did not
draft this tick are frozen — their state vectors pass through
untouched, so controller-off requests sharing a batch with
controller-on requests are bitwise unaffected (pinned in
``tests/test_controller_properties.py``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

#: state keys the controller adds to the lane batch (all [W], axis 0 —
#: registered in ``repro.sharding.specs.LANE_STATE_AXES``)
CONTROLLER_KEYS: Tuple[str, ...] = (
    "ctl_on", "ctl_dl", "ctl_rate", "ctl_adv", "ctl_target", "ctl_gain",
    "ctl_ema", "ctl_tau_lo", "ctl_tau_hi", "ctl_tau_base", "ctl_k_lo",
    "ctl_k_hi", "ctl_order", "ctl_order_lo", "ctl_order_hi", "ctl_ticks",
    "ctl_deadline",
)

SLO_MODES = ("accept", "deadline")


@dataclass(frozen=True)
class ControllerPolicy:
    """Per-request closed-loop adaptation policy (see module docstring).

    ``tau_max=None`` bounds τ0 by the request's base τ0 (accept mode
    always does, regardless — the quality guarantee); ``order_max=None``
    bounds the forecast-order cap by the config's ``taylor_order``.
    ``k_max`` is additionally clamped by the engine's compiled
    ``max_draft_depth`` at fill time.
    """

    slo: str = "accept"
    target_accept: float = 0.6
    gain: float = 0.25
    ema: float = 0.8
    tau_min: float = 1e-4
    tau_max: Optional[float] = None
    k_min: int = 1
    k_max: int = 8
    order_min: int = 0
    order_max: Optional[int] = None
    deadline_ticks: Optional[float] = None

    def __post_init__(self) -> None:
        if self.slo not in SLO_MODES:
            raise ValueError(f"unknown controller slo {self.slo!r} "
                             f"(have {SLO_MODES})")
        if not 0.0 < self.target_accept <= 1.0:
            raise ValueError("target_accept must be in (0, 1], "
                             f"got {self.target_accept}")
        if not 0.0 < self.gain <= 1.0:
            raise ValueError(f"gain must be in (0, 1], got {self.gain}")
        if not 0.0 <= self.ema < 1.0:
            raise ValueError(f"ema must be in [0, 1), got {self.ema}")
        if self.tau_min < 0.0:
            raise ValueError(f"tau_min must be >= 0, got {self.tau_min}")
        if self.tau_max is not None and self.tau_max < self.tau_min:
            raise ValueError(f"tau_max={self.tau_max} < "
                             f"tau_min={self.tau_min}")
        if self.k_min < 1 or self.k_max < self.k_min:
            raise ValueError(f"need 1 <= k_min <= k_max, got "
                             f"k_min={self.k_min}, k_max={self.k_max}")
        if self.order_min < 0:
            raise ValueError(f"order_min must be >= 0, "
                             f"got {self.order_min}")
        if self.order_max is not None and self.order_max < self.order_min:
            raise ValueError(f"order_max={self.order_max} < "
                             f"order_min={self.order_min}")
        if self.slo == "deadline":
            if self.deadline_ticks is None or self.deadline_ticks <= 0:
                raise ValueError("slo='deadline' needs deadline_ticks > 0")


def init_controller_state(lanes: int, order: int) -> Dict[str, Any]:
    """Fresh (all-off) controller state vectors for a lane batch.

    Off lanes carry ``ctl_order = order`` (the config's full forecast
    order) so the order-cap mask in the prediction weights is a no-op
    for them — value-identical to the controller-free program.
    """
    W = lanes

    def zf():
        # a buffer per key: the engine donates the state, and one buffer
        # under two keys cannot be donated twice
        return jnp.zeros((W,), jnp.float32)

    return {
        "ctl_on": jnp.zeros((W,), bool),
        "ctl_dl": jnp.zeros((W,), bool),
        "ctl_rate": zf(),
        "ctl_adv": zf(),
        "ctl_target": zf(),
        "ctl_gain": zf(),
        "ctl_ema": zf(),
        "ctl_tau_lo": zf(),
        "ctl_tau_hi": zf(),
        "ctl_tau_base": zf(),
        "ctl_k_lo": jnp.ones((W,), jnp.int32),
        "ctl_k_hi": jnp.ones((W,), jnp.int32),
        "ctl_order": jnp.full((W,), int(order), jnp.int32),
        "ctl_order_lo": jnp.full((W,), int(order), jnp.int32),
        "ctl_order_hi": jnp.full((W,), int(order), jnp.int32),
        "ctl_ticks": jnp.zeros((W,), jnp.int32),
        "ctl_deadline": zf(),
    }


def lane_values(pol: Optional[ControllerPolicy], *, tau0: float,
                order: int, max_draft_depth: int) -> Dict[str, Any]:
    """Host-side per-lane controller state for one filled request.

    ``pol=None`` writes the all-off row (the controller-free values of
    :func:`init_controller_state`).  ``tau0`` is the lane's resolved
    base threshold, ``order`` the config's forecast order and
    ``max_draft_depth`` the engine's compiled chain bound.
    """
    if pol is None:
        return {"ctl_on": False, "ctl_dl": False, "ctl_rate": 0.0,
                "ctl_adv": 0.0, "ctl_target": 0.0, "ctl_gain": 0.0,
                "ctl_ema": 0.0, "ctl_tau_lo": 0.0, "ctl_tau_hi": 0.0,
                "ctl_tau_base": 0.0, "ctl_k_lo": 1, "ctl_k_hi": 1,
                "ctl_order": int(order), "ctl_order_lo": int(order),
                "ctl_order_hi": int(order), "ctl_ticks": 0,
                "ctl_deadline": 0.0}
    o_hi = int(order) if pol.order_max is None else min(int(pol.order_max),
                                                        int(order))
    o_lo = min(int(pol.order_min), o_hi)
    k_hi = max(1, min(int(pol.k_max), int(max_draft_depth)))
    k_lo = max(1, min(int(pol.k_min), k_hi))
    tau_lo = min(float(pol.tau_min), float(tau0))
    if pol.slo == "deadline" and pol.tau_max is not None:
        tau_hi = max(float(pol.tau_max), float(tau0))
    else:
        # the accept-SLO quality guarantee: τ0 never exceeds its base
        tau_hi = float(tau0)
    deadline = float(pol.deadline_ticks or 0.0)
    return {"ctl_on": True, "ctl_dl": pol.slo == "deadline",
            "ctl_rate": float(pol.target_accept), "ctl_adv": 1.0,
            "ctl_target": float(pol.target_accept),
            "ctl_gain": float(pol.gain), "ctl_ema": float(pol.ema),
            "ctl_tau_lo": tau_lo, "ctl_tau_hi": tau_hi,
            "ctl_tau_base": float(tau0), "ctl_k_lo": k_lo,
            "ctl_k_hi": k_hi, "ctl_order": o_hi, "ctl_order_lo": o_lo,
            "ctl_order_hi": o_hi, "ctl_ticks": 0,
            "ctl_deadline": deadline}


def controller_update(state: Dict[str, Any], *, step_new, n_spec,
                      n_drafted, advanced, active) -> Dict[str, Any]:
    """One traced controller tick over the lane batch.

    Reads the lane-batch ``state`` (controller vectors + ``tau0`` /
    ``draft_k`` / ``max_step``) and this tick's counters (all [W] i32:
    accepted drafted steps, drafted positions, total schedule advance),
    returns the adapted ``{tau0, draft_k, ctl_rate, ctl_adv, ctl_order,
    ctl_ticks}``.  Pure function of [W] vectors — lane b's outputs
    depend only on lane b's inputs, which is what makes controller-off
    lanes bitwise inert and keeps the whole update free of cross-lane
    (and cross-shard) traffic.
    """
    f32 = jnp.float32
    on = state["ctl_on"] & active
    ticks = jnp.where(on, state["ctl_ticks"] + 1, state["ctl_ticks"])
    adapt = on & (n_drafted > 0)
    inst = n_spec.astype(f32) / jnp.maximum(n_drafted, 1).astype(f32)
    ema = state["ctl_ema"]
    rate = jnp.where(adapt, ema * state["ctl_rate"] + (1.0 - ema) * inst,
                     state["ctl_rate"])
    adv = jnp.where(on, ema * state["ctl_adv"]
                    + (1.0 - ema) * advanced.astype(f32),
                    state["ctl_adv"])
    target, gain = state["ctl_target"], state["ctl_gain"]
    # accept SLO: easy lanes (rate >= target) speculate deeper and relax
    # τ back toward base; hard lanes back off on every axis
    hi_a = adapt & ~state["ctl_dl"] & (rate >= target)
    lo_a = adapt & ~state["ctl_dl"] & (rate < target)
    # deadline SLO: steps still owed per remaining tick vs achieved pace
    dl = on & state["ctl_dl"]
    remaining = jnp.maximum(state["ctl_deadline"] - ticks.astype(f32), 1.0)
    need = (state["max_step"] - step_new).astype(f32) / remaining
    behind = dl & (need > adv)
    ahead = dl & ~behind & (need <= 0.5 * adv)
    up = hi_a | behind
    down = lo_a | ahead
    move = up | down
    d_adj = state["draft_k"] + up.astype(jnp.int32) - down.astype(jnp.int32)
    draft_k = jnp.where(on, jnp.clip(d_adj, state["ctl_k_lo"],
                                     state["ctl_k_hi"]),
                        state["draft_k"])
    o_adj = state["ctl_order"] + up.astype(jnp.int32) \
        - down.astype(jnp.int32)
    ctl_order = jnp.where(on, jnp.clip(o_adj, state["ctl_order_lo"],
                                       state["ctl_order_hi"]),
                          state["ctl_order"])
    relax = jnp.where(hi_a, 1.0 + gain * (rate - target),
                      jnp.where(behind, 1.0 + gain, 1.0))
    tighten = jnp.where(lo_a, 1.0 - gain * (target - rate),
                        jnp.where(ahead, 1.0 - 0.5 * gain, 1.0))
    tau_adj = state["tau0"] * relax * tighten
    tau0 = jnp.where(move, jnp.clip(tau_adj, state["ctl_tau_lo"],
                                    state["ctl_tau_hi"]),
                     state["tau0"])
    return {"tau0": tau0, "draft_k": draft_k, "ctl_rate": rate,
            "ctl_adv": adv, "ctl_order": ctl_order, "ctl_ticks": ticks}

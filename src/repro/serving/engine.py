"""SpeCa serving engine — per-request policy, slot-width lanes, and
workload-agnostic sessions (diffusion denoising + LLM decode).

The paper's sample-adaptive allocation (§1) says each sample should get
exactly as much computation as its complexity demands. The engine realises
that at production batch sizes with a *lane scheduler*: concurrent
requests are packed into a fixed-width lane batch and ONE jitted step —
the unified forecast-verify step from ``repro.core.lane_step``, the same
implementation the reproduction sampler scans — advances all lanes per
scheduler tick:

  * every lane carries its own TaylorSeer difference-table metadata,
    ``since_anchor`` counter, denoising step index, accept decision AND
    verification threshold (per-request τ policy);
  * drafting runs through the fused per-lane Pallas Taylor kernels and the
    one-pass verification kernel (``kernels.ops.verify_accept_mixed``);
  * accepted lanes advance on the speculative output; rejected lanes are
    served by a masked full forward that refreshes ONLY their slice of the
    difference table — when every lane accepts, the full forward is
    skipped entirely;
  * lanes live at *different* denoising steps: when a lane finishes, the
    scheduler immediately refills it from the admission queue (continuous
    batching), in the order the pluggable ``Scheduler`` decides (FIFO /
    SJF / EDF / weighted-fair WFQ — ``repro.serving.scheduler``).

Serving API v2 (this module's public surface):

  * **Per-request policy** — everything that used to be an engine mode
    rides on the request (``repro.serving.policy.RequestPolicy``):
    guidance scale, negative/null conditioning, τ, max steps, priority,
    deadline. One engine serves guided and unguided traffic, with
    distinct scales and thresholds, in ONE batch.
  * **Slot-width scheduling** — the lane batch is organised in *pair
    slots* of two adjacent lanes (2k, 2k+1). An unguided request takes
    one lane; a guided request takes a whole pair (cond stream at 2k,
    uncond/negative stream at 2k+1) and flips the slot's ``paired``
    mask, which switches verification to ONE guided-residual decision
    per pair (``docs/cfg.md``). On a mesh the width rounds to ``2·D``
    so pair slots never straddle a shard.
  * **Request lifecycle** — ``submit() -> Ticket``, ``poll``/``result``/
    ``results``, a ``stream()`` generator (``previews=True`` adds
    per-step progressive snapshots), explicit ``tick()``, and
    ``shutdown()``. Requests are admitted continuously into free slots
    mid-run; a bounded admission queue (``max_queue``) raises
    ``QueueFull`` for backpressure. Every ticket walks the state
    machine queued → running → done | dropped (→ released) reported by
    ``status()`` — ``docs/serving.md``.
  * **Back-compat wrappers** — ``run_request``/``serve_batched``/
    ``serve`` are thin wrappers over the lifecycle that reproduce the
    pre-v2 trajectories (pinned in ``tests/test_serving_v2.py``);
    ``SpeCaEngine(guidance=True)`` becomes a default policy.
  * **Workload routing** — the forecast-verify loop is workload-
    agnostic (``repro.core.workload``): the same engine serves
    diffusion denoising lanes AND self-speculative LLM decode lanes.
    ``RequestPolicy.workload`` names the lane batch a request rides in;
    ONE scheduler admits both kinds from one queue (backfill across
    slot shapes), each workload tag owns one fixed-width session whose
    jitted step is compiled from its ``Workload`` adapter, and all busy
    sessions advance every engine tick. Construct with
    ``workloads={"decode": DecodeWorkload(...)}`` alongside (or instead
    of) the diffusion ``(cfg, params, dcfg, scfg)`` quartet; FLOPs
    accounting, accept rates and draft-K depth policy are per-workload
    (``Result.workload``).

Host/device discipline: the step function needs NOTHING from the host to
decide warm/draft/accept — all decision state lives on-device, and lane
completion is host-predictable (an active lane advances exactly one
denoising step per tick). The scheduler therefore dispatches ticks without
ever blocking on a device value; per-tick flags are fetched only when a
request completes (its sample must be read anyway).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Iterator, List, Optional, Tuple, Union

import jax
import numpy as np

from repro.configs.base import DiffusionConfig, ModelConfig, SpeCaConfig
from repro.core import controller as CT
from repro.core import lane_step as LS
from repro.core.forecaster import get_forecaster
from repro.core.workload import DiffusionWorkload, Workload
from repro.diffusion.pipeline import null_cond_like
from repro.obs import (Clock, Observability, Timings, Trace, build_trace,
                       resolve_clock, span)
from repro.serving.policy import QueueFull, RequestPolicy, Ticket
from repro.serving.scheduler import (QueueItem, Scheduler, fresh_scheduler,
                                     make_scheduler)


# histogram bucket grids for the per-request observability metrics:
# rates live in [0, 1]; latency seconds get a coarse log grid wide
# enough for CPU-interpret smoke runs and real hardware alike
_RATE_EDGES = tuple(i / 20.0 for i in range(1, 21))
_SECONDS_EDGES = tuple(float(x) for x in
                       (1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1, 0.3,
                        1.0, 3.0, 10.0, 30.0, 100.0, 300.0))


@dataclasses.dataclass
class Request:
    """One serving request: conditioning + noise seed + policy.

    ``policy`` carries every per-request decision (guidance, negative
    conditioning, τ, max steps, priority, deadline — see
    ``repro.serving.policy.RequestPolicy``). The legacy
    ``guidance_scale`` field is folded into the policy and WINS when
    both are set (it is the more explicit, per-request spelling pre-v2
    callers already rely on) — set only one of the two.
    """
    request_id: int
    cond: Dict[str, Any]
    seed: int = 0
    guidance_scale: Optional[float] = None
    policy: Optional[RequestPolicy] = None


@dataclasses.dataclass
class Result:
    """Per-request serving outcome and accounting.

    For a guided request every counter is per *decision*, not per lane:
    the request's cond/uncond pair drafts, verifies and accepts as one
    unit, so ``num_full + num_spec`` still sums to the request's
    schedule length and ``alpha`` stays comparable with unguided
    serving. ``flops`` does count both streams (a guided full forward
    is two denoiser rows).
    """
    request_id: int
    sample: Any
    num_full: int
    num_spec: int
    # algorithmic per-request cost of the request's own SpeCa schedule
    # (batch=1 equivalent) — lane packing never changes it, so sequential
    # and lane-batched runs account identically; device FLOPs of a packed
    # step additionally cover the accepted lanes' discarded forward rows
    flops: float
    wall_s: float
    accepts: Optional[List[bool]] = None   # per-step accept trajectory
    # drafted denoising steps (chain positions attempted): the
    # denominator of the PER-DRAFTED-STEP acceptance rate — a depth-K
    # chain that verifies once still counts K drafted steps, so deep
    # speculation can never inflate the accept rate (0 on results from
    # engines predating the field)
    num_drafted: int = 0
    # False when the engine drained the lane before the request reached
    # its final denoising step (tick-budget shutdown) or never started it;
    # such requests are excluded from allocation_report (``n_dropped``)
    completed: bool = True
    # lifecycle accounting (None for dropped-before-start requests):
    # the scheduler tick at which the request completed, and the
    # policy's deadline tick — ``deadline_met`` is their comparison
    finish_tick: Optional[int] = None
    deadline: Optional[float] = None
    ticket_id: Optional[int] = None
    # which lane workload served the request ("diffusion" / "decode"):
    # ``sample`` is a latent batch for diffusion, the emitted token row
    # for decode, and the FLOPs fields are that workload's cost model
    workload: str = "diffusion"
    # the policy's fair-queueing class, echoed back so per-tenant share
    # accounting (WFQ, benchmarks/serve_load.py) needs no side table
    tenant: str = "default"
    # lifecycle timestamps/tick indices through the engine's Clock seam
    # (repro.obs.Timings) — populated on every lifecycle-served request
    # whether or not observability is enabled; None only for requests
    # dropped before they ever started
    timings: Optional[Timings] = None

    @property
    def alpha(self) -> float:
        """Acceptance rate: fraction of steps served speculatively."""
        return self.num_spec / max(self.num_full + self.num_spec, 1)

    @property
    def draft_accept_rate(self) -> float:
        """Accepted drafted steps per drafted step (speculative-decoding
        style accounting): ``num_spec / num_drafted``. Counts every
        chain position the request drafted — one depth-K chain is K
        drafted steps, not one — so depth-1 and depth-K runs are
        directly comparable. 0.0 when the request never drafted."""
        return self.num_spec / max(self.num_drafted, 1)

    @property
    def deadline_met(self) -> Optional[bool]:
        """True/False against the policy deadline; None when the request
        had no deadline or never finished."""
        if self.deadline is None or self.finish_tick is None \
                or not self.completed:
            return None
        return self.finish_tick <= self.deadline


@dataclasses.dataclass(frozen=True)
class Preview:
    """One per-step streaming snapshot of a RUNNING request
    (``SpeCaEngine.stream(previews=True)``).

    ``sample`` is the request's current intermediate state read through
    the workload's ``emit`` hook — the partially-denoised latent batch
    for diffusion, the emitted-token prefix for decode. Snapshots are
    pure reads of the lane state: the request's final ``Result.sample``
    is bitwise identical to a non-streaming run (pinned in
    ``tests/test_serving_lifecycle.py``). ``step`` counts schedule
    steps completed at the snapshot (always < the request's resolved
    schedule length — the final state arrives as the ``Result``);
    ``tick`` is the serving session's scheduler tick.
    """

    ticket_id: int
    request_id: int
    tick: int
    step: int
    sample: Any
    workload: str = "diffusion"


@dataclasses.dataclass(eq=False)       # identity semantics: one _Entry
class _Entry:                          # may span two lanes
    """One in-flight request: its queue item and the lanes it occupies
    (one lane, or a whole pair slot for a guided request)."""
    item: QueueItem
    lanes: Tuple[int, ...]
    start_tick: int
    t0: float
    done: int = 0       # host-tracked denoising step counter
    draft_k: int = 1    # the request's draft horizon (policy.draft_depth)
    # engine-clock stamp at the end of the first per-tick flag fetch that
    # showed this entry's lane advanced — Timings.first_token_s
    first_token_s: Optional[float] = None

    @property
    def streams(self) -> int:
        return len(self.lanes)


class _Session:
    """One serving session: a fixed-width lane batch of ONE workload,
    its jitted step, and the host-side slot bookkeeping. The engine's
    lifecycle API holds one long-lived session per workload tag; the
    ``serve_batched`` wrapper spins up private ones per call so one-shot
    serving never perturbs lifecycle state.

    ``paired`` sessions compile the slot-width ("mixed") step program
    and can admit guided requests into pair slots; plain sessions
    compile the pre-v2 per-lane program (bit-identical trajectories for
    pure-unguided traffic). Pairing requires a workload that supports it
    (diffusion CFG); decode sessions are always plain.
    """

    def __init__(self, engine: "SpeCaEngine", width: int, *,
                 paired: bool,
                 workload: Optional[Workload] = None) -> None:
        self.e = engine
        self.wl = engine.workloads["diffusion"] if workload is None \
            else workload
        self.W = width
        self.paired = bool(paired) and width >= 2 \
            and self.wl.supports_pairing
        self.step_fn = engine._lane_step(
            width, "mixed" if self.paired else False, tag=self.wl.tag)
        self.state: Optional[Dict[str, Any]] = None
        self.lane_entry: List[Optional[_Entry]] = [None] * width
        self.tick = 0
        self._flag_log: List[Optional[Dict[str, Any]]] = []
        self._flag_np: Dict[int, Dict[str, np.ndarray]] = {}
        # host clock stamp at the START of each session tick, index-
        # aligned with _flag_log; kept only with obs on, for the trace
        # spans build_trace synthesises
        self._tick_s: List[Optional[float]] = []
        # device-side telemetry accumulator (None when obs is off)
        self._acc = engine._obs.lane_accumulator() \
            if engine._obs is not None else None

    # --- occupancy -------------------------------------------------------
    def busy(self) -> bool:
        return any(e is not None for e in self.lane_entry)

    def entries(self) -> List[_Entry]:
        out: List[_Entry] = []
        for e in self.lane_entry:
            if e is not None and e not in out:   # identity (eq=False)
                out.append(e)
        return out

    def _free_lanes(self) -> List[int]:
        return [l for l in range(self.W) if self.lane_entry[l] is None]

    def _free_pairs(self) -> List[int]:
        return [k for k in range(self.W // 2)
                if self.lane_entry[2 * k] is None
                and self.lane_entry[2 * k + 1] is None]

    def fits(self, item: QueueItem) -> bool:
        if item.policy.workload != self.wl.tag:
            return False
        if item.streams == 2:
            return self.paired and bool(self._free_pairs())
        return bool(self._free_lanes())

    # --- admission -------------------------------------------------------
    def _place(self, item: QueueItem) -> _Entry:
        if item.streams == 2:
            lane0 = 2 * self._free_pairs()[0]
            lanes: Tuple[int, ...] = (lane0, lane0 + 1)
        else:
            free = self._free_lanes()
            if self.paired:
                # prefer a lane whose pair partner is occupied, keeping
                # whole pairs free for guided admission
                half = [l for l in free
                        if l ^ 1 < self.W
                        and self.lane_entry[l ^ 1] is not None]
                free = half or free
            lanes = (free[0],)
        with span("speca.admit", ticket=item.ticket_id, lane=lanes[0]):
            entry = _Entry(item=item, lanes=lanes, start_tick=self.tick,
                           t0=self.e.clock.now(),
                           draft_k=int(item.policy.draft_depth or 1))
            for l in lanes:
                self.lane_entry[l] = entry
            self._fill(entry)
        obs = self.e._obs
        if obs is not None:
            obs.recorder.record(
                "admit", entry.t0, ticket=item.ticket_id,
                request=item.request.request_id, workload=self.wl.tag,
                tenant=item.policy.tenant, tick=entry.start_tick,
                lanes=list(entry.lanes))
        return entry

    def _fill(self, entry: _Entry) -> None:
        """Reset the entry's lane slice(s) for its request (host-side;
        every update writes one lane, but on a mesh each is an eager
        scatter at a traced lane index, which the SPMD partitioner
        compiles to an all-gather of the whole lane-sharded leaf).
        The workload contributes its dynamic payload through
        ``fill_payload`` (diffusion: the seed noise latent; decode: one
        prompt prefill scattered into the lane's cache slice)."""
        e, wl = self.e, self.wl
        req, pol = entry.item.request, entry.item.policy
        if self.state is None:
            self.state = LS.init_workload_state(
                wl, self.W, req.cond if wl.cond_in_state else {},
                guidance="mixed" if self.paired else False,
                forecaster=e.forecaster, controller=e.controller,
                mesh=e.mesh)
        tau0 = float(wl.scfg.tau0 if pol.tau0 is None else pol.tau0)
        lane0 = entry.lanes[0]
        # draft_k is pair-equal by construction: a guided pair drafts
        # pair-coherently, one chain decision per position (docs/cfg.md)
        self._fill_lane(lane0, req.cond, tau0, entry)
        if entry.streams == 2:
            nc = pol.negative_cond
            if nc is None:
                nc = e.null_cond if e.null_cond is not None \
                    else null_cond_like(wl.cfg, req.cond)
            self._fill_lane(lane0 + 1, nc, tau0, entry)
            gs = float(pol.guidance_scale)
            st = dict(self.state)
            st["gscale"] = st["gscale"].at[lane0:lane0 + 2].set(gs)
            st["paired"] = st["paired"].at[lane0:lane0 + 2].set(True)
            self.state = st
        elif self.paired:
            st = dict(self.state)
            st["paired"] = st["paired"].at[lane0].set(False)
            self.state = st

    def _fill_lane(self, lane: int, cond: Dict[str, Any], tau0: float,
                   entry: _Entry) -> None:
        wl = self.wl
        state = dict(self.state)
        state["draft_k"] = state["draft_k"].at[lane].set(entry.draft_k)
        state["max_step"] = state["max_step"].at[lane].set(
            entry.item.steps)
        state["diffs"] = state["diffs"].at[:, :, :, lane].set(0.0)
        state["n_anchors"] = state["n_anchors"].at[lane].set(0)
        state["anchor_step"] = state["anchor_step"].at[lane].set(-1)
        state["gap"] = state["gap"].at[lane].set(1.0)
        state["since"] = state["since"].at[lane].set(0)
        state["step"] = state["step"].at[lane].set(0)
        state["active"] = state["active"].at[lane].set(True)
        state["tau0"] = state["tau0"].at[lane].set(tau0)
        if self.e.controller:
            # closed-loop lanes start at the request's resolved knobs;
            # controller-free lanes get the all-off row (bitwise inert)
            cv = CT.lane_values(entry.item.policy.controller, tau0=tau0,
                                order=wl.scfg.taylor_order,
                                max_draft_depth=self.e.max_draft_depth)
            for ck, cval in cv.items():
                state[ck] = state[ck].at[lane].set(cval)
        if wl.cond_in_state:
            state["cond"] = {k: v.at[lane].set(cond[k][0])
                             for k, v in state["cond"].items()}
        self.state = wl.fill_payload(state, lane, entry.item.request,
                                     entry.item.steps)

    # --- advance ---------------------------------------------------------
    def advance(self) -> List[Tuple[_Entry, Result]]:
        """One scheduler tick: dispatch the jitted step (async — no host
        sync while every in-flight request is depth-1), then complete
        every entry whose schedule finished. With any deep-drafting
        entry in flight the per-tick advancement is data-dependent (a
        lane moves 0..K steps per tick), so the tick's ``advanced``
        counters are fetched — the one host/device sync deep speculation
        costs. Returns the completions."""
        if self.e._obs is not None:
            self._tick_s.append(self.e.clock.now())
        with span("speca.dispatch", tick=self.tick):
            state, flags = self.step_fn(self.state)   # async dispatch
            self.state = state
            self._flag_log.append(flags)
            self.tick += 1
            if self._acc is not None:
                # fold this tick's flags into the on-device accumulator —
                # one extra ASYNC dispatch, zero host syncs
                self._acc.update(flags)
        # controller entries adapt draft_k ON DEVICE, so their host-side
        # draft_k is only the starting point: treat them as deep (their
        # per-tick advancement is data-dependent like any chain lane)
        deep = any(e.draft_k > 1 or e.item.policy.controller is not None
                   for e in self.entries())
        if deep:
            adv = self._fetch(self.tick - 1)["advanced"]
            fetched_s = self.e.clock.now()
        completed: List[Tuple[_Entry, Result]] = []
        for entry in self.entries():
            # depth-1 entries advance exactly 1/tick (host-predictable)
            step = int(adv[entry.lanes[0]]) if deep else 1
            if deep and step and entry.first_token_s is None:
                entry.first_token_s = fetched_s
            entry.done += step
            if entry.done < entry.item.steps:
                continue
            # request complete: NOW touch the device (sample readback +
            # this entry's accumulated flags)
            completed.append((entry, self.harvest(entry, self.tick,
                                                  completed=True)))
            self._release(entry)
        self._gc_flags()
        return completed

    def _release(self, entry: _Entry) -> None:
        lane0, k = entry.lanes[0], entry.streams
        with span("speca.release", ticket=entry.item.ticket_id, lane=lane0):
            st = dict(self.state)
            for l in entry.lanes:
                self.lane_entry[l] = None
            st["active"] = st["active"].at[lane0:lane0 + k].set(False)
            if self.paired and entry.streams == 2:
                st["paired"] = st["paired"].at[lane0:lane0 + 2].set(False)
            self.state = st

    def _fetch(self, t: int) -> Dict[str, np.ndarray]:
        if t not in self._flag_np:
            with span("speca.sync.flags", tick=t):
                self._flag_np[t] = {k: np.asarray(v)
                                    for k, v in self._flag_log[t].items()
                                    if k in LS.COUNTER_FLAGS}
        return self._flag_np[t]

    def _gc_flags(self) -> None:
        # bound the flag log: ticks older than every in-flight entry's
        # start have been consumed
        live = [e.start_tick for e in self.entries()]
        horizon = min(live) if live else self.tick
        for t in [t for t in self._flag_np if t < horizon]:
            self._flag_np.pop(t)
            self._flag_log[t] = None      # keep indices stable

    def harvest(self, entry: _Entry, end_tick: int,
                completed: bool) -> Result:
        """Materialise one entry's Result from its accumulated flags
        (sample readback + flag fetch are the only device touches) —
        shared by the completion and the tick-budget drain paths so
        partial and full accounting can never diverge. Flags are read at
        the entry's first lane: for a guided pair the flags are
        pair-equal, so this is the pair's single decision."""
        with span("speca.harvest", ticket=entry.item.ticket_id,
                  lane=entry.lanes[0]):
            return self._harvest(entry, end_tick, completed)

    def _harvest(self, entry: _Entry, end_tick: int,
                 completed: bool) -> Result:
        item = entry.item
        obs = self.e._obs
        lane0, k = entry.lanes[0], entry.streams
        accepts: List[bool] = []
        per_tick: List[Dict[str, int]] = []
        n_drafted, n_full = 0, 0
        for t in range(entry.start_tick, end_tick):
            f = self._fetch(t)
            # per-STEP accept trajectory: each accepted drafted step is
            # one True, a tick closed by the full forward appends one
            # False — at depth 1 this is exactly the legacy per-tick
            # [accepted] entry
            ns, nf = int(f["n_spec"][lane0]), int(f["full"][lane0])
            accepts.extend([True] * ns + [False] * nf)
            n_full += nf
            # drafted chain positions, NOT verify rounds: the
            # per-drafted-step accounting denominator
            n_drafted += int(f["n_drafted"][lane0])
            if obs is not None:
                # trace rows come from the SAME rows this loop already
                # materialised — span synthesis adds no device reads
                per_tick.append({
                    "n_spec": ns, "full": nf,
                    "n_drafted": int(f["n_drafted"][lane0]),
                    "advanced": int(f["advanced"][lane0])})
        finish_s = self.e.clock.now()
        timings = Timings(
            submit_s=item.submit_s, admit_s=entry.t0, finish_s=finish_s,
            first_token_s=entry.first_token_s,
            submit_tick=item.submit_tick, admit_tick=entry.start_tick,
            finish_tick=end_tick)
        res = Result(
            request_id=item.request.request_id,
            sample=self.wl.emit(self.state, lane0, entry.done),
            num_full=n_full, num_spec=entry.done - n_full,
            num_drafted=n_drafted,
            # every drafted position pays one verify-layer forward;
            # every rejected tick pays one full forward — both at the
            # WORKLOAD's analytic cost (denoiser rows vs decode steps)
            flops=n_full * k * self.wl.full_flops
            + n_drafted * k * self.wl.verify_flops,
            wall_s=finish_s - entry.t0,
            accepts=accepts, completed=completed,
            finish_tick=end_tick, deadline=item.policy.deadline,
            ticket_id=item.ticket_id, workload=self.wl.tag,
            tenant=item.policy.tenant, timings=timings)
        if obs is not None:
            self._observe_done(entry, res, timings, per_tick)
        return res

    def _observe_done(self, entry: _Entry, res: Result,
                      timings: Timings,
                      per_tick: List[Dict[str, int]]) -> None:
        """Record one harvested request into the obs layer: lifecycle
        event, per-request metrics, and its span Trace (host-side only —
        every number here was already materialised by harvest)."""
        obs = self.e._obs
        item = entry.item
        wl, tenant = self.wl.tag, item.policy.tenant
        deep = entry.draft_k > 1 or item.policy.controller is not None
        trace = build_trace(
            ticket_id=item.ticket_id,
            request_id=item.request.request_id, workload=wl,
            tenant=tenant, completed=res.completed, timings=timings,
            per_tick=per_tick, tick_times=self._tick_s, deep=deep)
        obs.recorder.put_trace(trace)
        obs.recorder.record(
            "finish" if res.completed else "drop", timings.finish_s,
            ticket=item.ticket_id, request=item.request.request_id,
            workload=wl, tenant=tenant, tick=timings.finish_tick,
            num_full=res.num_full, num_spec=res.num_spec,
            num_drafted=res.num_drafted)
        m = obs.metrics
        kind = "completed" if res.completed else "dropped"
        m.counter(f"speca_requests_{kind}_total",
                  workload=wl, tenant=tenant).inc()
        # service share in schedule-step decisions × lane streams — the
        # WFQ ledger's unit, so tenant-share accounting reads directly
        m.counter("speca_service_steps_total",
                  workload=wl, tenant=tenant).inc(
                      res.num_full + res.num_spec)
        m.histogram("speca_accept_rate", edges=_RATE_EDGES,
                    workload=wl).observe(res.alpha)
        if res.num_drafted:
            m.histogram("speca_request_draft_accept_rate",
                        edges=_RATE_EDGES, workload=wl).observe(
                            res.draft_accept_rate)
        m.histogram("speca_queue_wait_s", edges=_SECONDS_EDGES,
                    workload=wl).observe(timings.queue_wait_s)
        m.histogram("speca_service_s", edges=_SECONDS_EDGES,
                    workload=wl).observe(timings.service_s)

    def drain(self) -> List[Tuple[_Entry, Result]]:
        """Tick-budget shutdown: harvest every in-flight entry as
        UNFINISHED — partial counters, ``completed=False``."""
        out = []
        for entry in self.entries():
            out.append((entry, self.harvest(entry, self.tick,
                                            completed=False)))
            self._release(entry)
        return out


def _dropped_result(item: QueueItem) -> Result:
    """A queued request that never started (engine shutdown)."""
    return Result(request_id=item.request.request_id, sample=None,
                  num_full=0, num_spec=0, flops=0.0, wall_s=0.0,
                  accepts=[], completed=False,
                  deadline=item.policy.deadline, ticket_id=item.ticket_id,
                  workload=item.policy.workload,
                  tenant=item.policy.tenant)


class SpeCaEngine:
    """Batched diffusion serving with per-lane speculative caching.

    accept_mode:
      * ``"per_sample"`` (default) — every lane accepts/rejects on its own
        error; rejected lanes get a masked full forward.
      * ``"batch"`` — reproduction parity with the seed sampler: all
        currently-drafting lanes must pass verification or all of them
        take the full forward.
    verify_backend:
      * ``"fused"`` (default) — the Pallas one-pass sums+threshold kernel.
      * ``"jnp"`` — unfused ``relative_error``; forced automatically for
        non-rel-L2 error metrics (the kernel implements eq. 4 only).
    mesh:
      * a 1-D ``('data',)`` mesh (``repro.launch.mesh.make_lane_mesh``)
        shards the lane axis of every per-lane array — latents, the
        (m+1, L, 2, W, T, D) difference table, since/active/step/τ
        vectors — over its D devices, so one engine serves W×D lanes.
        Params replicate; the Pallas kernels run per-shard through their
        ``shard_map`` wrappers. Accept/reject sequences, counters and
        FLOPs accounting are bit-identical to the unsharded engine;
        samples agree to f32 reduction-order tolerance
        (tests/test_serving_sharded.py).
    guidance (legacy):
      * ``True`` makes every request guided by default — requests whose
        policy leaves ``guidance_scale`` unset fall back to
        ``DiffusionConfig.guidance_scale``, exactly the pre-v2 guided
        engine. v2 engines do not need it: any request can opt into
        guidance through its ``RequestPolicy`` and mix with unguided
        traffic in the same batch.
    scheduler:
      * admission-queue policy — ``"fifo"`` (default, pre-v2 order),
        ``"sjf"``, ``"edf"``, or any ``repro.serving.scheduler.
        Scheduler`` instance/factory.
    max_queue:
      * bound on the admission queue; ``submit`` raises ``QueueFull``
        beyond it (backpressure). ``None`` = unbounded.
    default_policy:
      * ``RequestPolicy`` applied to requests that do not carry one.
    max_draft_depth:
      * compiled draft-chain length K of the lane step (default 1 — the
        exact legacy depth-1 program). Requests opt into deeper drafting
        per-lane via ``RequestPolicy.draft_depth`` (validated ≤ this
        bound at submit time); depth-1 requests on a deep engine follow
        their depth-1 trajectories unchanged. FLOPs and accept-rate are
        accounted PER DRAFTED STEP (``Result.num_drafted``/
        ``draft_accept_rate``) so depths are directly comparable.
    lanes:
      * default lane width of the lifecycle session started by the
        first ``submit`` (``serve_batched`` takes its own ``lanes=``).
    forecaster:
      * the feature-forecast table implementation behind the draft — a
        registered name (``"taylor"``/``"spectral"``) or a
        ``repro.core.forecaster.Forecaster`` instance. The default
        (``None`` → Taylor) builds the IDENTICAL trace to the
        pre-forecaster engine (``docs/forecasters.md``).
    controller:
      * ``True`` compiles the controller-capable step program: requests
        carrying a ``RequestPolicy.controller``
        (``repro.core.controller.ControllerPolicy``) get closed-loop
        per-lane adaptation of τ0 / draft depth / forecast order toward
        their SLO; controller-free requests in the same batch are
        bitwise unaffected. The default ``False`` builds the exact
        controller-free program, and controller policies are rejected
        at submit time (mirroring ``max_draft_depth``).
    workloads:
      * extra ``Workload`` adapters keyed by tag, e.g. ``{"decode":
        DecodeWorkload(lm_cfg, lm_params, scfg, ...)}``. Requests route
        by ``RequestPolicy.workload``; every tag gets its own lane
        session (its own width, jitted step and FLOPs model) but shares
        the scheduler, the admission queue and the lifecycle API. The
        diffusion quartet ``(cfg, params, dcfg, scfg)`` may be omitted
        entirely for a decode-only engine.
    """

    def __init__(self, cfg: Optional[ModelConfig] = None, params=None,
                 dcfg: Optional[DiffusionConfig] = None,
                 scfg: Optional[SpeCaConfig] = None, *,
                 draft_mode: str = "taylor",
                 accept_mode: str = "per_sample",
                 verify_backend: str = "fused",
                 guidance: bool = False,
                 null_cond: Optional[Dict[str, Any]] = None,
                 mesh: Optional[Any] = None,
                 scheduler: Any = "fifo",
                 max_queue: Optional[int] = None,
                 default_policy: Optional[RequestPolicy] = None,
                 max_draft_depth: int = 1,
                 lanes: int = 4,
                 forecaster: Any = None,
                 controller: bool = False,
                 workloads: Optional[Dict[str, Workload]] = None,
                 obs: Union[bool, Observability] = False,
                 clock: Optional[Clock] = None):
        if accept_mode not in LS.ACCEPT_MODES:
            raise ValueError(f"unknown accept_mode {accept_mode!r}")
        if max_draft_depth < 1:
            raise ValueError(f"max_draft_depth must be >= 1, "
                             f"got {max_draft_depth}")
        if verify_backend not in LS.VERIFY_BACKENDS:
            raise ValueError(f"unknown verify_backend {verify_backend!r}")
        if mesh is not None and "data" not in mesh.axis_names:
            raise ValueError("serving mesh needs a 'data' axis "
                             f"(got {mesh.axis_names})")
        make_scheduler(scheduler)      # fail fast on a bad scheduler spec
        self.cfg, self.params = cfg, params
        self.dcfg, self.scfg = dcfg, scfg
        self.workloads: Dict[str, Workload] = {}
        if cfg is not None:
            if dcfg is None or scfg is None:
                raise ValueError("diffusion serving needs the full "
                                 "(cfg, params, dcfg, scfg) quartet")
            # the adapter ctor resolves verify layer/table dtype — the
            # same fail-fast the pre-workload engine ran inline
            self.workloads["diffusion"] = DiffusionWorkload(
                cfg, params, dcfg, scfg)
        for tag, wl in (workloads or {}).items():
            if tag != wl.tag:
                raise ValueError(f"workloads key {tag!r} does not match "
                                 f"adapter tag {wl.tag!r}")
            self.workloads[tag] = wl
        if not self.workloads:
            raise ValueError("engine needs at least one workload: pass "
                             "the diffusion (cfg, params, dcfg, scfg) "
                             "quartet and/or workloads={...}")
        if mesh is not None:
            # the weights replicate over the lane mesh once, here, rather
            # than on every call of a sharded step
            from repro.sharding.specs import replicated
            self.workloads = {
                tag: wl.with_params(jax.device_put(wl.params,
                                                   replicated(mesh)))
                for tag, wl in self.workloads.items()}
        diff = self.workloads.get("diffusion")
        self.stepper = getattr(diff, "stepper", None)
        self.vl = diff.verify_layer if diff is not None else None
        self.n_tok = diff.num_tokens if diff is not None else None
        self.draft_mode = draft_mode
        self.accept_mode = accept_mode
        if any(wl.scfg.error_metric != "rel_l2"
               for wl in self.workloads.values()):
            verify_backend = "jnp"
        self.verify_backend = verify_backend
        self.mesh = mesh
        self.guidance = bool(guidance)
        if self.guidance and diff is None:
            raise ValueError("guidance=True is the legacy all-guided "
                             "diffusion mode; this engine serves no "
                             "diffusion workload")
        self.null_cond = null_cond
        self.scheduler_spec = scheduler
        self.max_queue = max_queue
        self.default_policy = default_policy
        self.max_draft_depth = int(max_draft_depth)
        self.default_lanes = lanes
        # resolve the forecaster NOW so a bad name fails at construction,
        # not at first compile; the instance is fixed per engine (part of
        # every session's compiled program)
        self.forecaster = get_forecaster(forecaster)
        self.controller = bool(controller)
        # observability (docs/observability.md): obs=False records no
        # metric, event or trace (pinned bitwise against obs=True in
        # tests/test_obs.py; the profiler spans are always compiled in
        # and record only under a profiler); obs=True builds a fresh
        # Observability on
        # the engine clock; a prebuilt Observability is adopted as-is
        # (sharing one registry across engines), and supplies the clock
        # when the caller passed none.
        if isinstance(obs, Observability):
            self._obs: Optional[Observability] = obs
            self.clock: Clock = resolve_clock(
                clock if clock is not None else obs.clock)
        else:
            self.clock = resolve_clock(clock)
            self._obs = Observability(clock=self.clock) if obs else None
        self._tick_count = 0   # engine-level tick index (series x-axis)
        # lanes one request occupies under the legacy engine-wide mode:
        # 1, or 2 for a guidance=True engine — kept for lane_width()
        self._streams = 2 if self.guidance else 1
        from repro.sharding.specs import lane_shard_count
        self._lane_shards = lane_shard_count(mesh)
        self._full_flops = diff.full_flops if diff is not None else 0.0
        self._verify_flops = diff.verify_flops if diff is not None else 0.0
        self._lane_fns: Dict[Tuple[str, int, Any], Any] = {}
        # lifecycle state (shared long-lived sessions, one per workload
        # tag; serve_batched uses private per-call sessions instead)
        self._sessions: Dict[str, _Session] = {}
        self._sched: Scheduler = make_scheduler(scheduler)
        self._seq = 0
        self._results: Dict[int, Result] = {}
        self._completion_order: List[int] = []
        self._ticket_status: Dict[int, str] = {}
        # tickets whose Result was release()d: no longer in _results /
        # _ticket_status, but NOT unknown — status() says "released" and
        # stream() treats them as already-consumed
        self._released: set = set()

    # --- policy resolution ----------------------------------------------
    def resolve_policy(self, req: Request,
                       base: Optional[RequestPolicy] = None
                       ) -> RequestPolicy:
        """The request's effective policy: ``base`` (an explicit
        override, e.g. ``submit(policy=...)``) or the request's own (or
        the engine default), with the legacy ``Request.guidance_scale``
        field and the legacy ``guidance=True`` engine mode folded in —
        the folding applies on EVERY path, so a request serves
        identically through submit and serve_batched."""
        pol = base if base is not None \
            else req.policy if req.policy is not None \
            else (self.default_policy or RequestPolicy())
        wl = self._workload(pol.workload)
        if req.guidance_scale is not None:
            pol = dataclasses.replace(
                pol, guidance_scale=float(req.guidance_scale))
        if self.guidance and wl.supports_pairing \
                and pol.guidance_scale is None:
            pol = dataclasses.replace(
                pol, guidance_scale=float(self.dcfg.guidance_scale))
        if pol.guided and not wl.supports_pairing:
            raise ValueError(
                f"workload {wl.tag!r} does not support guided lane "
                "pairs — classifier-free guidance is a diffusion "
                "concept; submit decode requests unguided")
        dk = pol.draft_depth
        if dk is not None and not 1 <= int(dk) <= self.max_draft_depth:
            raise ValueError(
                f"draft_depth={dk} outside this engine's compiled chain "
                f"(1..max_draft_depth={self.max_draft_depth}); construct "
                "SpeCaEngine(max_draft_depth=K) to serve deeper drafts")
        if pol.controller is not None:
            if not isinstance(pol.controller, CT.ControllerPolicy):
                raise TypeError(
                    "RequestPolicy.controller must be a "
                    "repro.core.controller.ControllerPolicy, got "
                    f"{type(pol.controller).__name__}")
            if not self.controller:
                raise ValueError(
                    "this engine compiled the controller-free step "
                    "program; construct SpeCaEngine(controller=True) to "
                    "serve closed-loop requests")
        if not pol.weight > 0:
            raise ValueError(
                f"RequestPolicy.weight must be > 0, got {pol.weight}")
        return pol

    def _workload(self, tag: str) -> Workload:
        try:
            return self.workloads[tag]
        except KeyError:
            raise ValueError(
                f"unknown workload {tag!r} (this engine serves "
                f"{sorted(self.workloads)})") from None

    def _lane_step(self, W: int, mode: Any = False,
                   tag: str = "diffusion"):
        """The jitted W-lane step (compiled once per workload × width ×
        program): ``mode=False`` is the plain per-lane program,
        ``"mixed"`` the slot-width pair-mask program. Returned as
        ``partial(jitted, params)``: call it with the lane state alone,
        or AOT-compile ``.func`` on ``(*.args, state)``."""
        key = (tag, W, mode)
        if key not in self._lane_fns:
            wl = self._workload(tag)

            def speca_lane_step(params, state):
                # the weights are the program's argument: traced through
                # a params-swapped adapter, never embedded as constants.
                # The function's name is the program's name in a profile
                # (jit_speca_lane_step), one for every workload and width
                return LS.build_workload_step(
                    wl.with_params(params), lanes=W,
                    draft_mode=self.draft_mode,
                    accept_mode=self.accept_mode,
                    verify_backend=self.verify_backend,
                    guidance=mode, max_draft_depth=self.max_draft_depth,
                    forecaster=self.forecaster,
                    controller=self.controller, mesh=self.mesh)(state)

            self._lane_fns[key] = functools.partial(
                jax.jit(speca_lane_step), wl.params)
            if self._obs is not None:
                # per-tag program-build count (the compile-cost proxy:
                # each new (tag, width, mode) key is one XLA program)
                self._obs.metrics.counter(
                    "speca_programs_built_total", workload=tag).inc()
                self._obs.recorder.record(
                    "compile", self.clock.now(), workload=tag,
                    width=W, mode=str(mode))
        return self._lane_fns[key]

    def lane_width(self, lanes: int, n_requests: int) -> int:
        """Effective lane width the scheduler will actually serve at:
        clamp to the request count (× streams-per-request), then round
        UP to a multiple of ``streams × lane-shard count`` so every
        shard owns an equal lane block and a guided cond/uncond pair
        never straddles a shard boundary (surplus lanes just stay
        inactive). Public — benchmarks label their per-device-count rows
        with this. Uses the engine-wide stream count (legacy
        ``guidance=True``); heterogeneous request lists are sized by
        ``serve_batched`` itself."""
        k = self._streams
        W = max(min(lanes, k * n_requests), k)
        mult = k * self._lane_shards
        return -(-W // mult) * mult

    def _width_for(self, lanes: int, policies: List[RequestPolicy]) -> int:
        """Slot-width sizing for a concrete request list: clamp to the
        total stream demand, keep room for the widest request, and round
        to the mesh multiple (``2·D`` as soon as any request is guided,
        so pair slots stay shard-local)."""
        total = sum(p.streams for p in policies)
        widest = max(p.streams for p in policies)
        W = max(min(lanes, total), widest)
        mult = widest * self._lane_shards
        return -(-W // mult) * mult

    # --- lifecycle API ---------------------------------------------------
    @property
    def current_tick(self) -> int:
        return max((s.tick for s in self._sessions.values()), default=0)

    def pending(self) -> int:
        """Queued (not yet admitted) request count."""
        return len(self._sched)

    def in_flight(self) -> int:
        """Admitted, not yet completed request count."""
        return sum(len(s.entries()) for s in self._sessions.values())

    def _new_session(self, wl: Workload, lanes: int) -> _Session:
        """A session for one workload tag: pair-capable diffusion slots
        (width a multiple of ``2·D``, minimum one pair) or plain decode
        lanes (width a multiple of ``D``)."""
        if wl.supports_pairing:
            W, mult, paired = max(lanes, 2), 2 * self._lane_shards, True
        else:
            W, mult, paired = max(lanes, 1), self._lane_shards, False
        W = -(-W // mult) * mult
        return _Session(self, W, paired=paired, workload=wl)

    def start(self, *, lanes: Optional[int] = None,
              workload: str = "diffusion") -> None:
        """Start one workload's lifecycle session explicitly (otherwise
        the first ``submit`` routed to that workload starts it at the
        engine's default width). Diffusion sessions are always
        pair-capable — the width rounds up to a multiple of ``2·D`` so
        guided and unguided submissions mix; decode sessions round to a
        multiple of the lane-shard count."""
        wl = self._workload(workload)
        if workload in self._sessions:
            raise RuntimeError(
                f"serving session for workload {workload!r} already "
                "started; shutdown() first to resize")
        self._sessions[workload] = self._new_session(
            wl, lanes if lanes is not None else self.default_lanes)

    def submit(self, req: Request,
               policy: Optional[RequestPolicy] = None) -> Ticket:
        """Queue one request; returns a ``Ticket`` to poll/stream on.

        ``policy`` overrides ``req.policy`` wholesale when given (the
        legacy ``Request.guidance_scale`` field and ``guidance=True``
        engine default still fold in on top, exactly as in
        ``serve_batched``). The policy's ``workload`` tag routes the
        request to that workload's session (started lazily at the
        default width). Raises ``QueueFull`` when the admission queue
        is at ``max_queue`` (bounded-queue backpressure — the caller
        sheds or retries; admitted work is never dropped).

        Rejection is side-effect free: the resolved policy AND the
        request payload (``Workload.validate_request`` — e.g. a decode
        prompt's shape/length) are validated BEFORE the workload session
        lazily starts or the ticket sequence advances, so a rejected
        submit leaves no empty compiled session behind (pinned in
        ``tests/test_serving_lifecycle.py``)."""
        if self.max_queue is not None and len(self._sched) >= self.max_queue:
            raise QueueFull(
                f"admission queue at max_queue={self.max_queue}")
        pol = self.resolve_policy(req, base=policy)
        wl = self.workloads[pol.workload]
        steps = pol.steps(wl.num_steps)
        wl.validate_request(req, steps)
        if pol.workload not in self._sessions:
            self.start(workload=pol.workload)
        sess = self._sessions[pol.workload]
        item = QueueItem(seq=self._seq, request=req, policy=pol,
                         steps=steps,
                         submit_tick=sess.tick,
                         ticket_id=self._seq,
                         submit_s=self.clock.now())
        self._seq += 1
        self._sched.push(item)
        self._ticket_status[item.ticket_id] = "queued"
        if self._obs is not None:
            self._obs.recorder.record(
                "submit", item.submit_s, ticket=item.ticket_id,
                request=req.request_id, workload=pol.workload,
                tenant=pol.tenant, steps=steps)
        return Ticket(ticket_id=item.ticket_id,
                      request_id=req.request_id,
                      submit_tick=item.submit_tick)

    @staticmethod
    def _admit_into(sessions: Dict[str, _Session],
                    sched: Scheduler) -> List[Tuple[_Session, _Entry]]:
        """Pop fitting requests into the sessions' free slots until
        nothing fits (continuous batching with cross-workload backfill:
        the scheduler decides the order, each workload's session decides
        the placement; a request whose session is full never blocks a
        request another session could admit)."""
        placed: List[Tuple[_Session, _Entry]] = []

        def fits(item: QueueItem) -> bool:
            sess = sessions.get(item.policy.workload)
            return sess is not None and sess.fits(item)

        while len(sched):
            item = sched.pop(fits)
            if item is None:
                break
            sess = sessions[item.policy.workload]
            placed.append((sess, sess._place(item)))
        return placed

    def tick(self, n: int = 1) -> List[Result]:
        """Advance the lifecycle sessions up to ``n`` scheduler ticks
        (admission + one async step dispatch per busy session each);
        returns the Results completed along the way. Stops early when
        the engine is idle."""
        done: List[Result] = []
        for _ in range(n):
            if not self._sessions:
                break
            with span("speca.tick", tick=self._tick_count):
                if self._obs is not None:
                    # sample queue state BEFORE admission so burst peaks
                    # are visible — the poll-boundary sampling this
                    # replaces saw the queue only after the tick had
                    # drained it
                    self._obs_tick_sample()
                for _sess, entry in self._admit_into(self._sessions,
                                                     self._sched):
                    self._ticket_status[entry.item.ticket_id] = "running"
                busy = [s for s in self._sessions.values() if s.busy()]
                if not busy:
                    break
                self._tick_count += 1
                for sess in busy:
                    for entry, res in sess.advance():
                        self._record(res)
                        done.append(res)
        return done

    def _obs_tick_sample(self) -> None:
        """One per-scheduler-tick sample of the engine's queue state
        (host-side integers only). Series are indexed by the engine
        tick counter so every tick lands exactly one point."""
        m = self._obs.metrics
        t = self._tick_count
        m.series("speca_queue_depth").append(t, len(self._sched))
        m.series("speca_in_flight").append(t, self.in_flight())

    def _record(self, res: Result) -> None:
        self._results[res.ticket_id] = res
        self._completion_order.append(res.ticket_id)
        # "dropped", not "done", for a request the engine did not finish
        # (drained mid-flight or never started at shutdown) — its Result
        # is still pollable/releasable, with completed=False
        self._ticket_status[res.ticket_id] = \
            "done" if res.completed else "dropped"

    @staticmethod
    def _tid(ticket: Union[Ticket, int]) -> int:
        return ticket.ticket_id if isinstance(ticket, Ticket) else ticket

    def poll(self, ticket: Union[Ticket, int]) -> Optional[Result]:
        """Non-blocking: the ticket's Result if it has completed, else
        None. Never advances the engine, never evicts the Result —
        long-lived engines should ``release()`` consumed tickets."""
        return self._results.get(self._tid(ticket))

    def release(self, *tickets: Union[Ticket, int]) -> None:
        """Drop completed tickets' bookkeeping (Result incl. its sample
        array, status, completion-order entry). Completed Results are
        otherwise retained indefinitely so ``poll``/``result`` stay
        repeatable — a long-lived lifecycle engine should release each
        ticket once its Result is consumed, or host memory grows by one
        sample per request served."""
        tids = {self._tid(t) for t in tickets}
        undone = [t for t in tids if t not in self._results]
        if undone:
            raise KeyError(f"tickets {sorted(undone)} have no completed "
                           "Result to release")
        for tid in tids:
            self._results.pop(tid)
            self._ticket_status.pop(tid, None)
            self._released.add(tid)
        # _completion_order keeps its (integer) entries so any in-flight
        # stream() cursor stays valid — streams skip released tickets;
        # _released distinguishes them from never-seen tickets (status()
        # "released", stream([t]) already-consumed instead of KeyError)

    def status(self, ticket: Union[Ticket, int]) -> str:
        """The ticket's lifecycle state (``docs/serving.md`` for the
        full state machine):

        * ``"queued"``   — admitted to the queue, not yet in a lane
        * ``"running"``  — occupying lanes in a workload session
        * ``"done"``     — completed its full schedule; Result pollable
        * ``"dropped"``  — drained unfinished or never started at
          ``shutdown()``; Result pollable with ``completed=False``
        * ``"released"`` — Result consumed and evicted via ``release()``
        * ``"unknown"``  — this engine never issued the ticket
        """
        tid = self._tid(ticket)
        if tid in self._released:
            return "released"
        return self._ticket_status.get(tid, "unknown")

    def result(self, ticket: Union[Ticket, int],
               max_ticks: Optional[int] = None) -> Result:
        """Run scheduler ticks until the ticket completes and return its
        Result (raises if the engine goes idle first — e.g. the ticket
        is unknown, or ``max_ticks`` ran out)."""
        tid = self._tid(ticket)
        budget = max_ticks
        while tid not in self._results:
            if budget is not None and budget <= 0:
                raise TimeoutError(f"ticket {tid} incomplete after the "
                                   "tick budget")
            if self._idle():
                raise KeyError(f"ticket {tid} is not pending on this "
                               "engine")
            self.tick()
            if budget is not None:
                budget -= 1
        return self._results[tid]

    def _idle(self) -> bool:
        return not (len(self._sched)
                    or any(s.busy() for s in self._sessions.values()))

    def results(self, tickets: List[Union[Ticket, int]]) -> List[Result]:
        """``result`` over a ticket list, preserving order."""
        return [self.result(t) for t in tickets]

    def _previews(self, want: Optional[set]) -> List[Preview]:
        """Per-step snapshots of the wanted RUNNING entries — a pure
        read of each lane's current state through the workload's
        ``emit`` hook. Only called from ``stream(previews=True)``, so
        non-streaming serving never pays the per-tick host sync."""
        out: List[Preview] = []
        for sess in self._sessions.values():
            for entry in sess.entries():
                tid = entry.item.ticket_id
                # deep-draft lanes can advance 0 steps on a tick: no
                # snapshot until the entry has progress to show
                if (want is None or tid in want) and entry.done > 0:
                    out.append(Preview(
                        ticket_id=tid,
                        request_id=entry.item.request.request_id,
                        tick=sess.tick,
                        step=min(entry.done, entry.item.steps),
                        sample=sess.wl.emit(sess.state, entry.lanes[0],
                                            entry.done),
                        workload=sess.wl.tag))
        return out

    def stream(self, tickets: Optional[List[Union[Ticket, int]]] = None,
               *, previews: bool = False
               ) -> Iterator[Union[Result, Preview]]:
        """Yield Results in COMPLETION order as the engine runs —
        ``tickets=None`` streams completions from this call on, until
        the engine is idle (previously streamed/collected Results are
        never replayed); a ticket list streams exactly those tickets —
        including any already completed — until all of them have been
        yielded, and raises ``KeyError`` up front for a ticket this
        engine has never seen. A ``release()``d ticket is treated as
        already-consumed: it contributes nothing and never blocks the
        stream. New submissions made while streaming are admitted
        continuously.

        ``previews=True`` additionally yields a :class:`Preview` per
        wanted RUNNING request after every scheduler tick — progressive
        per-step output (partially-denoised latents / decoded-token
        prefixes). Previews are pure reads of lane state: final Results
        are bitwise identical with previews on or off, and the extra
        host syncs are paid ONLY inside this generator — ticks driven
        by ``result()``/``tick()``/non-preview streams never fetch
        intermediate lane state."""
        want = None if tickets is None else {self._tid(t) for t in tickets}
        if want is not None:
            unknown = [t for t in want
                       if t not in self._ticket_status
                       and t not in self._released]
            if unknown:
                raise KeyError(f"tickets {sorted(unknown)} are not known "
                               "to this engine")
        emitted = len(self._completion_order) if want is None else 0
        while True:
            while emitted < len(self._completion_order):
                tid = self._completion_order[emitted]
                emitted += 1
                if (want is None or tid in want) \
                        and tid in self._results:   # skip released
                    yield self._results[tid]
            if want is not None and all(
                    t in self._results            # completed
                    or t in self._released        # or consumed+evicted
                    for t in want):
                return
            if self._idle():
                return
            self.tick()
            if previews:
                # snapshot entries still in flight AFTER the tick; the
                # tick's completions are about to be yielded as Results
                # by the drain loop above, never as a preview
                for pv in self._previews(want):
                    yield pv

    def shutdown(self) -> List[Result]:
        """Stop the lifecycle session NOW: in-flight requests come back
        ``completed=False`` with partial counters, queued requests come
        back never-started; the session is discarded (a new one starts
        on the next ``submit``). Returns the drained Results."""
        out: List[Result] = []
        for sess in self._sessions.values():
            for entry, res in sess.drain():
                self._record(res)
                out.append(res)
        for item in self._sched.drain():
            res = _dropped_result(item)
            self._record(res)
            out.append(res)
            if self._obs is not None:
                self._obs.recorder.record(
                    "drop", self.clock.now(), ticket=item.ticket_id,
                    request=item.request.request_id,
                    workload=item.policy.workload,
                    tenant=item.policy.tenant, started=False)
        if self._obs is not None:
            # the sessions own the device-side accumulators: flush them
            # into the registry before they are discarded
            self._flush_lane_metrics()
        self._sessions = {}
        return out

    # --- observability surface -------------------------------------------
    @property
    def obs(self) -> Optional[Observability]:
        """The engine's observability bundle (None when obs is off)."""
        return self._obs

    def _flush_lane_metrics(self) -> None:
        for tag, sess in self._sessions.items():
            if sess._acc is not None:
                sess._acc.flush_into(self._obs.metrics, workload=tag)

    def metrics_snapshot(self) -> List[Dict[str, Any]]:
        """Flush the device-side lane accumulators (the ONE host sync
        observability ever adds, paid only here) and return the plain-
        Python metrics snapshot. Raises when obs is off."""
        if self._obs is None:
            raise RuntimeError("engine constructed with obs=False — "
                               "pass SpeCaEngine(obs=True) for metrics")
        self._flush_lane_metrics()
        return self._obs.metrics.snapshot()

    def trace(self, ticket: Union[Ticket, int]) -> Optional[Trace]:
        """The completed ticket's span Trace from the flight recorder
        (None when unknown, evicted, or still in flight). Raises when
        obs is off."""
        if self._obs is None:
            raise RuntimeError("engine constructed with obs=False — "
                               "pass SpeCaEngine(obs=True) for traces")
        return self._obs.recorder.trace(self._tid(ticket))

    # --- batch=1 serving: the lanes=streams case of the scheduler --------
    def run_request(self, req: Request) -> Result:
        """Serve one request (the exact per-sample reference schedule) —
        one lane, or one lane pair for a guided request."""
        return self.serve_batched(
            [req], lanes=self.resolve_policy(req).streams)[0]

    def serve_batched(self, requests: List[Request], *, lanes: int = 4,
                      max_ticks: Optional[int] = None,
                      scheduler: Any = None) -> List[Result]:
        """Serve a request list to completion (back-compat wrapper over
        the lifecycle machinery — one private session per call).

        Packs up to ``lanes`` concurrent streams per jitted step;
        finished lanes are refilled from the queue immediately
        (continuous batching) in the order the scheduler decides
        (default: the engine's, default-default: FIFO — the pre-v2
        admission order, which keeps this wrapper trajectory-identical
        to the pre-v2 engine). Per-request accept trajectories are
        identical at every lane width — only the packing differs. On a
        mesh the width rounds up to a multiple of the lane-shard count
        (``2·D`` as soon as any request is guided) and each shard
        refills its own lane block in the same deterministic order.

        The dispatch loop never blocks on the device: an active lane
        finishes after exactly its schedule's ticks (tracked host-side),
        so per-tick flags are only materialised when one of the ticks'
        requests completes.

        ``max_ticks`` bounds the number of scheduler ticks (engine
        shutdown / drain): requests still in flight when the budget runs
        out come back with ``completed=False`` and their partial
        counters; queued requests that never started come back
        ``completed=False`` with ``sample=None``. ``allocation_report``
        counts both as ``n_dropped``.

        Guided requests occupy a pair slot of two lanes — cond/uncond —
        which fill, advance, complete and drain together; per-request
        accounting is per pair decision (flags are pair-equal by the
        lane-step guarantee). Unguided requests occupy single lanes, in
        the same batch.
        """
        if not requests:
            return []
        policies = [self.resolve_policy(r) for r in requests]
        # reject bad payloads BEFORE any session compiles (same
        # side-effect-free validation order as submit())
        for req, pol in zip(requests, policies):
            self.workloads[pol.workload].validate_request(
                req, pol.steps(self.workloads[pol.workload].num_steps))
        # one private session per workload tag present in the batch:
        # each gets its own width (sized to ITS requests) and jitted
        # step; a single-workload batch reproduces the pre-workload
        # trajectories exactly
        sessions: Dict[str, _Session] = {}
        for tag in sorted({p.workload for p in policies}):
            pols = [p for p in policies if p.workload == tag]
            any_guided = any(p.guided for p in pols)
            W = self._width_for(max(lanes, 1), pols)
            sessions[tag] = _Session(self, W, paired=any_guided,
                                     workload=self.workloads[tag])
        # a FRESH private queue: reusing a caller-supplied scheduler
        # instance here would drain lifecycle submissions into this
        # one-shot session
        sched = fresh_scheduler(self.scheduler_spec if scheduler is None
                                else scheduler)
        # queue/results key on queue position, not request_id, so
        # duplicate ids still get their own Result (matching lanes=1)
        for i, (req, pol) in enumerate(zip(requests, policies)):
            sched.push(QueueItem(
                seq=i, request=req, policy=pol,
                steps=pol.steps(self.workloads[pol.workload].num_steps),
                ticket_id=i, submit_s=self.clock.now()))
        results: Dict[int, Result] = {}
        while len(sched) or any(s.busy() for s in sessions.values()):
            if max_ticks is not None and max(
                    s.tick for s in sessions.values()) >= max_ticks:
                break
            self._admit_into(sessions, sched)
            for sess in sessions.values():
                if not sess.busy():
                    continue
                for entry, res in sess.advance():
                    results[entry.item.seq] = res
        # tick-budget shutdown: drain in-flight entries as UNFINISHED and
        # mark never-started queue entries the same way, so
        # allocation_report reports them in n_dropped instead of counting
        # them as served
        for sess in sessions.values():
            for entry, res in sess.drain():
                results[entry.item.seq] = res
        for item in sched.drain():
            results[item.seq] = _dropped_result(item)
        if self._obs is not None:
            # private per-call sessions still report: their accumulators
            # flush into the engine registry before they are discarded
            for tag, sess in sessions.items():
                if sess._acc is not None:
                    sess._acc.flush_into(self._obs.metrics, workload=tag)
        return [results[i] for i in range(len(requests))]

    def serve(self, requests: List[Request], *, lanes: int = 1,
              max_ticks: Optional[int] = None) -> List[Result]:
        """``serve_batched`` under its pre-v2 name and default width —
        one code path (the former sequential batch=1 loop IS the
        lanes=1 scheduler: a single slot served in queue order)."""
        return self.serve_batched(requests, lanes=max(lanes, 1),
                                  max_ticks=max_ticks)

    def warmup(self, cond: Dict[str, Any], *, lanes: int = 1,
               mixed: bool = False, workload: str = "diffusion") -> None:
        """Compile the serving step for ``lanes`` outside any timed window
        by serving enough dummy requests end-to-end to fill that width
        (this also warms the host loop and both lax.cond branches).
        ``workload`` selects WHICH slot program to pre-compile — the
        lane step compiles per workload tag as well as per width and
        program, so a mixed-traffic deployment warms each tag it will
        serve (``warmup(prompt_cond, workload="decode")`` compiles the
        decode lane step; pre-workload engines only ever warmed the
        diffusion programs).

        ``cond`` is a conditioning template with leading axis 1 — for
        decode a ``{"tokens": [1, P]}`` prompt dict; the lane step
        compiles per lane width AND per program, so warm the shape the
        real serve will use: the default warms the engine-mode program
        (plain, or all-guided pairs on a legacy ``guidance=True``
        engine), while ``mixed=True`` warms the v2 slot-width program —
        a guided+unguided dummy mix at this width — which is what
        lifecycle sessions (``submit``/``stream``) and heterogeneous
        ``serve_batched`` workloads compile — and is the ONLY program
        warmed then (those call sites never run the plain one).
        ``mixed`` is a pair-slot (diffusion) concept and is ignored for
        non-pairing workloads."""
        lanes = max(lanes, 1)
        wl = self._workload(workload)
        if not wl.supports_pairing:
            pol = RequestPolicy(workload=workload)
            reqs = [Request(request_id=-1 - i, cond=cond,
                            seed=90_000 + i, policy=pol)
                    for i in range(lanes)]
            self.serve_batched(reqs, lanes=lanes)
            return
        if not mixed or self.guidance:
            n = max(-(-lanes // self._streams), 1)
            reqs = [Request(request_id=-1 - i, cond=cond, seed=90_000 + i)
                    for i in range(n)]
            self.serve(reqs, lanes=lanes)
        if mixed and not self.guidance:
            gs = float(self.dcfg.guidance_scale) or 1.0
            greqs = [Request(request_id=-100, cond=cond, seed=90_100,
                             policy=RequestPolicy(guidance_scale=gs))] \
                + [Request(request_id=-101 - i, cond=cond,
                           seed=90_101 + i)
                   for i in range(max(lanes - 2, 0))]
            self.serve_batched(greqs, lanes=lanes)


def allocation_report(results: List[Result],
                      full_flops_per_step: float) -> Dict[str, float]:
    """Sample-adaptive allocation summary (paper §1: 57.5% @6.48× etc.).

    Splits requests at the median acceptance rate into easy/hard buckets
    and reports the realised FLOPs speedup of each bucket vs always-full.
    ``full_flops_per_step`` is the always-full cost of ONE schedule step
    — for guided results pass ``2 × forward_flops`` (a CFG step is two
    denoiser rows), matching ``Result.flops`` which counts both streams.
    Requests the engine did not finish — lanes drained mid-flight at a
    tick-budget shutdown, or queue entries that never started
    (``completed=False``) — and requests with non-finite accounting
    (corrupt ``flops``/``alpha``) are excluded and counted in
    ``n_dropped``: a partial schedule would skew every bucket statistic.
    """
    finite = [r for r in results
              if r.completed and math.isfinite(r.flops)
              and math.isfinite(r.alpha)]
    dropped = len(results) - len(finite)
    if not finite:
        return {"n_requests": 0, "n_dropped": dropped} if dropped else {}
    alphas = sorted(r.alpha for r in finite)
    median = alphas[len(alphas) // 2]
    easy = [r for r in finite if r.alpha >= median]
    hard = [r for r in finite if r.alpha < median]

    def bucket_speedup(rs: List[Result]) -> float:
        if not rs:
            return 1.0
        ref = sum((r.num_full + r.num_spec) * full_flops_per_step
                  for r in rs)
        return ref / max(sum(r.flops for r in rs), 1e-9)

    return {
        "n_requests": len(finite),
        "n_dropped": dropped,
        "frac_easy": len(easy) / len(finite),
        "frac_hard": len(hard) / len(finite),
        "speedup_easy": bucket_speedup(easy),
        "speedup_hard": bucket_speedup(hard),
        "speedup_all": bucket_speedup(finite),
        "alpha_easy": sum(r.alpha for r in easy) / max(len(easy), 1),
        "alpha_hard": sum(r.alpha for r in hard) / max(len(hard), 1),
        "alpha_mean": sum(r.alpha for r in finite) / len(finite),
    }

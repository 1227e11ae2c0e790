"""Arrival-rate sweep: find each scheduler's saturation knee.

The ROADMAP's open load-harness item: drive open-loop Poisson arrivals
at increasing rate λ (requests per scheduler tick) through each
admission scheduler (FIFO / SJF / EDF / WFQ) and locate the *saturation
knee* — the first λ whose p50 completion latency exceeds
``--knee-factor ×`` the latency at the lowest (uncongested) λ. Below
the knee the engine absorbs arrivals (latency ≈ service time); above
it the queue grows for the length of the run and latency is dominated
by waiting. The knee is the scheduler's usable-capacity summary, and
charting it across PRs (``tools/plot_perf_trajectory.py``) is the
regression alarm for serving capacity.

Method per (scheduler, λ) point:

  * the SAME seeded arrival trace replays against every scheduler
    (mixed τ0 / schedule length / tenant / deadline — the policy mix
    that differentiates SJF/EDF/WFQ from FIFO);
  * one engine PER SCHEDULER serves every λ point in sequence —
    compiled lane programs survive ``shutdown()``, so only the first
    point pays compile;
  * latency is measured in scheduler ticks (completion − arrival) from
    the drive loop, queue depth from the observability registry's
    per-tick ``speca_queue_depth``/``speca_in_flight`` series
    (``repro.obs``), sliced per point.

The run also measures **observability overhead**: interleaved obs-on /
obs-off drives of the same fixed-λ workload (best-of ``--overhead-
repeats`` each). ``--gate`` asserts the acceptance criteria — a knee
found for all four schedulers AND obs-on within ``--overhead-bound``
(default 3%) of obs-off — exiting nonzero otherwise (the CI leg runs
with ``--gate``).

Artifacts: ``serve_sweep.json`` (per-point rows),
``serve_sweep_knee.json`` (per-scheduler knee rows),
``serve_sweep_overhead.json`` (the obs on/off comparison).

Run (repo root on the path for ``benchmarks.common``):
  PYTHONPATH=src:. python benchmarks/serve_sweep.py \
      --requests 24 --lanes 4 --steps 6
  PYTHONPATH=src:. python benchmarks/serve_sweep.py --gate
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import get_model, print_table, write_result
from repro.configs import SpeCaConfig
from repro.serving import Request, RequestPolicy, SpeCaEngine

SCHEDULERS = ("fifo", "sjf", "edf", "wfq")
TENANTS = (("gold", 4.0), ("silver", 1.0), ("bronze", 1.0))


def build_arrivals(lam: float, cfg, args):
    """Seeded Poisson(λ)/tick arrival trace: ``[(tick, Request), ...]``.

    The policy mix (τ0, schedule length, tenant/weight, deadlines)
    matches serve_load's heterogeneous traffic so SJF/EDF/WFQ have
    something to reorder; the same seed at every λ and scheduler keeps
    points comparable."""
    rng = np.random.default_rng(args.seed)
    trace, t, i = [], 0, 0
    while i < args.requests:
        for _ in range(min(int(rng.poisson(lam)), args.requests - i)):
            tenant, weight = TENANTS[int(rng.integers(len(TENANTS)))]
            tau0 = float(rng.choice([0.2, 0.4, 0.8]))
            max_steps = int(max(args.steps // 2, 1)) \
                if rng.random() < 0.3 else None
            deadline = float(t + args.steps * (3 + 2 * rng.random())) \
                if rng.random() < 0.3 else None
            trace.append((t, Request(
                request_id=i,
                cond={"labels": jnp.asarray([i % cfg.num_classes])},
                seed=i,
                policy=RequestPolicy(tau0=tau0, max_steps=max_steps,
                                     deadline=deadline, tenant=tenant,
                                     weight=weight))))
            i += 1
        t += 1
    return trace


def drive_point(engine: SpeCaEngine, trace, *, max_ticks: int):
    """Replay one arrival trace to completion. Returns (per-request
    latency ticks, loop ticks, wall seconds, peak outstanding work) —
    the peak read from the obs series slice for this point when the
    engine has obs, else tracked host-side (the obs-off overhead leg)."""
    backlog = list(trace)
    arrivals = {}
    lats = []
    obs = engine.obs is not None
    n0 = len(engine.obs.metrics.series("speca_queue_depth")) if obs else 0
    peak_off = 0
    t0 = time.time()
    t = 0
    while backlog or engine.pending() or engine.in_flight():
        if t >= max_ticks:
            raise RuntimeError(f"sweep point did not drain in "
                               f"{max_ticks} ticks")
        while backlog and backlog[0][0] <= t:
            tick_, req = backlog.pop(0)
            arrivals[engine.submit(req).ticket_id] = tick_
        if not obs:
            peak_off = max(peak_off,
                           engine.pending() + engine.in_flight())
        for res in engine.tick():
            lats.append(t + 1 - arrivals.pop(res.ticket_id))
            engine.release(res.ticket_id)
        t += 1
    wall = time.time() - t0
    if obs:
        qd = engine.obs.metrics.series("speca_queue_depth").points()[n0:]
        fl = engine.obs.metrics.series("speca_in_flight").points()[n0:]
        peak = max((q + f for (_, q), (_, f) in zip(qd, fl)), default=0)
    else:
        peak = peak_off
    engine.shutdown()     # discard sessions; compiled programs survive
    return lats, t, wall, int(peak)


def make_engine(cfg, params, dcfg, scfg, args, *, scheduler: str,
                obs: bool = True) -> SpeCaEngine:
    eng = SpeCaEngine(cfg, params, dcfg, scfg, scheduler=scheduler,
                      lanes=args.lanes, obs=obs)
    eng.warmup({"labels": jnp.asarray([0])}, lanes=args.lanes, mixed=True)
    return eng


def sweep_scheduler(eng: SpeCaEngine, sched: str, lams, cfg, args):
    """All λ points for one scheduler → (point rows, knee row)."""
    rows, base_p50, knee = [], None, None
    for lam in lams:
        trace = build_arrivals(lam, cfg, args)
        lats, ticks, wall, peak = drive_point(
            eng, trace, max_ticks=args.max_ticks)
        p50 = float(np.percentile(lats, 50))
        p99 = float(np.percentile(lats, 99))
        if base_p50 is None:
            base_p50 = p50
        rows.append({"scheduler": sched, "lam": round(lam, 4),
                     "requests": len(trace), "ticks": ticks,
                     "wall_s": round(wall, 2),
                     "req_per_s": round(len(trace) / max(wall, 1e-9), 3),
                     "p50_latency": round(p50, 1),
                     "p99_latency": round(p99, 1),
                     "qdepth_peak": peak,
                     "saturated": bool(p50 > args.knee_factor * base_p50)})
        if knee is None and p50 > args.knee_factor * base_p50:
            knee = {"scheduler": sched, "knee_lam": round(lam, 4),
                    "base_p50": round(base_p50, 1),
                    "knee_p50": round(p50, 1),
                    "knee_factor": args.knee_factor}
    if knee is None:
        knee = {"scheduler": sched, "knee_lam": None,
                "base_p50": round(base_p50, 1), "knee_p50": None,
                "knee_factor": args.knee_factor}
    return rows, knee


def measure_overhead(cfg, params, dcfg, scfg, args, lam: float):
    """Best-of-N interleaved obs-on / obs-off drives of the same
    fixed-λ workload. Interleaving (off, on, off, on, ...) and taking
    each side's best wall time squeezes out the machine-load noise a
    single pair would alias into the ratio."""
    eng_off = make_engine(cfg, params, dcfg, scfg, args,
                          scheduler="fifo", obs=False)
    eng_on = make_engine(cfg, params, dcfg, scfg, args,
                         scheduler="fifo", obs=True)
    trace = build_arrivals(lam, cfg, args)
    best_off = best_on = float("inf")
    for _ in range(args.overhead_repeats):
        _, _, w_off, _ = drive_point(eng_off, trace,
                                     max_ticks=args.max_ticks)
        _, _, w_on, _ = drive_point(eng_on, trace,
                                    max_ticks=args.max_ticks)
        best_off, best_on = min(best_off, w_off), min(best_on, w_on)
    return {"obs_off_s": round(best_off, 3), "obs_on_s": round(best_on, 3),
            "overhead_ratio": round(best_on / max(best_off, 1e-9), 4),
            "repeats": args.overhead_repeats,
            "lam": round(lam, 4), "requests": len(trace)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="dit", choices=["dit", "flux"])
    ap.add_argument("--requests", type=int, default=32,
                    help="requests per (scheduler, λ) point — enough "
                         "backlog that supercritical λ visibly queues")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--steps", type=int, default=6,
                    help="diffusion schedule length")
    ap.add_argument("--scheduler", default=",".join(SCHEDULERS),
                    help="comma list of schedulers to sweep")
    ap.add_argument("--lam", default=None,
                    help="comma list of λ values; default is a "
                         "geometric grid around the lane-capacity "
                         "estimate lanes/steps")
    ap.add_argument("--knee-factor", type=float, default=2.0,
                    help="saturation threshold: first λ with p50 > "
                         "factor × base-λ p50")
    ap.add_argument("--overhead-repeats", type=int, default=3)
    ap.add_argument("--overhead-bound", type=float, default=1.03,
                    help="--gate fails when obs-on/obs-off exceeds this")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-ticks", type=int, default=100_000)
    ap.add_argument("--skip-overhead", action="store_true")
    ap.add_argument("--gate", action="store_true",
                    help="exit nonzero unless every scheduler has a "
                         "knee and obs overhead is within bound")
    args = ap.parse_args()

    cfg, dcfg, params = get_model(args.model)
    dcfg = dataclasses.replace(dcfg, num_inference_steps=args.steps)
    scfg = SpeCaConfig(taylor_order=2, max_draft=8, tau0=0.4, beta=0.9)

    # open-loop capacity estimate: `lanes` servers, ~`steps` ticks of
    # service per request → λ* ≈ lanes/steps requests per tick; the
    # grid brackets it so the final points are firmly supercritical
    cap = args.lanes / max(args.steps, 1)
    if args.lam:
        lams = [float(x) for x in args.lam.split(",") if x]
    else:
        lams = [round(cap * m, 4)
                for m in (0.25, 0.5, 1.0, 2.0, 4.0, 8.0)]
    scheds = [s.strip() for s in args.scheduler.split(",") if s.strip()]
    print(f"sweep: λ grid {lams} (capacity estimate {cap:.3f} req/tick), "
          f"schedulers {scheds}, {args.requests} requests/point")

    point_rows, knee_rows = [], []
    for sched in scheds:
        eng = make_engine(cfg, params, dcfg, scfg, args, scheduler=sched)
        rows, knee = sweep_scheduler(eng, sched, lams, cfg, args)
        point_rows += rows
        knee_rows.append(knee)
        print(f"{sched}: knee λ = {knee['knee_lam']} "
              f"(base p50 {knee['base_p50']} ticks → "
              f"{knee['knee_p50']} at the knee)")

    overhead = None
    if not args.skip_overhead:
        overhead = measure_overhead(cfg, params, dcfg, scfg, args,
                                    lam=cap)
        print(f"obs overhead: on {overhead['obs_on_s']}s vs off "
              f"{overhead['obs_off_s']}s → ratio "
              f"{overhead['overhead_ratio']} "
              f"(best of {overhead['repeats']})")

    print_table(f"serve_sweep ({args.model}, lanes={args.lanes}, "
                f"steps={args.steps})", point_rows)
    print_table("saturation knees", knee_rows)
    paths = [write_result("serve_sweep", point_rows),
             write_result("serve_sweep_knee", knee_rows)]
    if overhead is not None:
        paths.append(write_result("serve_sweep_overhead", [overhead]))
    print("wrote " + " and ".join(paths))

    if args.gate:
        missing = [k["scheduler"] for k in knee_rows
                   if k["knee_lam"] is None]
        if missing:
            print(f"GATE FAIL: no saturation knee found for {missing} "
                  f"(λ grid {lams} never saturated — widen it)")
            return 1
        if overhead is not None \
                and overhead["overhead_ratio"] > args.overhead_bound:
            print(f"GATE FAIL: obs overhead ratio "
                  f"{overhead['overhead_ratio']} exceeds "
                  f"{args.overhead_bound}")
            return 1
        print(f"GATE OK: knees for {[k['scheduler'] for k in knee_rows]}"
              + ("" if overhead is None else
                 f", obs overhead {overhead['overhead_ratio']} ≤ "
                 f"{args.overhead_bound}"))
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())

"""Sustained multi-tenant load: hundreds of mixed-policy requests
through the ``submit()``/``poll()`` lifecycle.

Where ``serve_throughput.py`` times one-shot ``serve_batched`` calls,
this harness drives the LIFECYCLE engine the way a deployment would:
bursty Poisson arrivals (seeded, fully deterministic) of heterogeneous
requests — guided and unguided diffusion, LLM decode lanes, mixed τ0,
mixed draft depths, short and full schedules, deadlines — submitted as
they "arrive", advanced one scheduler tick per loop step, completions
polled and ``release()``d as they land, and ``QueueFull`` backpressure
absorbed by retrying shed arrivals on later ticks.

Per ``--scheduler`` entry (e.g. ``fifo,wfq``) the SAME traffic trace
replays against a fresh engine and one summary row reports:

  * ``p50_latency`` / ``p99_latency`` — completion latency in loop
    ticks (finish tick − arrival tick; shed retries count against
    latency, as they would for a real client);
  * ``deadline_hit_rate`` — over the requests that carry deadlines;
  * ``share_<tenant>`` — each tenant's fraction of the service
    (schedule steps × lane streams) completed in the FIRST HALF of the
    run's completions: under saturation a weighted-fair scheduler
    front-loads high-weight tenants (``gold`` weight 4 vs ``bronze``
    weight 1), while FIFO tracks the arrival mix;
  * ``lat_<tenant>`` — per-tenant mean completion latency (the other
    face of the same fairness: WFQ trades bronze latency for gold);
  * ``qdepth_max`` and a queue-depth-over-time series
    (``serve_load_queue.json``: one row per SCHEDULER TICK per
    scheduler, read from the engine's observability registry —
    ``repro.obs`` — which samples inside ``tick()`` before admission,
    so burst peaks are captured instead of the drained post-tick
    queue) that feeds ``tools/plot_perf_trajectory.py``.

Run (repo root on the path for ``benchmarks.common``):
  PYTHONPATH=src:. python benchmarks/serve_load.py \
      --requests 60 --lanes 4 --steps 10 --scheduler fifo,wfq
  PYTHONPATH=src:. python benchmarks/serve_load.py \
      --requests 200 --lanes 8 --steps 12 --decode-frac 0.25
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import (get_lm_model, get_model, print_table,
                               write_result)
from repro.configs import SpeCaConfig
from repro.serving import (DecodeWorkload, QueueFull, Request,
                           RequestPolicy, SpeCaEngine)

# tenant -> WFQ weight: gold is promised 4× the service of either
# best-effort class while backlogged
TENANTS = (("gold", 4.0), ("silver", 1.0), ("bronze", 1.0))

ROW_COLS = ("scheduler", "requests", "lanes", "ticks", "wall_s",
            "req_per_s", "p50_latency", "p99_latency",
            "deadline_hit_rate", "qdepth_max", "shed_retries",
            "completed", "dropped") + tuple(
                f"share_{t}" for t, _ in TENANTS) + tuple(
                f"lat_{t}" for t, _ in TENANTS)


def _row(**kw):
    row = {c: None for c in ROW_COLS}
    unknown = set(kw) - set(ROW_COLS)
    if unknown:
        raise KeyError(f"unknown row columns: {sorted(unknown)}")
    row.update(kw)
    return row


def build_trace(cfg, lm_cfg, args):
    """The deterministic traffic trace: ``[(arrival_tick, Request,
    deadline_slack | None), ...]`` sorted by arrival.

    Arrivals are a Poisson process (mean ``--arrival-rate`` per tick)
    whose rate quadruples during periodic bursts — the pattern that
    actually stresses admission: long queues during the burst, drain
    between. Policies are drawn per request from the mixed pool
    (tenant, guidance, τ0, schedule length, draft depth, deadline,
    workload) with the seeded generator, so every scheduler serves the
    IDENTICAL trace."""
    rng = np.random.default_rng(args.seed)
    trace = []
    t = 0
    i = 0
    while i < args.requests:
        burst = (t // 16) % 4 == 3          # every 4th 16-tick window
        lam = args.arrival_rate * (4.0 if burst else 1.0)
        n = int(rng.poisson(lam))
        for _ in range(min(n, args.requests - i)):
            tenant, weight = TENANTS[int(rng.integers(len(TENANTS)))]
            tau0 = float(rng.choice([0.2, 0.4, 0.8]))
            max_steps = int(max(args.steps // 4, 1)) \
                if rng.random() < 0.3 else None
            depth = int(rng.integers(1, args.max_draft_depth + 1))
            # feasible-when-prioritised deadline on ~30% of requests;
            # slack is resolved into an absolute tick at submit time
            slack = float(args.steps * (2 + 2 * rng.random())) \
                if rng.random() < 0.3 else None
            decode = lm_cfg is not None and rng.random() < args.decode_frac
            if decode:
                prompt = rng.integers(0, lm_cfg.vocab_size,
                                      size=(1, args.prompt_len),
                                      dtype=np.int32)
                req = Request(
                    request_id=i, cond={"tokens": prompt}, seed=i,
                    policy=RequestPolicy(
                        workload="decode", tau0=args.decode_tau0,
                        max_steps=max_steps, draft_depth=depth,
                        tenant=tenant, weight=weight))
            else:
                gs = 4.0 if rng.random() < 0.3 else None
                req = Request(
                    request_id=i,
                    cond={"labels": jnp.asarray([i % cfg.num_classes])},
                    seed=i,
                    policy=RequestPolicy(
                        guidance_scale=gs, tau0=tau0,
                        max_steps=max_steps, draft_depth=depth,
                        tenant=tenant, weight=weight))
            trace.append((t, req, slack))
            i += 1
        t += 1
    return trace


def drive(engine: SpeCaEngine, trace, *, max_ticks: int):
    """Replay one trace against one engine: submit due arrivals, tick,
    consume+release completions. Returns (records, queue-depth series,
    shed-retry count, loop ticks, wall seconds).

    The queue-depth series comes from the engine's observability
    registry (``speca_queue_depth``/``speca_in_flight``), sampled
    INSIDE ``tick()`` before admission — every scheduler tick lands one
    point. The old poll-boundary sampling read the queue only after the
    tick had already admitted the burst into free lanes, so burst peaks
    were systematically under-reported."""
    backlog = list(trace)          # (arrival_tick, req, slack), sorted
    latency = {}                   # ticket_id -> (arrival_t, tenant)
    records = []                   # (Result, latency_ticks, tenant)
    shed = 0
    t0 = time.time()
    t = 0
    while backlog or engine.pending() or engine.in_flight():
        if t >= max_ticks:
            raise RuntimeError(
                f"load run did not drain within {max_ticks} loop ticks "
                f"({len(backlog)} backlogged, {engine.pending()} queued, "
                f"{engine.in_flight()} in flight)")
        while backlog and backlog[0][0] <= t:
            arrival, req, slack = backlog[0]
            pol = req.policy
            if slack is not None:
                # resolve the trace's relative slack into an absolute
                # scheduler-tick deadline at submit time
                steps = pol.steps(
                    engine.workloads[pol.workload].num_steps)
                pol = dataclasses.replace(
                    pol, deadline=float(engine.current_tick + steps
                                        + slack))
            try:
                ticket = engine.submit(req, policy=pol)
            except QueueFull:
                shed += 1
                backlog[0] = (t + 1, req, slack)   # retry next tick
                break
            latency[ticket.ticket_id] = arrival
            backlog.pop(0)
        for res in engine.tick():
            arrival = latency.pop(res.ticket_id)
            records.append((res, t + 1 - arrival, res.tenant))
            engine.release(res.ticket_id)
        t += 1
    wall = time.time() - t0
    # per-scheduler-tick queue state from the metrics registry (one
    # point per tick, pre-admission — the burst-peak fix)
    qd = engine.obs.metrics.series("speca_queue_depth").points()
    fl = engine.obs.metrics.series("speca_in_flight").points()
    depth_series = [(int(x), int(q), int(f))
                    for (x, q), (_, f) in zip(qd, fl)]
    dropped = engine.shutdown()
    for res in dropped:            # should be empty: the loop drains
        arrival = latency.pop(res.ticket_id)
        records.append((res, t - arrival, res.tenant))
    return records, depth_series, shed, t, wall


def summarize(name: str, records, depth_series, shed, ticks, wall,
              lanes: int):
    lats = np.asarray([lat for r, lat, _ in records if r.completed],
                      np.float64)
    met = [r.deadline_met for r, _, _ in records
           if r.deadline is not None]
    hit = sum(bool(m) for m in met) / len(met) if met else None
    completed = [rec for rec in records if rec[0].completed]
    # fairness: who got served EARLY — each tenant's share of the
    # service completed in the first half of the run's completions
    half = completed[:max(len(completed) // 2, 1)]
    service = {t: 0.0 for t, _ in TENANTS}
    for res, _, tenant in half:
        # service in schedule-step decisions (a guided pair is one
        # decision per step, same as Result accounting)
        service[tenant] += res.num_full + res.num_spec
    total = sum(service.values()) or 1.0
    by_tenant = {t: [lat for _, lat, tn in completed if tn == t]
                 for t, _ in TENANTS}
    return _row(
        scheduler=name,
        requests=len(records), lanes=lanes, ticks=ticks,
        wall_s=round(wall, 2),
        req_per_s=round(len(records) / max(wall, 1e-9), 3),
        p50_latency=round(float(np.percentile(lats, 50)), 1),
        p99_latency=round(float(np.percentile(lats, 99)), 1),
        deadline_hit_rate=None if hit is None else round(hit, 3),
        qdepth_max=max(q + f for _, q, f in depth_series),
        shed_retries=shed,
        completed=len(completed),
        dropped=len(records) - len(completed),
        **{f"share_{t}": round(service[t] / total, 3)
           for t, _ in TENANTS},
        **{f"lat_{t}": round(float(np.mean(by_tenant[t])), 1)
           if by_tenant[t] else None for t, _ in TENANTS})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="dit", choices=["dit", "flux"])
    ap.add_argument("--requests", type=int, default=200)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--steps", type=int, default=12,
                    help="diffusion schedule length")
    ap.add_argument("--scheduler", default="fifo,wfq",
                    help="comma list of admission schedulers; the same "
                         "trace replays against each")
    ap.add_argument("--arrival-rate", type=float, default=0.5,
                    help="mean Poisson arrivals per tick (4x in bursts)")
    ap.add_argument("--max-queue", type=int, default=32,
                    help="admission-queue bound (QueueFull backpressure)")
    ap.add_argument("--max-draft-depth", type=int, default=2)
    ap.add_argument("--decode-frac", type=float, default=0.25,
                    help="fraction of traffic routed to LLM decode "
                         "lanes (0 disables the decode workload)")
    ap.add_argument("--lm-arch", default="mamba2-130m")
    ap.add_argument("--decode-tau0", type=float, default=5.0)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-ticks", type=int, default=100_000,
                    help="liveness bound on the drive loop")
    args = ap.parse_args()

    cfg, dcfg, params = get_model(args.model)
    dcfg = dataclasses.replace(dcfg, num_inference_steps=args.steps)
    scfg = SpeCaConfig(taylor_order=2, max_draft=8, tau0=0.4, beta=0.9)
    lm = get_lm_model(args.lm_arch) if args.decode_frac > 0 else None
    lm_cfg = lm[0] if lm else None

    trace = build_trace(cfg, lm_cfg, args)
    n_decode = sum(r.policy.workload == "decode" for _, r, _ in trace)
    print(f"trace: {len(trace)} requests over "
          f"{trace[-1][0] + 1} arrival ticks "
          f"({n_decode} decode, {len(trace) - n_decode} diffusion)")

    def make_engine(sched: str) -> SpeCaEngine:
        workloads = {}
        if lm is not None:
            workloads["decode"] = DecodeWorkload(
                lm[0], lm[1], SpeCaConfig(tau0=args.decode_tau0),
                max_new_tokens=args.gen_len,
                max_seq_len=args.prompt_len + args.gen_len)
        eng = SpeCaEngine(cfg, params, dcfg, scfg, scheduler=sched,
                          max_queue=args.max_queue,
                          max_draft_depth=args.max_draft_depth,
                          lanes=args.lanes, workloads=workloads,
                          obs=True)
        # compile outside the timed drive loop: the lifecycle diffusion
        # session runs the mixed slot program, decode the plain one
        eng.warmup({"labels": jnp.asarray([0])}, lanes=args.lanes,
                   mixed=True)
        if lm is not None:
            warm = np.zeros((1, args.prompt_len), np.int32)
            eng.warmup({"tokens": warm}, lanes=args.lanes,
                       workload="decode")
        return eng

    rows, depth_rows = [], []
    for sched in [s.strip() for s in args.scheduler.split(",") if s]:
        eng = make_engine(sched)
        records, depth_series, shed, ticks, wall = drive(
            eng, trace, max_ticks=args.max_ticks)
        rows.append(summarize(sched, records, depth_series, shed,
                              ticks, wall, args.lanes))
        depth_rows += [{"scheduler": sched, "tick": t, "queued": q,
                        "in_flight": f} for t, q, f in depth_series]
        r = rows[-1]
        print(f"{sched}: p50 {r['p50_latency']} / p99 "
              f"{r['p99_latency']} ticks, hit-rate "
              f"{r['deadline_hit_rate']}, max queue depth "
              f"{r['qdepth_max']}, gold/bronze early share "
              f"{r['share_gold']}/{r['share_bronze']}")

    print_table(f"serve_load ({args.model}, {args.requests} requests, "
                f"lanes={args.lanes})", rows)
    path = write_result("serve_load", rows)
    qpath = write_result("serve_load_queue", depth_rows)
    print(f"wrote {path} and {qpath}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

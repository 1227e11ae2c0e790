"""Serving throughput: sequential batch=1 vs per-lane batched scheduling.

Reports requests/s for both modes plus the Table-2-style sample-adaptive
allocation split (paper §1: 57.5% of samples at 6.48x / 42.5% at lower
acceleration): requests are bucketed at the median acceptance rate into
easy/hard and each bucket's realised FLOPs speedup is shown. Because the
lane scheduler reproduces the exact batch=1 accept trajectories, the two
modes serve identical work — the requests/s delta is pure scheduling.

``--workload diffusion,decode,mixed`` selects WHICH traffic is served
(workload-agnostic lane core, docs/llm_serving.md). Every row carries a
``workload`` column. ``decode`` serves LLM self-speculative decode lanes
(``DecodeWorkload`` over a small cached LM) twice — once at
``--decode-tau0`` and once reject-always (τ0=0, plain greedy decoding) —
so the artifact tracks the decode accept rate AND the FLOPs win of
self-speculation over always-full decoding (``tok_per_s`` is the decode
throughput column). ``mixed`` serves diffusion and decode requests
through ONE engine concurrently and reports one row per workload with
per-workload accept rates — the CI liveness signal that heterogeneous
traffic shares the engine without perturbing either side.

``--devices 1,2,4`` adds one lane-scheduler row per device count D: the
engine lane-shards over a D-device ``('data',)`` mesh (requests/s per
device count is the CI artifact column tracking how serving capacity
scales with the mesh). The process must see max(D) devices — on CPU set
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` first.

``--guidance-scale S`` (S>0) benchmarks classifier-free-guidance serving:
every run serves cond/uncond lane PAIRS with one verify decision per pair
(docs/cfg.md), and one extra ``split`` row serves the same work as
2×requests *independent* unguided lanes — the cond and uncond streams as
separate requests, each verifying on its own. ``req_per_s`` counts USER
requests on both rows (a split "request" is half a user request), so the
paired-vs-split delta is the structural win of one decision per pair:
the split streams reject independently, so the union of their rejections
forces more full forwards for the same guided work. Every JSON row
carries a ``guidance`` column (0.0 = unguided) so the perf-trajectory
artifact can chart guided vs unguided requests/s across PRs.

``--draft-depth 1,3`` adds two rows per depth K (deep speculation,
docs/serving.md): a ``depth=K`` row serving the full workload with
per-request ``RequestPolicy(draft_depth=K)`` on a ``max_draft_depth=K``
engine, and a ``depth=K,easy`` row serving only the EASY half of the
workload (requests at or above the median depth-1 acceptance rate —
exactly where chains run long, so where γ>1 drafting pays). Every row
reports ``draft_accept_rate`` = Σ accepted drafted steps / Σ drafted
steps — accounted PER DRAFTED STEP, so a depth-K chain that verifies
once still counts K drafted steps and depths compare honestly. The win
condition tracked by CI: ``depth=3,easy`` requests/s beats
``depth=1,easy``.

``--forecaster taylor,spectral`` adds one row per forecaster family
(pluggable forecasters, docs/forecasters.md): the same diffusion
workload served by an engine compiled with that forecaster, with
per-drafted-step accept rate and total served GFLOPs columns — the CI
artifact tracks what the spectral frequency-band basis buys over the
Taylor difference table at identical τ0 and width.

``--scheduler fifo,sjf,edf`` adds one row per admission scheduler
(serving API v2) serving a MIXED-LENGTH workload: long full-schedule
requests alternating with short ``max_steps=steps/4`` requests that
carry tight deadlines. Scheduling reorders admission only — per-request
trajectories are untouched — so the rows isolate the pure policy win:
``mean_completion_ticks`` (SJF < FIFO on any such workload: shortest-
job-first is completion-time optimal) and ``deadline_hit_rate``
(EDF > FIFO: earliest-deadline-first serves the tight-deadline shorts
before the deadline-less longs that FIFO lets block them).

Run (repo root must be on the path for ``benchmarks.common``):
  PYTHONPATH=src:. python benchmarks/serve_throughput.py \
      --requests 12 --lanes 4 --steps 30
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src:. python benchmarks/serve_throughput.py \
      --requests 8 --lanes 4 --steps 12 --devices 1,2,4
  PYTHONPATH=src:. python benchmarks/serve_throughput.py \
      --requests 8 --lanes 4 --steps 12 --guidance-scale 4.0
  PYTHONPATH=src:. python benchmarks/serve_throughput.py \
      --requests 8 --lanes 2 --steps 12 --scheduler fifo,sjf,edf
  PYTHONPATH=src:. python benchmarks/serve_throughput.py \
      --requests 4 --lanes 2 --steps 12 --workload diffusion,decode,mixed
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (get_lm_model, get_model, print_table,
                               write_result)
from repro.configs import SpeCaConfig
from repro.core.complexity import forward_flops
from repro.diffusion.pipeline import null_cond_like
from repro.launch.mesh import make_lane_mesh
from repro.serving import (DecodeWorkload, Request, RequestPolicy,
                           SpeCaEngine, allocation_report)

# one shared column schema across diffusion/decode/mixed rows so the
# printed table and the artifact JSON stay rectangular (print_table
# takes its header from the first row)
ROW_COLS = ("mode", "workload", "devices", "lanes", "guidance",
            "scheduler", "draft_depth", "forecaster", "requests", "wall_s",
            "req_per_s", "tok_per_s", "alpha_mean", "draft_accept_rate",
            "gflops", "frac_easy", "frac_hard", "speedup_easy",
            "speedup_hard", "speedup_all", "serving_speedup",
            "trajectory_mismatches", "mean_completion_ticks",
            "deadline_hit_rate")


def _row(**kw):
    row = {c: None for c in ROW_COLS}
    row.update({"workload": "diffusion", "devices": 1, "guidance": 0.0,
                "scheduler": "fifo", "draft_depth": 1,
                "forecaster": "taylor"})
    unknown = set(kw) - set(ROW_COLS)
    if unknown:
        raise KeyError(f"unknown row columns: {sorted(unknown)}")
    row.update(kw)
    return row


def make_requests(cfg, n: int, *, offset: int = 0, guidance_scale=None):
    return [Request(request_id=offset + i,
                    cond={"labels": jnp.asarray([i % cfg.num_classes])},
                    seed=offset + i, guidance_scale=guidance_scale)
            for i in range(n)]


def decode_requests(lm_cfg, n: int, prompt_len: int, *, tau0: float,
                    offset: int = 0, max_steps=None):
    """Decode-workload traffic: each request carries a distinct random
    prompt and a per-request τ0 policy (τ0=0 → reject-always greedy)."""
    out = []
    for i in range(n):
        prompt = np.asarray(
            jax.random.randint(jax.random.PRNGKey(offset + i),
                               (1, prompt_len), 0, lm_cfg.vocab_size),
            np.int32)
        out.append(Request(
            request_id=offset + i, cond={"tokens": prompt},
            seed=offset + i,
            policy=RequestPolicy(workload="decode", tau0=tau0,
                                 max_steps=max_steps)))
    return out


def deadline_workload(cfg, n: int, steps: int, lanes: int):
    """Mixed-length workload for the scheduler comparison: even indices
    are long full-schedule requests (no deadline), odd indices are short
    ``steps//4`` requests whose deadline is feasible when served ahead
    of the longs (k-th short: ceil(k/lanes)·short + steps/2 ticks) but
    blown as soon as FIFO parks them behind a long request. Completion
    ticks depend only on admission order and schedule lengths — never on
    accept decisions — so the scheduler deltas below are deterministic.
    """
    short = max(steps // 4, 1)
    reqs, k = [], 0
    for i in range(n):
        pol = None
        if i % 2 == 1:
            k += 1
            dl = float(-(-k // max(lanes, 1)) * short + steps // 2)
            pol = RequestPolicy(max_steps=short, deadline=dl)
        reqs.append(Request(
            request_id=i,
            cond={"labels": jnp.asarray([i % cfg.num_classes])},
            seed=i, policy=pol))
    return reqs


def sched_stats(results):
    """(mean completion ticks, deadline hit rate | None)."""
    ticks = [r.finish_tick for r in results if r.finish_tick is not None]
    met = [r.deadline_met for r in results if r.deadline is not None]
    mean_ticks = sum(ticks) / max(len(ticks), 1)
    hit = sum(bool(m) for m in met) / len(met) if met else None
    return mean_ticks, hit


def split_requests(cfg, guided_requests):
    """The two-independent-streams baseline: each guided request becomes
    a conditional AND an unconditional unguided request sharing its seed
    (same noise), so the same model work is served — but every stream
    verifies and accepts on its own, with no pair coupling."""
    out = []
    for r in guided_requests:
        out.append(Request(request_id=2 * r.request_id, cond=r.cond,
                           seed=r.seed))
        out.append(Request(request_id=2 * r.request_id + 1,
                           cond=null_cond_like(cfg, r.cond), seed=r.seed))
    return out


def bench(engine: SpeCaEngine, requests, *, lanes: int):
    t0 = time.time()
    results = engine.serve(requests, lanes=lanes)
    wall = time.time() - t0
    return results, wall


def draft_accept_rate(results) -> float:
    """Workload-level PER-DRAFTED-STEP acceptance: Σ accepted drafted
    steps over Σ drafted chain positions. One depth-K chain contributes
    K drafted steps to the denominator — counting it as one verify
    would let deep runs inflate the rate."""
    spec = sum(r.num_spec for r in results)
    drafted = sum(r.num_drafted for r in results)
    return spec / max(drafted, 1)


def _rep_cols(rep):
    return dict(
        alpha_mean=round(rep["alpha_mean"], 4),
        frac_easy=round(rep["frac_easy"], 3),
        frac_hard=round(rep["frac_hard"], 3),
        speedup_easy=round(rep["speedup_easy"], 3),
        speedup_hard=round(rep["speedup_hard"], 3),
        speedup_all=round(rep["speedup_all"], 3))


def run_diffusion(args, model):
    """The diffusion serving benchmark (sequential vs lanes, devices,
    CFG pairs, draft depths, schedulers). Returns the artifact rows."""
    cfg, dcfg, params = model
    device_counts = sorted({int(d) for d in args.devices.split(",")})
    guided = args.guidance_scale > 0
    gs = args.guidance_scale if guided else None
    streams = 2 if guided else 1
    scfg = SpeCaConfig(taylor_order=2, max_draft=8, tau0=args.tau0,
                       beta=0.9)

    def make_engine(D: int, *, guidance: bool = guided,
                    depth: int = 1) -> SpeCaEngine:
        return SpeCaEngine(cfg, params, dcfg, scfg,
                           accept_mode=args.accept_mode,
                           guidance=guidance, max_draft_depth=depth,
                           mesh=make_lane_mesh(D) if D > 1 else None)

    cond0 = {"labels": jnp.asarray([0])}
    reqs = make_requests(cfg, args.requests, guidance_scale=gs)
    lane_cap = min(args.lanes, streams * args.requests)
    engine = make_engine(1)
    # warm both paths so compile time stays out of the measurement
    engine.warmup(cond0, lanes=streams)
    engine.warmup(cond0, lanes=lane_cap)
    seq_results, seq_wall = bench(engine, reqs, lanes=streams)

    # one lane-scheduler run per device count (D=1: plain engine; D>1:
    # the lane axis sharded over a D-device ('data',) mesh). The row is
    # labeled with the EFFECTIVE lane width — a mesh engine rounds the
    # width up to a multiple of D, so requesting --lanes 2 on D=4 serves
    # 4 lanes; hiding that would let a pure width gain masquerade as
    # device scaling in the per-device-count column.
    lane_runs = []
    for D in device_counts:
        eng = engine if D == 1 else make_engine(D)
        if D > 1:
            eng.warmup(cond0, lanes=lane_cap)
        W_eff = eng.lane_width(args.lanes, len(reqs))
        results, wall = bench(eng, reqs, lanes=args.lanes)
        lane_runs.append((D, W_eff, results, wall))

    # split baseline (guided only): the same guided work as 2×requests
    # independent unguided lanes — cond and uncond streams decoupled, two
    # verify decisions where the paired engine takes one
    split_run = None
    if guided:
        split_engine = make_engine(1, guidance=False)
        split_reqs = split_requests(cfg, reqs)
        split_engine.warmup(cond0, lanes=min(args.lanes, len(split_reqs)))
        split_results, split_wall = bench(split_engine, split_reqs,
                                          lanes=args.lanes)
        split_run = (split_engine.lane_width(args.lanes, len(split_reqs)),
                     split_results, split_wall)

    n_tok = (dcfg.latent_size // cfg.patch_size) ** 2 \
        * max(dcfg.num_frames, 1)
    fwd = forward_flops(cfg, n_tok)
    seq_mode = f"batch=1{',paired' if guided else ''}"
    runs = [(seq_mode, 1, streams, seq_results, seq_wall, streams * fwd)] \
        + [(f"lanes={W_eff},D={D}{',paired' if guided else ''}", D, W_eff,
            results, wall, streams * fwd)
           for D, W_eff, results, wall in lane_runs]
    if split_run is not None:
        W_eff, split_results, split_wall = split_run
        runs.append((f"lanes={W_eff},D=1,split", 1, W_eff, split_results,
                     split_wall, fwd))
    rows = []
    for mode, D, W_eff, results, wall, fwd_ref in runs:
        rep = allocation_report(results, fwd_ref)
        split = mode.endswith(",split")
        # the lane scheduler must serve identical per-request work at
        # every width and device count (guaranteed in per_sample mode;
        # batch mode couples lanes by design). The split row serves
        # different work by construction (independent stream decisions),
        # so its mismatch count is meaningless and reported as None.
        mismatches = None if split else \
            sum(a.accepts != b.accepts
                for a, b in zip(seq_results, results))
        # req_per_s counts USER requests: a split row's 2N stream
        # requests serve N user requests' work
        n_user = len(results) // (2 if split else 1)
        mean_ticks, hit = sched_stats(results)
        rows.append(_row(
            mode=mode, devices=D, lanes=W_eff,
            guidance=args.guidance_scale if guided else 0.0,
            requests=n_user,
            wall_s=round(wall, 2),
            req_per_s=round(n_user / wall, 3),
            draft_accept_rate=round(draft_accept_rate(results), 4),
            serving_speedup=round(seq_wall / wall, 3),
            trajectory_mismatches=mismatches,
            mean_completion_ticks=round(mean_ticks, 2),
            deadline_hit_rate=hit,
            **_rep_cols(rep)))

    # scheduler comparison (serving API v2): one row per admission
    # policy, same engine, same mixed-length deadline workload — the
    # deltas are pure admission-order policy (docs/serving.md)
    sched_names = [s for s in args.scheduler.split(",") if s]
    sched_rows = []
    if sched_names:
        # the comparison workload is unguided — guidance changes lane
        # occupancy, not admission order, and the guided rows above
        # already track the pairing win
        wl = deadline_workload(cfg, args.requests, args.steps, args.lanes)
        sched_engine = make_engine(1, guidance=False)
        sched_engine.warmup(cond0, lanes=args.lanes)
        for name in sched_names:
            t0 = time.time()
            results = sched_engine.serve_batched(wl, lanes=args.lanes,
                                                 scheduler=name)
            wall = time.time() - t0
            # the comparison workload is unguided regardless of
            # --guidance-scale: unguided step cost and guidance=0.0
            rep = allocation_report(results, fwd)
            mean_ticks, hit = sched_stats(results)
            row = _row(
                mode=f"sched={name}",
                lanes=sched_engine._width_for(
                    args.lanes, [sched_engine.resolve_policy(r)
                                 for r in wl]),
                scheduler=name,
                requests=len(wl),
                wall_s=round(wall, 2),
                req_per_s=round(len(wl) / wall, 3),
                draft_accept_rate=round(draft_accept_rate(results), 4),
                # the sequential baseline timed a different (all
                # full-length) workload — serving_speedup not comparable
                mean_completion_ticks=round(mean_ticks, 2),
                deadline_hit_rate=hit,
                **_rep_cols(rep))
            sched_rows.append(row)
            rows.append(row)

    # deep-speculation comparison (--draft-depth): per depth K one
    # full-workload row and one row serving only the EASY bucket
    # (requests at/above the median depth-1 acceptance rate — long
    # accept runs, where a K-step chain replaces K scheduler ticks).
    # All depth engines run at D=1 with per-request draft_depth
    # policies; accept rates are per DRAFTED step on every row.
    depths = sorted({int(d) for d in args.draft_depth.split(",") if d})
    depth_rows = []
    if depths and depths != [1]:
        alphas = sorted(r.alpha for r in seq_results)
        med = alphas[len(alphas) // 2]
        easy_ids = {r.request_id for r in seq_results if r.alpha >= med}
        for K in depths:
            deng = make_engine(1, depth=K)
            deng.warmup(cond0, lanes=lane_cap)
            easy_cap = min(args.lanes, streams * len(easy_ids))
            if easy_cap != lane_cap:
                deng.warmup(cond0, lanes=easy_cap)
            pol = RequestPolicy(draft_depth=K)
            dreqs = [dataclasses.replace(r, policy=pol) for r in reqs]
            for tag, subset in (
                    ("", dreqs),
                    (",easy", [r for r in dreqs
                               if r.request_id in easy_ids])):
                results, wall = bench(deng, subset, lanes=args.lanes)
                rep = allocation_report(results, streams * fwd)
                mean_ticks, hit = sched_stats(results)
                mismatches = None if tag else sum(
                    a.accepts != b.accepts
                    for a, b in zip(seq_results, results))
                row = _row(
                    mode=f"depth={K}{tag}",
                    lanes=deng.lane_width(args.lanes, len(subset)),
                    guidance=args.guidance_scale if guided else 0.0,
                    draft_depth=K,
                    requests=len(subset),
                    wall_s=round(wall, 2),
                    req_per_s=round(len(subset) / wall, 3),
                    draft_accept_rate=round(draft_accept_rate(results),
                                            4),
                    # the easy row serves half the workload — not
                    # comparable to the sequential full-workload wall
                    serving_speedup=None if tag
                    else round(seq_wall / wall, 3),
                    trajectory_mismatches=mismatches,
                    mean_completion_ticks=round(mean_ticks, 2),
                    deadline_hit_rate=hit,
                    **_rep_cols(rep))
                depth_rows.append(row)
                rows.append(row)

    for row in rows[1:]:
        if row["mode"].startswith(("sched=", "depth=")):
            continue
        line = (f"{row['mode']}: {row['serving_speedup']}x requests/s "
                f"vs {seq_mode}")
        if row["trajectory_mismatches"] is not None:
            line += (f", {row['trajectory_mismatches']} trajectory "
                     "mismatches")
        print(line)
    if depth_rows:
        by_depth_easy = {r["draft_depth"]: r for r in depth_rows
                         if r["mode"].endswith(",easy")}
        for r in depth_rows:
            print(f"{r['mode']}: {r['req_per_s']} req/s, "
                  f"accept/drafted {r['draft_accept_rate']}")
        if 1 in by_depth_easy:
            base = by_depth_easy[1]["req_per_s"]
            for K in sorted(by_depth_easy):
                if K == 1:
                    continue
                ratio = by_depth_easy[K]["req_per_s"] / max(base, 1e-9)
                print(f"depth={K} vs depth=1 easy-bucket requests/s: "
                      f"{ratio:.2f}x")
    if sched_rows:
        by_name = {r["scheduler"]: r for r in sched_rows}
        for r in sched_rows:
            hit = "n/a" if r["deadline_hit_rate"] is None \
                else f"{r['deadline_hit_rate']:.2f}"
            print(f"sched={r['scheduler']}: mean completion "
                  f"{r['mean_completion_ticks']} ticks, deadline hit "
                  f"rate {hit}")
        if "fifo" in by_name:
            f = by_name["fifo"]
            if "sjf" in by_name:
                print(f"sjf vs fifo mean completion ticks: "
                      f"{by_name['sjf']['mean_completion_ticks']} vs "
                      f"{f['mean_completion_ticks']}")
            if "edf" in by_name and f["deadline_hit_rate"] is not None:
                print(f"edf vs fifo deadline hit rate: "
                      f"{by_name['edf']['deadline_hit_rate']:.2f} vs "
                      f"{f['deadline_hit_rate']:.2f}")
    if guided and split_run is not None:
        # the split baseline always runs at D=1, so compare it against
        # the D=1 paired row specifically — with --devices 2,4 the first
        # lane row is a multi-device run and would conflate mesh scaling
        # with the one-decision-per-pair win
        paired = next((r for r in rows
                       if r["devices"] == 1 and r["mode"].endswith(
                           ",paired") and not r["mode"].startswith(
                           "batch=1")), None)
        split_row = next(r for r in rows if r["mode"].endswith(",split"))
        if paired is not None:
            ratio = paired["req_per_s"] / max(split_row["req_per_s"],
                                              1e-9)
            print(f"paired vs split (cond+uncond as independent lanes): "
                  f"{ratio:.2f}x requests/s")
    return rows


def run_forecasters(args, model):
    """Forecaster comparison (``--forecaster taylor,spectral``): one row
    per forecaster family serving the SAME diffusion workload on its own
    engine — the Taylor difference table vs the spectral frequency-band
    ring (docs/forecasters.md).  The tracked columns: per-drafted-step
    accept rate and total served GFLOPs, so the artifact shows what each
    extrapolation basis buys (or costs) at identical τ0/width."""
    cfg, dcfg, params = model
    scfg = SpeCaConfig(taylor_order=2, max_draft=8, tau0=args.tau0,
                       beta=0.9)
    names = [f for f in args.forecaster.split(",") if f]
    reqs = make_requests(cfg, args.requests)
    cond0 = {"labels": jnp.asarray([0])}
    rows = []
    n_tok = (dcfg.latent_size // cfg.patch_size) ** 2 \
        * max(dcfg.num_frames, 1)
    fwd = forward_flops(cfg, n_tok)
    for name in names:
        eng = SpeCaEngine(cfg, params, dcfg, scfg,
                          accept_mode=args.accept_mode, forecaster=name)
        eng.warmup(cond0, lanes=min(args.lanes, args.requests))
        results, wall = bench(eng, reqs, lanes=args.lanes)
        rep = allocation_report(results, fwd)
        mean_ticks, _ = sched_stats(results)
        rows.append(_row(
            mode=f"forecaster={name}", forecaster=name,
            lanes=eng.lane_width(args.lanes, len(reqs)),
            requests=len(reqs),
            wall_s=round(wall, 2),
            req_per_s=round(len(reqs) / wall, 3),
            draft_accept_rate=round(draft_accept_rate(results), 4),
            gflops=round(sum(r.flops for r in results) / 1e9, 3),
            mean_completion_ticks=round(mean_ticks, 2),
            **_rep_cols(rep)))
        print(f"forecaster={name}: accept/drafted "
              f"{rows[-1]['draft_accept_rate']}, "
              f"{rows[-1]['gflops']} GFLOPs, "
              f"{rows[-1]['req_per_s']} req/s")
    return rows


def run_decode(args, lm):
    """LLM decode lanes: one engine, two request batches — speculative
    (τ0 = --decode-tau0) and reject-always (τ0 = 0, exact greedy
    decoding) — served at identical lane widths. The tracked win:
    accept rate > 0 AND fewer total FLOPs than reject-always for the
    same emitted tokens-per-request."""
    lm_cfg, lm_params = lm
    wl = DecodeWorkload(lm_cfg, lm_params,
                        SpeCaConfig(tau0=args.decode_tau0),
                        max_new_tokens=args.gen_len,
                        max_seq_len=args.prompt_len + args.gen_len)
    eng = SpeCaEngine(workloads={"decode": wl}, lanes=args.lanes)
    warm = decode_requests(lm_cfg, 1, args.prompt_len,
                           tau0=args.decode_tau0, offset=90_000)[0]
    eng.warmup(warm.cond, lanes=min(args.lanes, args.requests),
               workload="decode")

    rows, flops = [], {}
    for mode, tau0 in (("decode", args.decode_tau0),
                       ("decode,reject", 0.0)):
        reqs = decode_requests(lm_cfg, args.requests, args.prompt_len,
                               tau0=tau0)
        t0 = time.time()
        results = eng.serve_batched(reqs, lanes=args.lanes)
        wall = time.time() - t0
        rep = allocation_report(results, wl.full_flops)
        flops[mode] = sum(r.flops for r in results)
        mean_ticks, _ = sched_stats(results)
        rows.append(_row(
            mode=mode, workload="decode",
            lanes=eng.lane_width(args.lanes, len(reqs)),
            requests=len(reqs),
            wall_s=round(wall, 2),
            req_per_s=round(len(reqs) / wall, 3),
            tok_per_s=round(len(reqs) * args.gen_len / wall, 1),
            draft_accept_rate=round(draft_accept_rate(results), 4),
            mean_completion_ticks=round(mean_ticks, 2),
            **_rep_cols(rep)))
    spec_row = rows[0]
    ratio = flops["decode,reject"] / max(flops["decode"], 1e-9)
    print(f"decode: accept rate {spec_row['alpha_mean']}, "
          f"{flops['decode'] / 1e9:.3f} GFLOPs vs "
          f"{flops['decode,reject'] / 1e9:.3f} reject-always "
          f"({ratio:.2f}x fewer FLOPs)")
    return rows


def run_mixed(args, model, lm):
    """Diffusion + decode traffic interleaved through ONE engine (one
    scheduler, per-workload sessions). One row per workload with that
    side's accept rate; ``wall_s`` is the SHARED wall of the whole
    mixed batch, so the per-row req/s reflect concurrent service."""
    cfg, dcfg, params = model
    lm_cfg, lm_params = lm
    scfg = SpeCaConfig(taylor_order=2, max_draft=8, tau0=args.tau0,
                       beta=0.9)
    wl = DecodeWorkload(lm_cfg, lm_params,
                        SpeCaConfig(tau0=args.decode_tau0),
                        max_new_tokens=args.gen_len,
                        max_seq_len=args.prompt_len + args.gen_len)
    eng = SpeCaEngine(cfg, params, dcfg, scfg,
                      workloads={"decode": wl}, lanes=args.lanes)
    n = args.requests
    dreqs = make_requests(cfg, n)
    treqs = decode_requests(lm_cfg, n, args.prompt_len,
                            tau0=args.decode_tau0, offset=1000)
    # warm both per-tag slot programs at the widths the timed batch will
    # use (same per-tag request counts → same _width_for result); the
    # warm requests run truncated 2-step schedules — compilation depends
    # on width and tag, not schedule length
    k = min(args.lanes, n)
    warm = [dataclasses.replace(r, request_id=-1 - i,
                                policy=RequestPolicy(max_steps=2))
            for i, r in enumerate(dreqs[:k])] \
        + decode_requests(lm_cfg, k, args.prompt_len,
                          tau0=args.decode_tau0, offset=91_000,
                          max_steps=2)
    eng.serve_batched(warm, lanes=args.lanes)

    reqs = [r for pair in zip(dreqs, treqs) for r in pair]
    t0 = time.time()
    results = eng.serve_batched(reqs, lanes=args.lanes)
    wall = time.time() - t0
    n_tok = (dcfg.latent_size // cfg.patch_size) ** 2 \
        * max(dcfg.num_frames, 1)
    fwd_ref = {"diffusion": forward_flops(cfg, n_tok),
               "decode": wl.full_flops}
    rows = []
    for tag in ("diffusion", "decode"):
        rs = [r for r in results if r.workload == tag]
        rep = allocation_report(rs, fwd_ref[tag])
        mean_ticks, _ = sched_stats(rs)
        rows.append(_row(
            mode=f"mixed,{tag}", workload=tag,
            lanes=eng.lane_width(args.lanes, len(rs)),
            requests=len(rs),
            wall_s=round(wall, 2),
            req_per_s=round(len(rs) / wall, 3),
            tok_per_s=round(len(rs) * args.gen_len / wall, 1)
            if tag == "decode" else None,
            draft_accept_rate=round(draft_accept_rate(rs), 4),
            mean_completion_ticks=round(mean_ticks, 2),
            **_rep_cols(rep)))
    print(f"mixed: diffusion accept {rows[0]['alpha_mean']}, "
          f"decode accept {rows[1]['alpha_mean']} — "
          f"{len(results)} requests through one engine in {wall:.2f}s")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="dit", choices=["dit", "flux"])
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--tau0", type=float, default=0.4)
    ap.add_argument("--accept-mode", default="per_sample",
                    choices=["per_sample", "batch"])
    ap.add_argument("--workload", default="diffusion",
                    help="comma list of traffic kinds to serve: "
                         "diffusion, decode (LLM self-speculative "
                         "lanes, spec vs reject-always rows), mixed "
                         "(both kinds through one engine)")
    ap.add_argument("--lm-arch", default="mamba2-130m",
                    help="registry arch of the decode-workload LM")
    ap.add_argument("--decode-tau0", type=float, default=5.0,
                    help="verification threshold of the decode rows "
                         "(the reject-always baseline always runs τ0=0)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16,
                    help="new tokens per decode request")
    ap.add_argument("--guidance-scale", type=float, default=0.0,
                    help=">0: classifier-free-guidance serving (paired "
                         "cond/uncond lanes) plus a split baseline row "
                         "serving the streams as independent requests")
    ap.add_argument("--forecaster", default="",
                    help="comma list of forecaster families to compare "
                         "on the diffusion workload, e.g. taylor,"
                         "spectral (adds one row per forecaster with "
                         "accept-rate and GFLOPs columns)")
    ap.add_argument("--draft-depth", default="1",
                    help="comma list of draft horizons, e.g. 1,3: adds a "
                         "full-workload row and an easy-bucket row per "
                         "depth K>0 beyond the base depth-1 rows")
    ap.add_argument("--devices", default="1",
                    help="comma list of lane-shard device counts, e.g. "
                         "1,2,4 (needs that many visible devices)")
    ap.add_argument("--scheduler", default="",
                    help="comma list of admission schedulers to compare "
                         "on a mixed-length deadline workload, e.g. "
                         "fifo,sjf,edf (adds one row per scheduler)")
    args = ap.parse_args()
    wls = []
    for w in args.workload.split(","):
        w = w.strip()
        if w and w not in wls:
            wls.append(w)
    unknown = set(wls) - {"diffusion", "decode", "mixed"}
    if unknown or not wls:
        ap.error(f"--workload must name diffusion/decode/mixed, got "
                 f"{args.workload!r}")
    guided = args.guidance_scale > 0

    model = None
    if "diffusion" in wls or "mixed" in wls:
        cfg, dcfg, params = get_model(args.model)
        dcfg = dataclasses.replace(dcfg, num_inference_steps=args.steps)
        model = (cfg, dcfg, params)
    lm = get_lm_model(args.lm_arch) \
        if "decode" in wls or "mixed" in wls else None

    rows = []
    if "diffusion" in wls:
        rows += run_diffusion(args, model)
        if args.forecaster:
            rows += run_forecasters(args, model)
    if "decode" in wls:
        rows += run_decode(args, lm)
    if "mixed" in wls:
        rows += run_mixed(args, model, lm)

    print_table(f"serve_throughput ({args.model}, "
                f"accept_mode={args.accept_mode}"
                + (f", guidance={args.guidance_scale}" if guided else "")
                + (f", workload={'+'.join(wls)}"
                   if wls != ["diffusion"] else "")
                + ")", rows)
    suffix = "_cfg" if guided and "diffusion" in wls else ""
    if wls != ["diffusion"]:
        suffix += "".join(f"_{w}" for w in wls if w != "diffusion")
    path = write_result(f"serve_throughput_{args.model}{suffix}", rows)
    print(f"wrote {path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

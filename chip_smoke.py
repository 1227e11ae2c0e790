#!/usr/bin/env python3
"""Chip smoke test: the SpeCa serving engine end to end on a TPU.

    python chip_smoke.py [--seed N]    one chip: phases A and B
    python chip_smoke.py --chips 4     four chips: the phase A requests on
                                       a lane-sharded engine against the
                                       same requests on one device

Phase A serves class-conditional DiT-XL/2 at its published width (28
layers, d 1152, 16 heads, bf16, 256² images = 32² latents, 256 tokens)
through ``SpeCaEngine.submit``/``tick``/``result`` at 4 lanes. Requests
at τ0 = 0 must reject every draft and equal the full-compute sampler;
requests at a large τ0 must accept drafts. Phase B serves mamba2-130m
decode lanes at draft depth 2 (chain forecast and rollback) and compares
the τ0 = 0 tokens with plain greedy decoding.

Weights are random, drawn from ``--seed``. DiT's AdaLN-Zero gates and its
head are zero at init, which makes every block the identity and the
output zero; the script refills those leaves with seeded normals at the
init's own scale so blocks and outputs are non-trivial.

The script runs only on a TPU: anywhere else it exits non-zero and prints
no result. The seconds it prints are set-up time (compilation), not
performance. Its last line of standard output is one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

DIFFUSION_STEPS = 16
DIFFUSION_REQUESTS = 6
DIFFUSION_LANES = 4
MESH_LANES = 8
PROMPT_LEN = 32
NEW_TOKENS = 16
DRAFT_DEPTH = 2
# a τ0 no finite verify error reaches: every draft the schedule allows
# passes, so the accept branch runs on every drafted step
BIG_TAU = 1e3
# rel-L2 of a served latent against the full-compute sampler (or of a
# sharded against a one-device engine): both run the same bf16 model,
# which rounds at 2^-8 relative per op; a few ulps through 28 layers and
# 16 steps stay well inside this
REL_L2_BOUND = 5e-2
# two bf16 ulps (2^-7 relative each) at the top logit: a top-2 gap this
# small is a tie the two decode paths may break either way
TIE_REL = 2.0 ** -6


class SmokeFailure(AssertionError):
    """A phase ran but its outputs broke a check."""


def require_tpu(chips: int):
    """The first device JAX reports, when it is a TPU and there are
    ``chips`` of them; otherwise exit non-zero naming what was found."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX sees "
                 f"{len(devices)} TPU device(s)")
    return dev, len(devices)


def assert_mosaic(compiled, name: str) -> None:
    """The compiled program holds a Mosaic kernel (not the jnp
    interpreter's rendering of one)."""
    if "tpu_custom_call" not in compiled.as_text():
        raise SmokeFailure(f"{name}: no tpu_custom_call in the compiled "
                           "program — the Pallas kernels did not compile")


def compile_setup(name: str, jitted, *args):
    """AOT-compile ``jitted`` on ``args`` twice — cold, then after the
    in-memory caches are dropped (a persistent-cache hit) — and print
    both as set-up seconds. Returns the first executable."""
    import jax
    t0 = time.perf_counter()
    compiled = jitted.lower(*args).compile()
    cold = time.perf_counter() - t0
    jax.clear_caches()
    t0 = time.perf_counter()
    jitted.lower(*args).compile()
    warm = time.perf_counter() - t0
    print(f"setup {name}: compile cold {cold:.2f} s, warm {warm:.2f} s")
    return compiled


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------------------------------
# Phase A: diffusion
# ---------------------------------------------------------------------------

def dit_params(cfg, seed: int):
    """``init_params`` weights with the zero-initialised AdaLN-Zero and
    head leaves refilled by seeded normals at the init's scale
    (1/√fan-in, fan-in = the leaf's first axis, or its length for a
    vector)."""
    import jax
    import jax.numpy as jnp
    from repro.layers import model as M

    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 1)

    def refill(k, leaf, stacked: bool):
        shape = leaf.shape[1:] if stacked else leaf.shape
        scale = 1.0 / math.sqrt(shape[0])
        return (jax.random.normal(k, leaf.shape, jnp.float32)
                * scale).astype(leaf.dtype)

    blocks, head = dict(params["blocks"]), dict(params["head"])
    for i, name in enumerate(("mod_w", "mod_b")):
        blocks[name] = refill(jax.random.fold_in(key, i), blocks[name], True)
    for i, name in enumerate(("w", "b", "mod_w", "mod_b")):
        head[name] = refill(jax.random.fold_in(key, 10 + i), head[name],
                            False)
    return {**params, "blocks": blocks, "head": head}


def diffusion_requests(cfg, seed: int, n: int):
    """``n`` class-conditional requests; even ones at τ0 = 0, odd ones at
    ``BIG_TAU``."""
    import jax.numpy as jnp
    from repro.serving import Request, RequestPolicy
    return [Request(request_id=i, seed=seed * 1000 + i,
                    cond={"labels": jnp.asarray([(7 * i + seed)
                                                 % cfg.num_classes])},
                    policy=RequestPolicy(tau0=0.0 if i % 2 == 0
                                         else BIG_TAU))
            for i in range(n)]


def diffusion_engine(cfg, dcfg, params, lanes: int, mesh=None):
    """A lifecycle-API engine with its kernel paths selected explicitly:
    fused verification and the Pallas table kernels."""
    from repro.configs import SpeCaConfig
    from repro.serving import SpeCaEngine
    return SpeCaEngine(cfg, params, dcfg, SpeCaConfig(),
                       verify_backend="fused", lanes=lanes, mesh=mesh)


def lane_step_setup(engine, name: str, workload: str, cond) -> None:
    """Compile the engine's own lane-step program for its session and
    check that the Pallas kernels are in it."""
    from repro.core import lane_step as LS

    engine.start(workload=workload)
    sess = engine._sessions[workload]          # the program submit uses
    wl = engine.workloads[workload]
    state = LS.init_workload_state(
        wl, sess.W, cond if wl.cond_in_state else {},
        guidance="mixed" if sess.paired else False,
        forecaster=engine.forecaster, controller=engine.controller,
        mesh=engine.mesh)
    step = sess.step_fn                        # partial(jitted, params)
    assert_mosaic(compile_setup(name, step.func, *step.args, state), name)


def serve(engine, requests):
    """submit every request, tick until all are done, collect results."""
    tickets = [engine.submit(r) for r in requests]
    while engine.pending() or engine.in_flight():
        engine.tick()
    return [engine.result(t) for t in tickets]


def report(results, tag: str) -> None:
    for r in results:
        print(f"{tag} request {r.request_id}: num_full={r.num_full} "
              f"num_spec={r.num_spec} num_drafted={r.num_drafted}")


def phase_diffusion(cfg, dcfg, params, *, seed: int,
                    lanes: int = DIFFUSION_LANES,
                    n_requests: int = DIFFUSION_REQUESTS) -> None:
    """Serve the phase A requests and check them against the
    full-compute sampler; raises ``SmokeFailure`` listing every broken
    check after printing all numbers."""
    import jax
    import numpy as np
    from repro.diffusion.pipeline import sample_full

    requests = diffusion_requests(cfg, seed, n_requests)
    engine = diffusion_engine(cfg, dcfg, params, lanes)
    lane_step_setup(engine, "diffusion lane step", "diffusion",
                    requests[0].cond)

    def reference(p, key, labels):
        return sample_full(cfg, p, dcfg, key, {"labels": labels}, 1)[0]

    ref = jax.jit(reference)
    compile_setup("full-compute sampler", ref, params,
                  jax.random.PRNGKey(0), requests[0].cond["labels"])

    results = serve(engine, requests)
    report(results, "diffusion")
    problems = []
    for req, res in zip(requests, results):
        x = np.asarray(res.sample)
        if not np.isfinite(x).all():
            problems.append(f"request {req.request_id}: non-finite latent")
        if req.policy.tau0 == 0.0:
            want = ref(params, jax.random.PRNGKey(req.seed),
                       req.cond["labels"])
            err = rel_l2(x, want)
            print(f"diffusion request {req.request_id} (tau0=0): rel-L2 "
                  f"vs full-compute sampler {err:.3e} "
                  f"(bound {REL_L2_BOUND:g})")
            if res.num_spec != 0:
                problems.append(f"request {req.request_id}: tau0=0 but "
                                f"num_spec={res.num_spec}")
            if not err <= REL_L2_BOUND:
                problems.append(f"request {req.request_id}: rel-L2 "
                                f"{err:.3e} > {REL_L2_BOUND:g}")
        elif res.num_spec <= 0:
            problems.append(f"request {req.request_id}: tau0={BIG_TAU:g} "
                            "but no draft was accepted")
    if problems:
        raise SmokeFailure("phase A: " + "; ".join(problems))
    print("phase A (diffusion): passed")


# ---------------------------------------------------------------------------
# Phase B: decode
# ---------------------------------------------------------------------------

def greedy_decode(cfg, params, prompt, new_tokens: int):
    """Plain prefill + greedy decode (the engine-free reference). Returns
    the emitted tokens and the logits row each was the argmax of."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.layers import model as M
    from repro.training import lm as T

    P = prompt.shape[1]
    logits, cache = jax.jit(partial(T.prefill_step, cfg))(
        params, {"tokens": prompt})
    dec = M.init_cache(cfg, 1, P + new_tokens)
    for k in ("ssm_state", "conv_state"):
        if k in dec:
            dec[k] = cache[k]
    if "k" in dec:
        dec["k"] = dec["k"].at[:, :, :P].set(cache["k"])
        dec["v"] = dec["v"].at[:, :, :P].set(cache["v"])
    step = jax.jit(partial(T.serve_step, cfg))
    tok = jnp.argmax(logits, axis=-1)
    tokens, rows = [], []
    for pos in range(P, P + new_tokens):
        logits, dec = step(params, tok, dec, pos)
        tok = jnp.argmax(logits, axis=-1)
        tokens.append(int(tok[0, 0]))
        rows.append(np.asarray(logits[0, 0], np.float32))
    return tokens, rows


def first_divergence(got, want, rows):
    """None when ``got`` equals ``want``; else (position, top-2 gap at
    that position of the reference logits, whether it is a tie)."""
    import numpy as np
    for j, (g, w) in enumerate(zip(got, want)):
        if g == w:
            continue
        row = rows[j]
        top2 = np.argsort(row)[-2:][::-1]
        gap = float(row[top2[0]] - row[top2[1]])
        tie = g in top2 and gap <= TIE_REL * abs(float(row[top2[0]]))
        return j, gap, tie
    if len(got) != len(want):
        return min(len(got), len(want)), float("inf"), False
    return None


def phase_decode(cfg, params, *, seed: int, prompt_len: int = PROMPT_LEN,
                 new_tokens: int = NEW_TOKENS) -> None:
    """Serve two decode requests at draft depth 2 (τ0 = 0 and
    ``BIG_TAU``) and check the τ0 = 0 tokens against greedy decoding."""
    import jax
    from repro.configs import SpeCaConfig
    from repro.serving import (DecodeWorkload, Request, RequestPolicy,
                               SpeCaEngine)

    prompts = [jax.random.randint(jax.random.PRNGKey(seed * 1000 + i),
                                  (1, prompt_len), 0, cfg.vocab_size)
               for i in range(2)]
    wl = DecodeWorkload(cfg, params, SpeCaConfig(tau0=0.0),
                        max_new_tokens=new_tokens,
                        max_seq_len=prompt_len + new_tokens)
    engine = SpeCaEngine(workloads={"decode": wl}, lanes=2,
                         max_draft_depth=DRAFT_DEPTH, verify_backend="fused")
    lane_step_setup(engine, "decode lane step", "decode", {})
    requests = [Request(request_id=i, cond={"tokens": prompts[i]},
                        policy=RequestPolicy(workload="decode",
                                             draft_depth=DRAFT_DEPTH,
                                             tau0=tau))
                for i, tau in enumerate((0.0, BIG_TAU))]
    results = serve(engine, requests)
    report(results, "decode")
    problems = []
    for req, res in zip(requests, results):
        got = [int(t) for t in res.sample]
        want, rows = greedy_decode(cfg, params, prompts[req.request_id],
                                   new_tokens)
        match = sum(g == w for g, w in zip(got, want)) / len(want)
        tau = req.policy.tau0
        print(f"decode request {req.request_id} (tau0={tau:g}): "
              f"token match vs greedy {match:.3f}")
        if tau != 0.0:
            if res.num_spec <= 0:
                problems.append(f"request {req.request_id}: tau0={tau:g} "
                                "but no draft was accepted")
            continue
        if res.num_spec != 0:
            problems.append(f"request {req.request_id}: tau0=0 but "
                            f"num_spec={res.num_spec}")
        div = first_divergence(got, want, rows)
        if div is not None:
            j, gap, tie = div
            print(f"decode request {req.request_id}: first divergence at "
                  f"position {j}, reference top-2 logit gap {gap:.4g} "
                  f"({'a bf16 tie' if tie else 'not a tie'})")
            if not tie:
                problems.append(f"request {req.request_id}: tokens differ "
                                f"from greedy at position {j}")
    if problems:
        raise SmokeFailure("phase B: " + "; ".join(problems))
    print("phase B (decode): passed")


# ---------------------------------------------------------------------------
# --chips 4: lane-sharded serving against one device
# ---------------------------------------------------------------------------

def phase_mesh(cfg, dcfg, params, *, seed: int, devices: int,
               lanes: int = MESH_LANES,
               n_requests: int = DIFFUSION_REQUESTS) -> None:
    """The phase A requests on a ``devices``-way lane mesh and on one
    device: per-request counters equal, latents within the bound."""
    from repro.launch.mesh import make_lane_mesh

    requests = diffusion_requests(cfg, seed, n_requests)
    sharded = diffusion_engine(cfg, dcfg, params, lanes,
                               mesh=make_lane_mesh(devices))
    lane_step_setup(sharded, f"diffusion lane step on {devices} devices",
                    "diffusion", requests[0].cond)
    got = serve(sharded, requests)
    want = serve(diffusion_engine(cfg, dcfg, params, lanes), requests)
    report(got, f"{devices}-device")
    report(want, "1-device")
    problems = []
    for g, w in zip(got, want):
        counters = [(g.num_full, g.num_spec, g.num_drafted, g.accepts),
                    (w.num_full, w.num_spec, w.num_drafted, w.accepts)]
        err = rel_l2(g.sample, w.sample)
        print(f"request {g.request_id}: counters "
              f"{'equal' if counters[0] == counters[1] else 'DIFFER'}, "
              f"latent rel-L2 {err:.3e} (bound {REL_L2_BOUND:g})")
        if counters[0] != counters[1]:
            problems.append(f"request {g.request_id}: counters differ")
        if not err <= REL_L2_BOUND:
            problems.append(f"request {g.request_id}: rel-L2 {err:.3e}")
    if problems:
        raise SmokeFailure("mesh phase: " + "; ".join(problems))
    print(f"mesh phase ({devices} devices vs 1): passed")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the lane-sharded comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev, count = require_tpu(args.chips)
    print(f"device_kind: {dev.device_kind} (x{count})")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    # the Pallas table kernels, whatever the environment selects
    os.environ["REPRO_TABLE_BACKEND"] = "kernel"
    from repro.configs import DiffusionConfig, get_config
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    dit = get_config("dit-xl2")
    dcfg = DiffusionConfig(num_inference_steps=DIFFUSION_STEPS,
                           latent_size=32)
    params = dit_params(dit, args.seed)
    if args.chips == 4:
        phase_mesh(dit, dcfg, params, seed=args.seed, devices=4)
    else:
        phase_diffusion(dit, dcfg, params, seed=args.seed)
        del params
        from repro.layers import model as M
        lm = get_config("mamba2-130m")
        import jax
        phase_decode(lm, M.init_params(lm, jax.random.PRNGKey(args.seed)),
                     seed=args.seed)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))


if __name__ == "__main__":
    main()

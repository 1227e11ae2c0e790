"""Serving launcher: SpeCa diffusion serving or LM decode, reduced scale.

Usage:
  python -m repro.launch.serve --mode diffusion --requests 6 --lanes 4
  python -m repro.launch.serve --mode diffusion --requests 8 --lanes 8 \
      --mesh 2
  python -m repro.launch.serve --mode diffusion --requests 6 --lanes 4 \
      --guidance-scale 4.0
  python -m repro.launch.serve --mode diffusion --requests 8 --lanes 4 \
      --mixed --scheduler sjf

``--lanes N`` (N>1) serves through the per-lane adaptive batched scheduler
(docs/serving.md); ``--lanes 1`` keeps the sequential batch=1 loop.
``--mesh D`` shards the lane axis over a D-device ``('data',)`` mesh (one
engine, W×D lanes); on a CPU host with fewer than D devices the launcher
forces D host devices via XLA_FLAGS before the first jax import.
``--guidance-scale S`` (S>0) serves under classifier-free guidance: each
request occupies a cond/uncond lane pair with one verify decision per
pair (docs/cfg.md); the lane width rounds to a multiple of 2×D.
``--mixed`` serves a heterogeneous API-v2 workload on ONE engine —
alternating guided (the ``--guidance-scale`` value, default 4.0) and
unguided requests with distinct per-request τ via ``RequestPolicy``
(slot-width scheduling, docs/serving.md). ``--scheduler`` picks the
admission policy (fifo/sjf/edf).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from functools import partial


def serve_diffusion(args) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import (DiffusionConfig, SpeCaConfig, TrainConfig,
                               get_config, reduced)
    from repro.core.complexity import forward_flops
    from repro.launch.mesh import make_lane_mesh
    from repro.serving import (Request, RequestPolicy, SpeCaEngine,
                               allocation_report)
    from repro.training.diffusion_trainer import train_diffusion

    cfg = dataclasses.replace(reduced(get_config("dit-xl2")), num_layers=2,
                              d_model=128, d_ff=256, num_heads=4,
                              num_kv_heads=4, num_classes=8)
    dcfg = DiffusionConfig(num_inference_steps=args.steps, latent_size=8,
                           schedule="cosine")
    out = train_diffusion(cfg, dcfg,
                          TrainConfig(global_batch=16, steps=120, lr=2e-3),
                          verbose=False)
    scfg = SpeCaConfig(taylor_order=2, max_draft=8, tau0=args.tau0, beta=0.9)
    mesh = make_lane_mesh(args.mesh) if args.mesh > 1 else None
    guided = args.guidance_scale > 0
    engine = SpeCaEngine(cfg, out["state"]["params"], dcfg, scfg,
                         accept_mode=args.accept_mode,
                         guidance=guided and not args.mixed,
                         mesh=mesh, scheduler=args.scheduler)
    gs = args.guidance_scale if guided else None
    labels = lambda i: {"labels": jnp.asarray([i % cfg.num_classes])}  # noqa: E731
    if args.mixed:
        # heterogeneous API-v2 traffic on ONE engine: alternating guided
        # pairs (distinct scales) and unguided lanes (distinct τ)
        mgs = gs if guided else 4.0
        reqs = [Request(request_id=i, cond=labels(i), seed=i,
                        policy=RequestPolicy(guidance_scale=mgs + i % 3)
                        if i % 2 == 0 else
                        RequestPolicy(tau0=args.tau0 * (0.5 + i % 3)))
                for i in range(args.requests)]
        streams = 2
    else:
        reqs = [Request(request_id=i, cond=labels(i), seed=i,
                        guidance_scale=gs)
                for i in range(args.requests)]
        streams = 2 if guided else 1
    # warm at the served lane width AND program (mixed workloads compile
    # the slot-width step) so compile time stays out of req/s
    engine.warmup({"labels": jnp.asarray([0])},
                  lanes=min(args.lanes, streams * args.requests),
                  mixed=args.mixed)
    t0 = time.time()
    results = engine.serve(reqs, lanes=args.lanes)
    wall = time.time() - t0
    for r in results:
        print(f"req {r.request_id}: full={r.num_full} spec={r.num_spec} "
              f"alpha={r.alpha:.2f} done@tick {r.finish_tick}")
    mode = f"{args.lanes} lanes" if args.lanes > 1 else "batch=1"
    if args.mixed:
        mode += ", mixed guided+unguided slots"
    elif guided:
        mode += f", cfg pairs s={args.guidance_scale}"
    if args.scheduler != "fifo":
        mode += f", {args.scheduler}"
    if mesh is not None:
        mode += f" x {args.mesh} devices"
    print(f"served {len(reqs)} requests in {wall:.1f}s "
          f"({len(reqs)/wall:.2f} req/s, {mode})")
    n_tok = (dcfg.latent_size // cfg.patch_size) ** 2
    fwd = forward_flops(cfg, n_tok)
    if args.mixed:
        # the reference step cost differs per slot shape (a guided step
        # is two denoiser rows), so report the two populations apart
        gsub = [r for r, q in zip(results, reqs)
                if engine.resolve_policy(q).guided]
        usub = [r for r, q in zip(results, reqs)
                if not engine.resolve_policy(q).guided]
        print("guided:", allocation_report(gsub, 2 * fwd))
        print("unguided:", allocation_report(usub, fwd))
    else:
        print(allocation_report(results, streams * fwd))


def serve_lm(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.layers import model as M
    from repro.optim.adamw import AdamWConfig
    from repro.training import lm as T

    cfg = reduced(get_config(args.arch))
    state = T.make_train_state(cfg, jax.random.PRNGKey(0), AdamWConfig())
    params = state["params"]
    key = jax.random.PRNGKey(1)
    B = args.batch
    if cfg.arch_type == "audio":
        prompt = jax.random.randint(key, (B, cfg.num_codebooks, 16), 0,
                                    cfg.vocab_size)
    else:
        prompt = jax.random.randint(key, (B, 16), 0, cfg.vocab_size)
    logits, cache = jax.jit(partial(T.prefill_step, cfg))(
        params, {"tokens": prompt})
    max_len = 16 + args.gen
    dec = M.init_cache(cfg, B, max_len)
    if "k" in dec:
        dec["k"] = dec["k"].at[:, :, :16].set(cache["k"])
        dec["v"] = dec["v"].at[:, :, :16].set(cache["v"])
    if "ssm_state" in dec:
        dec["ssm_state"] = cache["ssm_state"]
        dec["conv_state"] = cache["conv_state"]
    serve = jax.jit(partial(T.serve_step, cfg))
    tok = jnp.argmax(logits[..., :cfg.vocab_size], axis=-1)
    if cfg.arch_type == "audio":
        tok = tok.reshape(B, cfg.num_codebooks, 1)
    t0 = time.time()
    for pos in range(16, max_len):
        logits, dec = serve(params, tok, dec, pos)
        tok = jnp.argmax(logits[..., :cfg.vocab_size], axis=-1)
        if cfg.arch_type == "audio":
            tok = tok.reshape(B, cfg.num_codebooks, 1)
    dt = time.time() - t0
    print(f"{args.arch}: decoded {args.gen} tokens × {B} seqs "
          f"in {dt:.2f}s ({args.gen*B/dt:.1f} tok/s on CPU)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["diffusion", "lm"],
                    default="diffusion")
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--lanes", type=int, default=4,
                    help="serving lane width; 1 = sequential batch=1 loop")
    ap.add_argument("--mesh", type=int, default=1,
                    help="lane-shard the engine over this many devices "
                         "(('data',) mesh); on CPU the launcher forces "
                         "that many host devices via XLA_FLAGS")
    ap.add_argument("--accept-mode", default="per_sample",
                    choices=["per_sample", "batch"])
    ap.add_argument("--guidance-scale", type=float, default=0.0,
                    help="classifier-free guidance scale; >0 serves each "
                         "request as a cond/uncond lane pair with one "
                         "verify decision per pair (docs/cfg.md)")
    ap.add_argument("--mixed", action="store_true",
                    help="serve a heterogeneous per-request-policy "
                         "workload (alternating guided pairs and "
                         "unguided lanes with distinct τ) on one engine")
    ap.add_argument("--scheduler", default="fifo",
                    choices=["fifo", "sjf", "edf"],
                    help="admission-queue policy (docs/serving.md)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--tau0", type=float, default=0.4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    # must land before the first jax import (jax is imported inside the
    # serve functions for exactly this reason)
    from repro.launch.mesh import force_host_device_count
    force_host_device_count(args.mesh)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "diffusion":
        serve_diffusion(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()

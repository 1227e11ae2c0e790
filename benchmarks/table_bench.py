"""Difference-table hot path: per-step draft latency + table bytes moved.

The SpeCa speedup claim needs the draft path to be nearly free (paper
§3.5: verification overhead 1.67%–3.5%), so the TaylorSeer table
evaluation/refresh must stay memory-lean. This benchmark compares the two
table backends on the serving layout (m+1, L, 2, B, T, D):

  * ``jnp``   — the staged oracle: ``astype(f32)`` whole-table copy +
    einsum for predict; recursive rows + ``stack`` + ``where`` (three
    table-sized materialisations) for the masked refresh.
  * ``kernel`` — the fused lane-masked Pallas kernels: one pass over the
    table, weights/mask applied in registers, no whole-table temporary.

Reported per step and per backend: measured wall latency and the analytic
HBM bytes moved (from the op semantics — what a roofline would charge).
NOTE on CPU this container executes the kernels in *interpret* mode
(correctness oracle — the measured kernel wall time is NOT indicative);
the bytes-moved column is backend-intrinsic and is the before/after
metric tracked across PRs. On a TPU backend the same calls compile to
Mosaic and the latency column becomes meaningful.

Run:  PYTHONPATH=src:. python benchmarks/table_bench.py \
          --layers 4 --lanes 4 --tokens 64 --d-model 128 --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from benchmarks.common import print_table, write_result
from repro.core import taylor


def _bytes(feat, m1, ds):
    """Analytic bytes moved by one predict + one masked update."""
    import math
    n = math.prod(feat)
    table = m1 * n * ds
    pred_out = n * ds
    return {
        # predict: astype(f32) copy (r/w) + einsum read + f32 out + cast
        "jnp_predict": table + table * 4 // ds * 2 + n * 4 + pred_out,
        # kernel: read the table once, write the prediction
        "kernel_predict": table + pred_out,
        # update: read old, write rows-stack, read stack+old for where,
        # write result (feats traffic is ~table/m1, folded in)
        "jnp_update": 3 * table + 2 * table + n * ds,
        # kernel: read old + feats once, write new once
        "kernel_update": 2 * table + n * ds,
    }


def _time(fn, *args, steps: int) -> float:
    jax.block_until_ready(fn(*args))   # compile + warm outside the window
    t0 = time.time()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / steps


def run(layers=4, lanes=4, tokens=64, d_model=128, order=2, steps=20,
        dtype="float32"):
    dt = jnp.dtype(dtype)
    feat = taylor.feature_shape_for(layers, lanes, tokens, d_model)
    m1 = order + 1
    key = jax.random.PRNGKey(0)
    state = taylor.init_state(order, feat, dt, lanes=lanes)
    for i, s in enumerate(range(0, 4 * m1, 4)):
        f = jax.random.normal(jax.random.fold_in(key, i), feat, jnp.float32)
        state = taylor.update_lanes(state, f.astype(dt), s,
                                    jnp.ones((lanes,), bool),
                                    backend="jnp")
    feats = jax.random.normal(jax.random.fold_in(key, 99), feat,
                              jnp.float32).astype(dt)
    mask = jnp.asarray([i % 2 == 0 for i in range(lanes)])
    step = int(state["anchor_step"][0]) + 2
    ana = _bytes(feat, m1, dt.itemsize)

    rows = []
    for backend in ("jnp", "kernel"):
        predict = jax.jit(lambda st, b=backend: taylor.predict_lanes(
            st, step, backend=b))
        update = jax.jit(lambda st, f, m, b=backend: taylor.update_lanes(
            st, f, step, m, backend=b)["diffs"])
        t_pred = _time(predict, state, steps=steps)
        t_upd = _time(update, state, feats, mask, steps=steps)
        rows.append({
            "backend": backend,
            "table_mb": round(m1 * feats.size * dt.itemsize / 2**20, 2),
            "predict_ms": round(t_pred * 1e3, 3),
            "update_ms": round(t_upd * 1e3, 3),
            "draft_step_ms": round((t_pred + t_upd) * 1e3, 3),
            "predict_bytes_mb": round(ana[f"{backend}_predict"] / 2**20, 2),
            "update_bytes_mb": round(ana[f"{backend}_update"] / 2**20, 2),
        })
    jb = ana["jnp_predict"] + ana["jnp_update"]
    kb = ana["kernel_predict"] + ana["kernel_update"]
    for r in rows:
        r["bytes_ratio_vs_jnp"] = round(
            jb / kb if r["backend"] == "kernel" else 1.0, 2)
    print_table(
        f"table backend ({layers}L x {lanes} lanes x {tokens} tok x "
        f"{d_model}d, {dtype}, m={order})", rows)
    print(f"\nfused kernels move {jb / kb:.2f}x fewer table bytes per "
          f"draft step ({jb / 2**20:.1f} MiB -> {kb / 2**20:.1f} MiB)")
    if jax.default_backend() != "tpu":
        print("NOTE: non-TPU backend -> Pallas runs in interpret mode; "
              "latency columns are oracle-mode numbers, bytes columns are "
              "backend-intrinsic.")
    path = write_result("table_bench", rows)
    print(f"wrote {path}")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--order", type=int, default=2)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    args = ap.parse_args()
    run(layers=args.layers, lanes=args.lanes, tokens=args.tokens,
        d_model=args.d_model, order=args.order, steps=args.steps,
        dtype=args.dtype)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

"""Fused relative-L2 verification kernel (pl.pallas_call + BlockSpec).

Computes per-sample Σ(p−r)² and Σr² in ONE pass over the feature plane.
The unfused jnp version materialises (p−r) and reads both operands twice;
here each (B, block_c) VMEM tile is read once and both partial sums are
accumulated into the output block across the sequential column grid — the
TPU grid executes in order, so read-modify-write accumulation on the
output ref is safe (this is the standard Pallas reduction idiom).

The per-lane threshold variant (``tau`` given) additionally finalises the
accept decision inside the same pass: on the last column tile each lane's
relative error e = √num/(√den+ε) is formed in-register and compared with
that lane's τ, so the serving engine's accept bit never needs a second
read of the feature plane (eq. 4 + §3.4.2 in one kernel).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _row_sums(p_ref, r_ref):
    """Per-row Σ(p−r)² and Σr² of one (B, block_c) tile -> two [B, 1]."""
    p = p_ref[...].astype(jnp.float32)
    r = r_ref[...].astype(jnp.float32)
    d = p - r
    return (jnp.sum(d * d, axis=-1, keepdims=True),
            jnp.sum(r * r, axis=-1, keepdims=True))


def _columns(shape, *cols):
    """A [B, n] block whose column j is the [B, 1] value ``cols[j]`` — a
    lane-iota select, so every store below is a whole-block store."""
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    out = jnp.zeros(shape, jnp.float32)
    for k, v in enumerate(cols):
        out = jnp.where(j == k, v, out)
    return out


def _verify_kernel(p_ref, r_ref, o_ref):
    c = pl.program_id(0)
    num, den = _row_sums(p_ref, r_ref)
    part = _columns(o_ref.shape, num, den)              # [B, 2]

    @pl.when(c == 0)
    def _init():
        o_ref[...] = part

    @pl.when(c > 0)
    def _acc():
        o_ref[...] += part


def _verify_tau_kernel(p_ref, r_ref, tau_ref, o_ref, *, eps: float):
    c = pl.program_id(0)
    num, den = _row_sums(p_ref, r_ref)
    part = _columns(o_ref.shape, num, den)              # [B, 4]

    @pl.when(c == 0)
    def _init():
        o_ref[...] = part

    @pl.when(c > 0)
    def _acc():
        o_ref[...] += part

    # Finalise on the last column tile: the accumulated sums are already in
    # the output block (grid runs in order), so err/accept are pure
    # register math — no extra pass over the feature plane.
    @pl.when(c == pl.num_programs(0) - 1)
    def _fin():
        acc = o_ref[...]
        err = jnp.sqrt(acc[:, 0:1]) / (jnp.sqrt(acc[:, 1:2]) + eps)
        ok = (err <= tau_ref[...]).astype(jnp.float32)
        o_ref[...] = _columns(o_ref.shape, acc[:, 0:1], acc[:, 1:2],
                              err, ok)


def verify_sums(pred: jnp.ndarray, ref: jnp.ndarray, *,
                tau: Optional[jnp.ndarray] = None, eps: float = 1e-8,
                block_c: int = 1024, interpret: bool = False,
                name: str = "verify_sums") -> jnp.ndarray:
    """One-pass per-sample verification sums. pred/ref [B, N] (N%128==0).
    ``name`` is the kernel's name in the compiled program and a profile
    (callers pass their own: ``verify_accept``, ``verify_error``).

    Without ``tau``: returns [B, 2] = (Σ(p−r)², Σr²).
    With per-lane thresholds ``tau`` [B]: returns [B, 4] =
    (Σ(p−r)², Σr², e, accept) with e = √num/(√den+ε) and
    accept = float(e ≤ τ_lane), finalised inside the same fused pass.

    Every block spans all B rows (the whole row axis, which Mosaic's
    tiling rule admits at any B); the grid walks the column tiles in
    order and the [B, 2|4] output block stays resident as the
    accumulator.
    """
    B, N = pred.shape
    block_c = min(block_c, N)
    assert N % block_c == 0, (N, block_c)
    grid = (N // block_c,)
    tile = pl.BlockSpec((B, block_c), lambda c: (0, c))
    if tau is None:
        return pl.pallas_call(
            _verify_kernel,
            name=name,
            grid=grid,
            in_specs=[tile, tile],
            out_specs=pl.BlockSpec((B, 2), lambda c: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, 2), jnp.float32),
            interpret=interpret,
        )(pred, ref)
    # tau travels as a whole [B, 1] column so its compare broadcasts
    # against the [B, 1] error column
    return pl.pallas_call(
        functools.partial(_verify_tau_kernel, eps=eps),
        name=name,
        grid=grid,
        in_specs=[tile, tile, pl.BlockSpec((B, 1), lambda c: (0, 0))],
        out_specs=pl.BlockSpec((B, 4), lambda c: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 4), jnp.float32),
        interpret=interpret,
    )(pred, ref, tau.astype(jnp.float32).reshape(B, 1))


def verify_error(pred: jnp.ndarray, ref: jnp.ndarray, *, eps: float = 1e-8,
                 block_c: int = 1024, interpret: bool = False) -> jnp.ndarray:
    """Per-sample relative L2 error (eq. 4). pred/ref [B, N] -> [B]."""
    sums = verify_sums(pred, ref, block_c=block_c, interpret=interpret,
                       name="verify_error")
    return jnp.sqrt(sums[:, 0]) / (jnp.sqrt(sums[:, 1]) + eps)

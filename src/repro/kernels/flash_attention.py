"""Blocked flash attention (pl.pallas_call + BlockSpec, online softmax).

TPU adaptation of FlashAttention: KV-blocked streaming with running
(max, sum, acc) carried in VMEM scratch across the innermost sequential
grid dimension. Tiles are MXU-aligned (128×128 q/k blocks, full head_dim
lanes). Causal and sliding-window masks are applied per block; this is the
prefill/DiT attention hot path (decode is a GEMV — left to XLA, see
DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  block_q: int, block_k: int, num_k: int, causal: bool,
                  window: int, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # [bq, hd]
    k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]

    if causal or window > 0:
        qi = iq * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_k), 0)
        ki = ik * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                     (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), bool)
        if causal:
            ok &= ki <= qi
        if window > 0:
            ok &= (qi - ki) < window
        s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                               # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == num_k - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)               # fully-masked rows
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bhsd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                         causal: bool = True, window: int = 0,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jnp.ndarray:
    """q/k/v [BH, S, hd] -> out [BH, S, hd]."""
    bh, s, hd = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0, (s, block_q, block_k)
    nq, nk = s // block_q, s // block_k
    scale = 1.0 / (hd ** 0.5)
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, num_k=nk,
        causal=causal, window=window, scale=scale)
    return pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, q_, k_: (b, q_, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, q_, k_: (b, k_, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, q_, k_: (b, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, q_, k_: (b, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)

"""Fused TaylorSeer prediction kernel (pl.pallas_call + BlockSpec).

The draft step is memory-bound: it reads m+1 difference planes and writes
one prediction. Staged jnp code would round-trip HBM per order; this kernel
loads all m+1 planes of a (rows, lanes) VMEM tile once and evaluates
Σ wᵢ·Δⁱ in registers — one HBM read per plane, one write.

Tile choice: (block_r, block_c) multiples of (8, 128) — float32 VREG tiling
on TPU; the weight vector sits in a tiny replicated VMEM block.

The matching recursive *update* kernel fuses the anchor-step difference
refresh the same way (Δⁱ chain needs old Δⁱ⁻¹ exactly once).

The *lane* variants (``taylor_predict_lanes_2d`` / ``taylor_update_lanes_2d``)
are the serving/sampler hot path: the difference table carries one lane per
request (layout row = group·lanes + lane), each lane evaluates its own
weight column w[:, b] and the anchor refresh is masked per lane — rejected
lanes refresh, accepted lanes pass their old rows through — all in ONE pass
over the table with no float32 whole-table temporary.

Lane-kernel tiling: a table row's C features are folded into a
(SUBLANES, C/SUBLANES) plane (a free reshape), so every VMEM block ends in
a (16, block_c) tile — aligned to Mosaic's (8, 128) float32 and (16, 128)
bfloat16 tiling whatever the row count — with the row as a squeezed
leading block index. The per-lane scalars (weight columns, refresh mask)
sit whole in SMEM and are read at the lane's grid index; the rollback
index is a scalar-prefetch operand that steers the snapshot block itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# sublane fold of the lane kernels: a multiple of both the float32 (8) and
# the bfloat16 (16) sublane tile
SUBLANES = 16

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _predict_kernel(w_ref, d_ref, o_ref, *, order: int):
    acc = w_ref[0] * d_ref[0].astype(jnp.float32)
    for i in range(1, order + 1):
        acc += w_ref[i] * d_ref[i].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def taylor_predict_2d(diffs: jnp.ndarray, weights: jnp.ndarray, *,
                      block_r: int = 256, block_c: int = 512,
                      interpret: bool = False) -> jnp.ndarray:
    """diffs [m+1, R, C] (R%8==0, C%128==0), weights [m+1] -> pred [R, C]."""
    m1, R, C = diffs.shape
    block_r = min(block_r, R)
    block_c = min(block_c, C)
    assert R % block_r == 0 and C % block_c == 0, (R, C, block_r, block_c)
    grid = (R // block_r, C // block_c)
    return pl.pallas_call(
        functools.partial(_predict_kernel, order=m1 - 1),
        name="taylor_predict",
        grid=grid,
        in_specs=[
            pl.BlockSpec((m1,), lambda r, c: (0,)),
            pl.BlockSpec((m1, block_r, block_c), lambda r, c: (0, r, c)),
        ],
        out_specs=pl.BlockSpec((block_r, block_c), lambda r, c: (r, c)),
        out_shape=jax.ShapeDtypeStruct((R, C), diffs.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32), diffs)


def _lane_grid(R: int, C: int, lanes: int, block_c: int):
    """(G, lanes, column-tile) grid over a [.., R, C] lane table folded to
    [.., R, SUBLANES, C // SUBLANES]; ``block_c`` tiles the folded axis
    and must be a multiple of 128 or the whole folded axis."""
    assert R % lanes == 0, (R, lanes)
    assert C % SUBLANES == 0, (C, SUBLANES)
    S = C // SUBLANES
    assert S % block_c == 0 and (block_c % 128 == 0 or block_c == S), \
        (S, block_c)
    return (R // lanes, lanes, S // block_c)


def _row_spec(lanes: int, block_c: int, planes: int = 0) -> pl.BlockSpec:
    """One table row's (SUBLANES, block_c) tile (across ``planes`` leading
    planes when nonzero)."""
    if planes:
        return pl.BlockSpec((planes, None, SUBLANES, block_c),
                            lambda g, b, c: (0, g * lanes + b, 0, c))
    return pl.BlockSpec((None, SUBLANES, block_c),
                        lambda g, b, c: (g * lanes + b, 0, c))


def _fold(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape(x.shape[:-1] + (SUBLANES, x.shape[-1] // SUBLANES))


def _predict_lanes_kernel(w_ref, d_ref, o_ref, *, order: int):
    # w_ref is the whole [m+1, lanes] weight table in SMEM, read at this
    # lane's column; d_ref holds one (16, block_c) tile of each difference
    # plane. Sequential FMA in f32 registers — the table is read once,
    # nothing but the prediction is written.
    b = pl.program_id(1)
    acc = w_ref[0, b] * d_ref[0].astype(jnp.float32)
    for i in range(1, order + 1):
        acc += w_ref[i, b] * d_ref[i].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def taylor_predict_lanes_2d(diffs: jnp.ndarray, weights: jnp.ndarray, *,
                            lanes: int, block_c: int = 512,
                            interpret: bool = False) -> jnp.ndarray:
    """Per-lane fused Taylor evaluation.

    diffs [m+1, R, C] with R = G·lanes (lane index = row % lanes, i.e. the
    lane axis is the innermost row factor), weights [m+1, lanes] (each
    lane's w_i column), C % 16 == 0 and ``block_c`` tiling C/16 -> pred
    [R, C]. Every row-tile reads its own lane's weights from SMEM — no
    gather, no broadcast table.
    """
    m1, R, C = diffs.shape
    assert weights.shape == (m1, lanes), (weights.shape, m1, lanes)
    grid = _lane_grid(R, C, lanes, block_c)
    out = pl.pallas_call(
        functools.partial(_predict_lanes_kernel, order=m1 - 1),
        name="taylor_predict_lanes",
        grid=grid,
        in_specs=[_SMEM, _row_spec(lanes, block_c, m1)],
        out_specs=_row_spec(lanes, block_c),
        out_shape=jax.ShapeDtypeStruct((R, SUBLANES, C // SUBLANES),
                                       diffs.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32), _fold(diffs))
    return out.reshape(R, C)


def _predict_chain_kernel(w_ref, d_ref, o_ref, *, order: int, depth: int):
    # w_ref is the whole [(m+1)·K, lanes] chain weight table in SMEM (row
    # i·K + k = order i at chain position k); d_ref holds one (16,
    # block_c) tile of each difference plane. The K chain positions share
    # the m+1 table reads: each position k runs the SAME sequential FMA
    # as ``_predict_lanes_kernel`` (identical association order, so
    # position k of the chain is bit-equal to a depth-1 predict called
    # with that position's weight column).
    b = pl.program_id(1)
    for k in range(depth):
        acc = w_ref[k, b] * d_ref[0].astype(jnp.float32)
        for i in range(1, order + 1):
            acc += w_ref[i * depth + k, b] * d_ref[i].astype(jnp.float32)
        o_ref[k] = acc.astype(o_ref.dtype)


def taylor_predict_chain_2d(diffs: jnp.ndarray, weights: jnp.ndarray, *,
                            lanes: int, block_c: int = 512,
                            interpret: bool = False) -> jnp.ndarray:
    """Per-lane fused Taylor chain evaluation (draft-K speculation).

    diffs [m+1, R, C] with R = G·lanes (lane = row % lanes), weights
    [m+1, K, lanes] (each lane's w_i column per chain position),
    C % 16 == 0 and ``block_c`` tiling C/16 -> preds [K, R, C]. One pass
    over the table serves all K chain positions — the m+1 difference
    planes are read once and K predictions are written, instead of K
    round-trips through the depth-1 kernel. At K=1 this is bit-identical
    to ``taylor_predict_lanes_2d`` (same FMA order per position).
    """
    m1, R, C = diffs.shape
    K = weights.shape[1]
    assert weights.shape == (m1, K, lanes), (weights.shape, m1, K, lanes)
    grid = _lane_grid(R, C, lanes, block_c)
    out = pl.pallas_call(
        functools.partial(_predict_chain_kernel, order=m1 - 1, depth=K),
        name="taylor_predict_chain_lanes",
        grid=grid,
        in_specs=[_SMEM, _row_spec(lanes, block_c, m1)],
        out_specs=_row_spec(lanes, block_c, K),
        out_shape=jax.ShapeDtypeStruct((K, R, SUBLANES, C // SUBLANES),
                                       diffs.dtype),
        interpret=interpret,
    )(weights.astype(jnp.float32).reshape(m1 * K, lanes), _fold(diffs))
    return out.reshape(K, R, C)


def _lane_rollback_kernel(i_ref, c_ref, o_ref):
    # the index map already selected this lane's snapshot block (i_ref is
    # the scalar-prefetched restore index), so the restore is one exact
    # copy — bitwise whichever snapshot wins, and only the winning
    # snapshot is read
    o_ref[...] = c_ref[...]


def lane_rollback_2d(chain: jnp.ndarray, idx: jnp.ndarray, *, lanes: int,
                     block_c: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """Per-lane snapshot restore (speculation rollback).

    chain [K+1, R, C] with R = G·lanes (lane = row % lanes) holds the
    state snapshot before each drafted chain position (position 0 = the
    pre-draft state, position k = after k accepted drafted steps); idx
    [lanes] (integer-valued, clamped to 0..K) is each lane's
    accepted-prefix length -> out [R, C] = chain[idx[row % lanes], row].
    Exact copies, so the rollback is bit-exact against the selected
    snapshot.
    """
    K1, R, C = chain.shape
    assert idx.shape == (lanes,), (idx.shape, lanes)
    grid = _lane_grid(R, C, lanes, block_c)
    # the restore index drives the snapshot block's index map, so it is
    # clamped into range: an out-of-range block index is never formed
    idx = jnp.clip(jnp.asarray(idx, jnp.int32), 0, K1 - 1)
    out = pl.pallas_call(
        _lane_rollback_kernel,
        name="lane_rollback",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[pl.BlockSpec(
                (None, None, SUBLANES, block_c),
                lambda g, b, c, i: (i[b], g * lanes + b, 0, c))],
            out_specs=pl.BlockSpec(
                (None, SUBLANES, block_c),
                lambda g, b, c, i: (g * lanes + b, 0, c))),
        out_shape=jax.ShapeDtypeStruct((R, SUBLANES, C // SUBLANES),
                                       chain.dtype),
        interpret=interpret,
    )(idx, _fold(chain))
    return out.reshape(R, C)


def _update_lanes_kernel(m_ref, d_ref, f_ref, o_ref, *, order: int):
    # One pass: each old plane is read exactly once, each new plane written
    # exactly once; lanes whose SMEM mask entry is 0 copy their old rows
    # through untouched (the masked in-place-style refresh). The Δ chain
    # runs in the table dtype so the kernel is bit-identical to the jnp
    # oracle.
    refresh = m_ref[pl.program_id(1)] != 0
    new = f_ref[...].astype(o_ref.dtype)
    for i in range(order + 1):
        old_i = d_ref[i]
        o_ref[i] = jnp.where(refresh, new, old_i)
        new = new - old_i


def taylor_update_lanes_2d(old_diffs: jnp.ndarray, feats: jnp.ndarray,
                           mask: jnp.ndarray, *, lanes: int,
                           block_c: int = 512,
                           interpret: bool = False) -> jnp.ndarray:
    """Masked per-lane recursive difference refresh.

    old_diffs [m+1, R, C] (R = G·lanes, lane = row % lanes, C % 16 == 0),
    feats [R, C] (the new anchor features in the same layout), mask
    [lanes] (nonzero = refresh that lane) -> new diffs [m+1, R, C].
    Single pass over the table; no whole-table temporary.
    """
    m1, R, C = old_diffs.shape
    assert feats.shape == (R, C), (feats.shape, R, C)
    grid = _lane_grid(R, C, lanes, block_c)
    out = pl.pallas_call(
        functools.partial(_update_lanes_kernel, order=m1 - 1),
        name="taylor_update_lanes",
        grid=grid,
        in_specs=[_SMEM, _row_spec(lanes, block_c, m1),
                  _row_spec(lanes, block_c)],
        out_specs=_row_spec(lanes, block_c, m1),
        out_shape=jax.ShapeDtypeStruct((m1, R, SUBLANES, C // SUBLANES),
                                       old_diffs.dtype),
        interpret=interpret,
    )(jnp.asarray(mask).astype(jnp.int32), _fold(old_diffs), _fold(feats))
    return out.reshape(m1, R, C)


def _update_kernel(d_ref, f_ref, o_ref, *, order: int):
    new = [f_ref[...].astype(jnp.float32)]
    for i in range(1, order + 1):
        new.append(new[i - 1] - d_ref[i - 1].astype(jnp.float32))
    for i in range(order + 1):
        o_ref[i] = new[i].astype(o_ref.dtype)


def taylor_update_2d(old_diffs: jnp.ndarray, feats: jnp.ndarray, *,
                     block_r: int = 256, block_c: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """old_diffs [m+1, R, C], feats [R, C] -> new diffs [m+1, R, C]."""
    m1, R, C = old_diffs.shape
    block_r = min(block_r, R)
    block_c = min(block_c, C)
    assert R % block_r == 0 and C % block_c == 0
    grid = (R // block_r, C // block_c)
    return pl.pallas_call(
        functools.partial(_update_kernel, order=m1 - 1),
        name="taylor_update",
        grid=grid,
        in_specs=[
            pl.BlockSpec((m1, block_r, block_c), lambda r, c: (0, r, c)),
            pl.BlockSpec((block_r, block_c), lambda r, c: (r, c)),
        ],
        out_specs=pl.BlockSpec((m1, block_r, block_c),
                               lambda r, c: (0, r, c)),
        out_shape=jax.ShapeDtypeStruct((m1, R, C), old_diffs.dtype),
        interpret=interpret,
    )(old_diffs, feats)

"""Lane-masked spectral-forecaster table kernel (pl.pallas_call).

The spectral forecaster (``repro.core.forecaster.SpectralForecaster``)
keeps the last m+1 RAW anchor feature snapshots in a per-lane ring —
same ``[m+1, R, C]`` folded layout as the TaylorSeer difference table
(row = group·lanes + lane), different row semantics (row 0 = the newest
anchor, row i = the anchor i refreshes ago).

Its anchor refresh is the masked per-lane RING SHIFT implemented here:
for every lane whose draft was rejected, row 0 becomes the new anchor
features and row i takes the lane's old row i−1 (the oldest snapshot
falls off the end); accepted lanes pass all their rows through
untouched.  Exact copies, no arithmetic — one pass over the table, each
old plane read once, each new plane written once, bitwise identical to
the staged jnp oracle (``kernels.ref.spectral_update_lanes_ref``).

The spectral PREDICTION is the same fused per-lane contraction
Σ_j w_j·row_j the Taylor kernels implement — only the weight columns
differ (frequency-band extrapolation instead of polynomial
extrapolation; computed in ``repro.core.forecaster.spectral_weights``).
The prediction/chain kernels are therefore shared with
``taylor_predict`` and re-exported here under their spectral names so
the spectral kernel surface is complete in one module.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.taylor_predict import (
    SUBLANES, _SMEM, _fold, _lane_grid, _row_spec,
    taylor_predict_chain_2d as spectral_predict_chain_2d,  # noqa: F401
    taylor_predict_lanes_2d as spectral_predict_lanes_2d,  # noqa: F401
)


def _ring_update_kernel(m_ref, d_ref, f_ref, o_ref, *, order: int):
    # m_ref is the whole [lanes] refresh mask in SMEM, read at this lane's
    # grid index; d_ref holds the m+1 ring rows of one (16, block_c)
    # tile; f_ref is the new anchor features tile. Refreshing lanes shift
    # their ring (row 0 <- feats, row i <- old row i-1); untouched lanes
    # copy through. Exact copies in the table dtype — bitwise.
    refresh = m_ref[pl.program_id(1)] != 0
    o_ref[0] = jnp.where(refresh, f_ref[...].astype(o_ref.dtype), d_ref[0])
    for i in range(1, order + 1):
        o_ref[i] = jnp.where(refresh, d_ref[i - 1], d_ref[i])


def spectral_update_lanes_2d(old_ring: jnp.ndarray, feats: jnp.ndarray,
                             mask: jnp.ndarray, *, lanes: int,
                             block_c: int = 512,
                             interpret: bool = False) -> jnp.ndarray:
    """Masked per-lane ring-shift refresh of the raw-anchor table.

    old_ring [m+1, R, C] (R = G·lanes, lane = row % lanes, C % 16 == 0),
    feats [R, C] (the new anchor features in the same layout), mask
    [lanes] (nonzero = refresh that lane) -> new ring [m+1, R, C].
    Single pass over the table; no whole-table temporary.
    """
    m1, R, C = old_ring.shape
    assert feats.shape == (R, C), (feats.shape, R, C)
    grid = _lane_grid(R, C, lanes, block_c)
    out = pl.pallas_call(
        functools.partial(_ring_update_kernel, order=m1 - 1),
        name="spectral_update_lanes",
        grid=grid,
        in_specs=[_SMEM, _row_spec(lanes, block_c, m1),
                  _row_spec(lanes, block_c)],
        out_specs=_row_spec(lanes, block_c, m1),
        out_shape=jax.ShapeDtypeStruct((m1, R, SUBLANES, C // SUBLANES),
                                       old_ring.dtype),
        interpret=interpret,
    )(jnp.asarray(mask).astype(jnp.int32), _fold(old_ring), _fold(feats))
    return out.reshape(m1, R, C)

"""Jit'd public wrappers around the Pallas kernels.

Where the kernels run is chosen by the backend JAX reports: on ``tpu``
every call compiles to a Mosaic kernel (a ``tpu_custom_call`` in the
HLO); on ``cpu`` the same calls run in ``interpret=True`` mode — the
kernel body executes as traced jnp, which is how the test suite checks
them without a chip. Any other backend is an error rather than a silent
fallback.

Exported surface (each documented on its function):

  * ``taylor_predict`` / ``taylor_update`` — scalar-anchor table ops
    (whole-batch anchors, the reproduction sampler's degenerate case).
  * ``taylor_predict_lanes`` / ``taylor_update_lanes`` — the serving hot
    path: per-lane weight columns and the lane-masked recursive refresh,
    one pass over the (m+1, L, 2, W, T, D) difference table.
  * ``verify_error`` / ``verify_accept`` — per-lane rel-L2 (eq. 4) and
    the fused sums+threshold verification.
  * ``verify_accept_mixed`` — slot-width serving (API v2): a per-pair
    ``paired`` mask selects, pair by pair, between per-lane decisions
    (unpaired lanes verify their own stream) and ONE guided-residual
    decision per cond/uncond pair — guided and unguided requests mix in
    one batch (``repro.core.lane_step`` / ``docs/cfg.md``).
  * ``verify_accept_pairs`` — the all-paired reduction of the above
    (CFG serving's original surface): guided residual ``u + s·(c − u)``
    per cond/uncond lane pair and ONE τ comparison per pair.
  * ``*_sharded`` — ``shard_map`` routings of the above for lane-sharded
    serving meshes (``pallas_call`` is opaque to the SPMD partitioner).
  * ``flash_attention`` — fused attention used by the backbone when
    ``use_flash=True``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import spectral as _sp
from repro.kernels import taylor_predict as _tp
from repro.kernels import verify_error as _ve
from repro.kernels import ref as ref  # noqa: F401 (re-export for tests)


def _interpret() -> bool:
    """True on the CPU (Pallas interpreter), False on a TPU (Mosaic)."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels compile for 'tpu' and are "
                       f"interpreted on 'cpu'; backend {backend!r} is "
                       "neither")


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_r", "block_c"))
def taylor_predict(diffs: jnp.ndarray, weights: jnp.ndarray, *,
                   block_r: int = 256, block_c: int = 512) -> jnp.ndarray:
    """diffs [m+1, ...feat], weights [m+1] -> prediction [...feat]."""
    shape = diffs.shape[1:]
    n = 1
    for s in shape:
        n *= s
    m1 = diffs.shape[0]
    # fold into an (8, C) plane for float32 (8, 128) VREG tiling
    flat = _pad_to(diffs.reshape(m1, n), 1, 8 * 128)
    c = flat.shape[1] // 8
    flat = flat.reshape(m1, 8, c)
    bc = min(block_c, c)
    while c % bc:
        bc //= 2
    out = _tp.taylor_predict_2d(flat, weights, block_r=8, block_c=bc,
                                interpret=_interpret())
    return out.reshape(-1)[:n].reshape(shape)


@functools.partial(jax.jit, static_argnames=("block_r", "block_c"))
def taylor_update(old_diffs: jnp.ndarray, feats: jnp.ndarray, *,
                  block_r: int = 256, block_c: int = 512) -> jnp.ndarray:
    """old_diffs [m+1, ...feat], feats [...feat] -> new diffs."""
    m1 = old_diffs.shape[0]
    shape = old_diffs.shape[1:]
    n = 1
    for s in shape:
        n *= s
    od = _pad_to(old_diffs.reshape(m1, 1, n), 2, 128)
    f = _pad_to(feats.reshape(1, n), 1, 128)
    c = od.shape[2]
    bc = min(block_c, c)
    while c % bc:
        bc //= 2
    out = _tp.taylor_update_2d(od.reshape(m1, 1, c), f.reshape(1, c),
                               block_r=1, block_c=bc,
                               interpret=_interpret())
    return out.reshape(m1, -1)[:, :n].reshape((m1,) + shape)


def _lane_cols(C: int, block_c: int):
    """(padded C, column tile) for a lane kernel: C pads to a multiple of
    ``_tp.SUBLANES`` and the folded row width S = C/SUBLANES is tiled by
    the largest power-of-two share of ``block_c`` that divides it (a
    multiple of 128), or taken whole when S is not a 128-multiple and
    fits one tile; only a wider, unaligned S pads up to the 128 tile."""
    sub = _tp.SUBLANES
    S = -(-C // sub)
    if S % 128 and S > block_c:
        S = -(-S // 128) * 128
    if S % 128:
        return S * sub, S
    bc = min(block_c, S)
    while S % bc:
        bc //= 2
    return S * sub, bc


def _lane_fold(shape, lane_axis: int):
    """(G, B, C) row/lane/col factorisation of a feature layout."""
    B = shape[lane_axis]
    G = 1
    for s in shape[:lane_axis]:
        G *= s
    C = 1
    for s in shape[lane_axis + 1:]:
        C *= s
    return G, B, C


@functools.partial(jax.jit, static_argnames=("lane_axis", "block_c"))
def taylor_predict_lanes(diffs: jnp.ndarray, weights: jnp.ndarray, *,
                         lane_axis: int = 2,
                         block_c: int = 8192) -> jnp.ndarray:
    """Per-lane fused Taylor evaluation over a feature-layout table.

    diffs [m+1, ...feat] with ``lane_axis`` indexing the lane (batch) axis
    of the *feature* part, weights [m+1, B] -> prediction [...feat]. The
    folds below are pure reshapes (the lane axis stays an inner row
    factor), so aligned shapes move zero extra bytes; a trailing-axis pad
    to the 128-lane tile is the only copy for odd shapes.
    """
    m1 = diffs.shape[0]
    feat = diffs.shape[1:]
    G, B, C = _lane_fold(feat, lane_axis)
    cp, bc = _lane_cols(C, block_c)
    flat = _pad_to(diffs.reshape(m1, G * B, C), 2, cp)
    out = _tp.taylor_predict_lanes_2d(flat, weights, lanes=B, block_c=bc,
                                      interpret=_interpret())
    return out[:, :C].reshape(feat)


@functools.partial(jax.jit, static_argnames=("lane_axis", "block_c"))
def taylor_predict_chain_lanes(diffs: jnp.ndarray, weights: jnp.ndarray, *,
                               lane_axis: int = 2,
                               block_c: int = 8192) -> jnp.ndarray:
    """Per-lane fused Taylor CHAIN evaluation (draft-K speculation).

    diffs [m+1, ...feat] with ``lane_axis`` the lane axis of the feature
    part, weights [m+1, K, B] (each lane's weight column per chain
    position) -> predictions [K, ...feat]. One pass over the table
    serves all K positions; position k is bit-identical to
    :func:`taylor_predict_lanes` with ``weights[:, k]``.
    """
    m1, K = weights.shape[0], weights.shape[1]
    feat = diffs.shape[1:]
    G, B, C = _lane_fold(feat, lane_axis)
    cp, bc = _lane_cols(C, block_c)
    flat = _pad_to(diffs.reshape(m1, G * B, C), 2, cp)
    out = _tp.taylor_predict_chain_2d(flat, weights, lanes=B, block_c=bc,
                                      interpret=_interpret())
    return out[:, :, :C].reshape((K,) + feat)


@functools.partial(jax.jit, static_argnames=("lane_axis", "block_c"))
def lane_rollback(chain: jnp.ndarray, idx: jnp.ndarray, *,
                  lane_axis: int = 2,
                  block_c: int = 8192) -> jnp.ndarray:
    """Per-lane snapshot restore (speculation rollback).

    chain [K+1, ...feat] with ``lane_axis`` the lane axis of the feature
    part (snapshot 0 = pre-draft state, snapshot k = after k accepted
    drafted steps), idx [B] integer-valued in 0..K -> restored [...feat]
    = chain[idx[lane]] per lane. Exact copies — bitwise against the
    selected snapshot.
    """
    K1 = chain.shape[0]
    feat = chain.shape[1:]
    G, B, C = _lane_fold(feat, lane_axis)
    cp, bc = _lane_cols(C, block_c)
    flat = _pad_to(chain.reshape(K1, G * B, C), 2, cp)
    out = _tp.lane_rollback_2d(flat, jnp.asarray(idx, jnp.int32),
                               lanes=B, block_c=bc,
                               interpret=_interpret())
    return out[:, :C].reshape(feat)


@functools.partial(jax.jit, static_argnames=("lane_axis", "block_c"))
def taylor_update_lanes(old_diffs: jnp.ndarray, feats: jnp.ndarray,
                        mask: jnp.ndarray, *, lane_axis: int = 2,
                        block_c: int = 8192) -> jnp.ndarray:
    """Masked per-lane recursive difference refresh (one pass).

    old_diffs [m+1, ...feat], feats [...feat], mask [B] (True = refresh
    that lane) -> new diffs [m+1, ...feat]. Accepted lanes' rows pass
    through unchanged.
    """
    m1 = old_diffs.shape[0]
    feat = old_diffs.shape[1:]
    G, B, C = _lane_fold(feat, lane_axis)
    cp, bc = _lane_cols(C, block_c)
    od = _pad_to(old_diffs.reshape(m1, G * B, C), 2, cp)
    f = _pad_to(feats.astype(old_diffs.dtype).reshape(G * B, C), 1, cp)
    out = _tp.taylor_update_lanes_2d(od, f, mask, lanes=B, block_c=bc,
                                     interpret=_interpret())
    return out[:, :, :C].reshape((m1,) + feat)


@functools.partial(jax.jit, static_argnames=("lane_axis", "block_c"))
def spectral_update_lanes(old_ring: jnp.ndarray, feats: jnp.ndarray,
                          mask: jnp.ndarray, *, lane_axis: int = 2,
                          block_c: int = 8192) -> jnp.ndarray:
    """Masked per-lane ring-shift refresh of the spectral raw-anchor
    table (one pass).

    old_ring [m+1, ...feat], feats [...feat], mask [B] (True = refresh
    that lane) -> new ring [m+1, ...feat]: refreshed lanes shift their
    ring (row 0 = feats, row i = old row i−1); accepted lanes' rows
    pass through unchanged. Exact copies — bitwise against
    ``ref.spectral_update_lanes_ref``.
    """
    m1 = old_ring.shape[0]
    feat = old_ring.shape[1:]
    G, B, C = _lane_fold(feat, lane_axis)
    cp, bc = _lane_cols(C, block_c)
    od = _pad_to(old_ring.reshape(m1, G * B, C), 2, cp)
    f = _pad_to(feats.astype(old_ring.dtype).reshape(G * B, C), 1, cp)
    out = _sp.spectral_update_lanes_2d(od, f, mask, lanes=B, block_c=bc,
                                       interpret=_interpret())
    return out[:, :, :C].reshape((m1,) + feat)


# The spectral PREDICTION is the same fused per-lane contraction
# Σ_j w_j·table_j the Taylor kernels run — only the weight columns
# differ (frequency-band extrapolation weights computed in
# ``repro.core.forecaster.spectral_weights``). The named aliases keep
# the spectral kernel surface complete and let the two diverge later
# without touching callers.
spectral_predict_lanes = taylor_predict_lanes
spectral_predict_chain_lanes = taylor_predict_chain_lanes


@functools.partial(jax.jit, static_argnames=("eps", "block_c"))
def verify_error(pred: jnp.ndarray, ref_: jnp.ndarray, *, eps: float = 1e-8,
                 block_c: int = 1024) -> jnp.ndarray:
    """Per-sample rel-L2 (eq. 4). pred/ref [B, ...] -> [B]."""
    B = pred.shape[0]
    p = pred.reshape(B, -1)
    r = ref_.reshape(B, -1)
    p = _pad_to(p, 1, 128)
    r = _pad_to(r, 1, 128)
    bc = min(block_c, p.shape[1])
    while p.shape[1] % bc:
        bc //= 2
    return _ve.verify_error(p, r, eps=eps, block_c=bc,
                            interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eps", "block_c"))
def verify_accept(pred: jnp.ndarray, ref_: jnp.ndarray, tau: jnp.ndarray, *,
                  eps: float = 1e-8, block_c: int = 1024):
    """Fused per-lane verification (serving path): one pass over the
    feature plane yields each lane's rel-L2 error AND its accept bit
    against that lane's threshold. pred/ref [B, ...], tau [B] ->
    (err [B] f32, accept [B] bool)."""
    B = pred.shape[0]
    p = _pad_to(pred.reshape(B, -1), 1, 128)
    r = _pad_to(ref_.reshape(B, -1), 1, 128)
    bc = min(block_c, p.shape[1])
    while p.shape[1] % bc:
        bc //= 2
    out = _ve.verify_sums(p, r, tau=jnp.asarray(tau, jnp.float32), eps=eps,
                          block_c=bc, interpret=_interpret(),
                          name="verify_accept")
    return out[:, 2], out[:, 3] > 0.0


def _mixed_planes(pred: jnp.ndarray, ref_: jnp.ndarray,
                  gscale: jnp.ndarray, paired: jnp.ndarray):
    """The effective per-lane verification planes of a mixed batch.

    Lanes (2k, 2k+1) form pair slot k. Where ``paired[2k]`` both rows
    are replaced by the pair's guided residual ``u + s·(c − u)`` (so
    the two rows carry the SAME plane and the per-lane sums kernel
    naturally yields one pair-equal decision); unpaired rows pass
    through untouched. An odd trailing lane is always unpaired. The
    combination is restated from ``pipeline.guided_output`` (kernels
    must not import the diffusion layer) — keep the two in sync.
    """
    W = pred.shape[0]
    p = pred.reshape(W, -1).astype(jnp.float32)
    r = ref_.reshape(W, -1).astype(jnp.float32)
    NP = W // 2
    if NP == 0:
        return p, r
    F = p.shape[1]
    p2 = p[:2 * NP].reshape(NP, 2, F)
    r2 = r[:2 * NP].reshape(NP, 2, F)
    s = jnp.asarray(gscale, jnp.float32)[0:2 * NP:2].reshape(NP, 1, 1)
    pg = p2[:, 1:2] + s * (p2[:, 0:1] - p2[:, 1:2])     # [NP, 1, F]
    rg = r2[:, 1:2] + s * (r2[:, 0:1] - r2[:, 1:2])
    pm = jnp.asarray(paired)[:2 * NP].reshape(NP, 2, 1)
    pe = jnp.where(pm, pg, p2).reshape(2 * NP, F)
    re = jnp.where(pm, rg, r2).reshape(2 * NP, F)
    if W % 2:
        pe = jnp.concatenate([pe, p[2 * NP:]], axis=0)
        re = jnp.concatenate([re, r[2 * NP:]], axis=0)
    return pe, re


@functools.partial(jax.jit, static_argnames=("eps", "block_c"))
def verify_accept_mixed(pred: jnp.ndarray, ref_: jnp.ndarray,
                        tau: jnp.ndarray, gscale: jnp.ndarray,
                        paired: jnp.ndarray, *,
                        eps: float = 1e-8, block_c: int = 1024):
    """Slot-width fused verification (mixed guided+unguided serving).

    ``pred``/``ref_`` [W, ...]; lanes (2k, 2k+1) form pair slot k.
    ``paired`` [W] bool (pair-equal by the engine's fill invariant)
    marks guided pairs: their rows verify on the pair's guided residual
    — both rows carry the identical plane, so the one-pass sums kernel
    issues the pair's single decision to both lanes — while unpaired
    rows verify on their own stream, exactly :func:`verify_accept`.
    ``tau``/``gscale`` are per-LANE [W] (pair-equal where paired).
    Returns (err [W] f32, accept [W] bool).

    With ``paired`` all-False this is bit-identical to
    :func:`verify_accept` (same planes after the kernel's in-tile f32
    cast, same block split); with ``paired`` all-True each pair's rows
    reproduce :func:`verify_accept_pairs`' per-pair values exactly —
    both properties are pinned in tests/test_kernels.py and underpin
    the serving back-compat wrappers.

    Cost note: an all-paired batch reduces W duplicated guided rows
    where the pair-only kernel reduces W/2 — the price of one uniform
    kernel with per-lane outputs for arbitrary masks. Verification is
    γ ≈ 1-4% of a step's FLOPs (docs/architecture.md), so the
    duplicated reduction is noise next to the backbone forward; revisit
    with a scatter-from-pair-rows variant only if a profile ever says
    otherwise.
    """
    W = pred.shape[0]
    p, r = _mixed_planes(pred, ref_, gscale, paired)
    p = _pad_to(p, 1, 128)
    r = _pad_to(r, 1, 128)
    bc = min(block_c, p.shape[1])
    while p.shape[1] % bc:
        bc //= 2
    out = _ve.verify_sums(p, r, tau=jnp.asarray(tau, jnp.float32),
                          eps=eps, block_c=bc, interpret=_interpret(),
                          name="verify_accept_mixed")
    return out[:, 2], out[:, 3] > 0.0


@functools.partial(jax.jit, static_argnames=("eps", "block_c"))
def verify_accept_pairs(pred: jnp.ndarray, ref_: jnp.ndarray,
                        tau: jnp.ndarray, gscale: jnp.ndarray, *,
                        eps: float = 1e-8, block_c: int = 1024):
    """Pair-reduced fused verification (CFG serving path).

    ``pred``/``ref_`` [W, ...] hold interleaved cond/uncond lane pairs
    (cond at row 2k, uncond at 2k+1; W even). The guided residual
    ``u + s·(c − u)`` is formed per pair for both operands and verified
    through the same one-pass sums kernel as :func:`verify_accept` — ONE
    τ comparison per pair. ``tau``/``gscale`` are per-PAIR [W/2].
    Returns (err [W/2] f32, accept [W/2] bool).

    The all-paired reduction of :func:`verify_accept_mixed` (one code
    path): the mixed kernel's pair rows carry identical planes, so the
    cond rows hold the per-pair values.
    """
    W = pred.shape[0]
    if W % 2 != 0:
        raise ValueError(f"pair verification needs interleaved cond/"
                         f"uncond lane pairs: got odd lane count {W}")
    P = W // 2
    tau_l = jnp.repeat(jnp.asarray(tau, jnp.float32), 2)
    gs_l = jnp.repeat(jnp.asarray(gscale, jnp.float32), 2)
    err, acc = verify_accept_mixed(pred, ref_, tau_l, gs_l,
                                   jnp.ones((W,), bool), eps=eps,
                                   block_c=block_c)
    return err[0::2].reshape(P), acc[0::2].reshape(P)


# ---------------------------------------------------------------------------
# Mesh-sharded lane wrappers
# ---------------------------------------------------------------------------
# ``pallas_call`` is an opaque custom call to the SPMD partitioner, so a
# lane-sharded operand would be gathered onto one device before the kernel
# ran. These wrappers route the per-lane kernels through ``shard_map``
# instead: each shard runs the EXISTING lane-masked kernel on its local
# lane block (the kernels are per-lane-independent, so local == global per
# lane, bit-for-bit), and the lane axis never leaves its device. The jnp
# table path needs no wrapper — einsum/where partition natively and serve
# as the sharded oracle. ``check_vma=False`` because the custom call
# defeats shard_map's varying-manual-axes checker.

def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _lane_p(ndim: int, lane_dim: int, axis: str):
    from repro.sharding.specs import lane_spec
    return lane_spec(ndim, lane_dim, axis)


def taylor_predict_lanes_sharded(diffs: jnp.ndarray, weights: jnp.ndarray,
                                 *, mesh, lane_axis: int = 2,
                                 axis_name: str = "data",
                                 block_c: int = 8192) -> jnp.ndarray:
    """``taylor_predict_lanes`` with the lane axis sharded over ``mesh``.

    diffs [m+1, ...feat] (lane axis of the feature part over
    ``axis_name``), weights [m+1, B] (lanes over ``axis_name``) ->
    prediction [...feat], lane-sharded like the input.
    """
    fspec = _lane_p(diffs.ndim - 1, lane_axis, axis_name)
    dspec = _lane_p(diffs.ndim, lane_axis + 1, axis_name)
    wspec = _lane_p(2, 1, axis_name)
    fn = functools.partial(taylor_predict_lanes, lane_axis=lane_axis,
                           block_c=block_c)
    return _shard_map(fn, mesh, (dspec, wspec), fspec)(diffs, weights)


def taylor_predict_chain_lanes_sharded(diffs: jnp.ndarray,
                                       weights: jnp.ndarray, *, mesh,
                                       lane_axis: int = 2,
                                       axis_name: str = "data",
                                       block_c: int = 8192) -> jnp.ndarray:
    """``taylor_predict_chain_lanes`` with the lane axis sharded.

    diffs [m+1, ...feat] (lane axis over ``axis_name``), weights
    [m+1, K, B] (lanes over ``axis_name``) -> predictions [K, ...feat],
    lane-sharded like the input.
    """
    fspec = _lane_p(diffs.ndim, lane_axis + 1, axis_name)
    dspec = _lane_p(diffs.ndim, lane_axis + 1, axis_name)
    wspec = _lane_p(3, 2, axis_name)
    fn = functools.partial(taylor_predict_chain_lanes, lane_axis=lane_axis,
                           block_c=block_c)
    return _shard_map(fn, mesh, (dspec, wspec), fspec)(diffs, weights)


def lane_rollback_sharded(chain: jnp.ndarray, idx: jnp.ndarray, *, mesh,
                          lane_axis: int = 2, axis_name: str = "data",
                          block_c: int = 8192) -> jnp.ndarray:
    """``lane_rollback`` with the lane axis sharded: each shard restores
    its own lanes' snapshot rows — the chain never leaves its device."""
    cspec = _lane_p(chain.ndim, lane_axis + 1, axis_name)
    ospec = _lane_p(chain.ndim - 1, lane_axis, axis_name)
    ispec = _lane_p(1, 0, axis_name)
    fn = functools.partial(lane_rollback, lane_axis=lane_axis,
                           block_c=block_c)
    return _shard_map(fn, mesh, (cspec, ispec), ospec)(chain, idx)


def taylor_update_lanes_sharded(old_diffs: jnp.ndarray, feats: jnp.ndarray,
                                mask: jnp.ndarray, *, mesh,
                                lane_axis: int = 2,
                                axis_name: str = "data",
                                block_c: int = 8192) -> jnp.ndarray:
    """Masked per-lane table refresh with the lane axis sharded: each
    shard refreshes its own lanes' slices in place — the difference table
    is never gathered."""
    fspec = _lane_p(feats.ndim, lane_axis, axis_name)
    dspec = _lane_p(old_diffs.ndim, lane_axis + 1, axis_name)
    mspec = _lane_p(1, 0, axis_name)
    fn = functools.partial(taylor_update_lanes, lane_axis=lane_axis,
                           block_c=block_c)
    return _shard_map(fn, mesh, (dspec, fspec, mspec),
                      dspec)(old_diffs, feats, mask)


def spectral_update_lanes_sharded(old_ring: jnp.ndarray,
                                  feats: jnp.ndarray, mask: jnp.ndarray,
                                  *, mesh, lane_axis: int = 2,
                                  axis_name: str = "data",
                                  block_c: int = 8192) -> jnp.ndarray:
    """Masked per-lane ring shift with the lane axis sharded: each shard
    shifts its own lanes' ring rows in place — the raw-anchor table is
    never gathered."""
    fspec = _lane_p(feats.ndim, lane_axis, axis_name)
    dspec = _lane_p(old_ring.ndim, lane_axis + 1, axis_name)
    mspec = _lane_p(1, 0, axis_name)
    fn = functools.partial(spectral_update_lanes, lane_axis=lane_axis,
                           block_c=block_c)
    return _shard_map(fn, mesh, (dspec, fspec, mspec),
                      dspec)(old_ring, feats, mask)


# sharded spectral prediction: the shared contraction, spectral weights
spectral_predict_lanes_sharded = taylor_predict_lanes_sharded
spectral_predict_chain_lanes_sharded = taylor_predict_chain_lanes_sharded


def verify_accept_sharded(pred: jnp.ndarray, ref_: jnp.ndarray,
                          tau: jnp.ndarray, *, mesh,
                          axis_name: str = "data", eps: float = 1e-8,
                          block_c: int = 1024):
    """Fused per-lane verification over a lane-sharded feature plane:
    pred/ref [B, ...] (B over ``axis_name``), tau [B] -> (err [B],
    accept [B]), both lane-sharded. Each lane's Σ(p−r)²/Σr² reduction is
    shard-local — no cross-device traffic."""
    lspec = _lane_p(1, 0, axis_name)
    pspec = _lane_p(pred.ndim, 0, axis_name)
    fn = functools.partial(verify_accept, eps=eps, block_c=block_c)
    return _shard_map(fn, mesh, (pspec, pspec, lspec),
                      (lspec, lspec))(pred, ref_, tau)


def verify_accept_mixed_sharded(pred: jnp.ndarray, ref_: jnp.ndarray,
                                tau: jnp.ndarray, gscale: jnp.ndarray,
                                paired: jnp.ndarray, *, mesh,
                                axis_name: str = "data",
                                eps: float = 1e-8, block_c: int = 1024):
    """:func:`verify_accept_mixed` with the lane axis sharded.

    pred/ref [W, ...] (lanes over ``axis_name``), tau/gscale/paired [W]
    lane-sharded -> (err [W], accept [W]), lane-sharded. Requires W to
    be a multiple of ``2·D`` — the engine's mixed-session width rounding
    guarantees it — so each shard holds whole pair slots: the guided
    residual select and each lane's reduction are shard-local, with zero
    cross-device traffic."""
    from repro.sharding.specs import lane_shard_count
    D = lane_shard_count(mesh, axis_name)
    if pred.shape[0] % (2 * D) != 0:
        raise ValueError(
            f"lane count {pred.shape[0]} must be a multiple of 2·D={2*D} "
            "so pair slots never straddle a shard boundary")
    lspec = _lane_p(1, 0, axis_name)
    pspec = _lane_p(pred.ndim, 0, axis_name)
    fn = functools.partial(verify_accept_mixed, eps=eps, block_c=block_c)
    return _shard_map(fn, mesh, (pspec, pspec, lspec, lspec, lspec),
                      (lspec, lspec))(pred, ref_, tau, gscale, paired)


def verify_accept_pairs_sharded(pred: jnp.ndarray, ref_: jnp.ndarray,
                                tau: jnp.ndarray, gscale: jnp.ndarray, *,
                                mesh, axis_name: str = "data",
                                eps: float = 1e-8, block_c: int = 1024):
    """:func:`verify_accept_pairs` with the lane axis sharded.

    pred/ref [W, ...] (lanes over ``axis_name``), tau/gscale [W/2]
    (pairs over ``axis_name``) -> (err [W/2], accept [W/2]),
    pair-sharded. Requires W to be a multiple of ``2·D`` — the engine's
    guided width rounding guarantees it — so each shard holds whole
    cond/uncond pairs and the guided combination plus each pair's
    reduction is shard-local, with zero cross-device traffic."""
    from repro.sharding.specs import lane_shard_count
    D = lane_shard_count(mesh, axis_name)
    if pred.shape[0] % (2 * D) != 0:
        raise ValueError(
            f"lane count {pred.shape[0]} must be a multiple of 2·D={2*D} "
            "so cond/uncond pairs never straddle a shard boundary")
    pair_spec = _lane_p(1, 0, axis_name)
    pspec = _lane_p(pred.ndim, 0, axis_name)
    fn = functools.partial(verify_accept_pairs, eps=eps, block_c=block_c)
    return _shard_map(fn, mesh, (pspec, pspec, pair_spec, pair_spec),
                      (pair_spec, pair_spec))(pred, ref_, tau, gscale)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """q/k/v [B, S, H, hd] (equal head counts) -> [B, S, H, hd]."""
    b, s, h, hd = q.shape
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, hd)
    bq = min(block_q, s)
    bk = min(block_k, s)
    while s % bq:
        bq //= 2
    while s % bk:
        bk //= 2
    out = _fa.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                   block_q=bq, block_k=bk,
                                   interpret=_interpret())
    return out.reshape(b, h, s, hd).transpose(0, 2, 1, 3)

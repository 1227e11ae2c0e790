"""Per-kernel shape/dtype sweeps, allclose vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [
    (1, 64), (3, 17), (4, 2, 2, 33, 40), (2, 1000), (5, 8, 128),
])
def test_taylor_predict_kernel(shape, dtype):
    key = jax.random.PRNGKey(hash(shape) % 2**31)
    diffs = jax.random.normal(key, shape, jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (shape[0],))
    got = ops.taylor_predict(diffs, w)
    want = R.taylor_predict_ref(diffs, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(2, 40), (4, 3, 130), (3, 8, 128)])
def test_taylor_update_kernel(shape, dtype):
    key = jax.random.PRNGKey(0)
    old = jax.random.normal(key, shape, jnp.float32).astype(dtype)
    feats = jax.random.normal(jax.random.fold_in(key, 1), shape[1:],
                              jnp.float32).astype(dtype)
    got = ops.taylor_update(old, feats)
    want = R.taylor_update_ref(old, feats)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("feat,lane_axis", [
    ((2, 2, 3, 13, 24), 2),    # serving layout (L, 2, B, T, D), odd T/D
    ((3, 5, 7), 1),            # odd everything, interior lane axis
    ((4, 2, 1, 33, 40), 2),    # single lane
    ((6, 129), 0),             # lane-leading, one past the 128 tile
])
def test_taylor_predict_lanes_kernel(feat, lane_axis, dtype):
    """Per-lane fused prediction vs the einsum oracle at padding-
    exercising shapes."""
    m1 = 4
    B = feat[lane_axis]
    key = jax.random.PRNGKey(sum(feat))
    diffs = jax.random.normal(key, (m1,) + feat, jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m1, B))
    got = ops.taylor_predict_lanes(diffs, w, lane_axis=lane_axis)
    want = R.taylor_predict_lanes_ref(diffs, w, lane_axis=lane_axis)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("feat,lane_axis", [
    ((2, 2, 3, 13, 24), 2),
    ((3, 5, 7), 1),
    ((4, 2, 1, 33, 40), 2),
    ((6, 129), 0),
])
def test_taylor_update_lanes_kernel_bitwise(feat, lane_axis, dtype):
    """The masked one-pass refresh is BIT-IDENTICAL to the staged
    (stack + where) oracle — refreshed lanes get the recursive chain,
    masked-out lanes pass through untouched."""
    m1 = 4
    B = feat[lane_axis]
    key = jax.random.PRNGKey(sum(feat) + 1)
    old = jax.random.normal(key, (m1,) + feat, jnp.float32).astype(dtype)
    feats = jax.random.normal(jax.random.fold_in(key, 1), feat,
                              jnp.float32).astype(dtype)
    mask = jnp.asarray([i % 2 == 0 for i in range(B)])
    got = ops.taylor_update_lanes(old, feats, mask, lane_axis=lane_axis)
    want = R.taylor_update_lanes_ref(old, feats, mask, lane_axis=lane_axis)
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    # untouched lanes really are untouched
    keep = np.logical_not(np.asarray(mask))
    got_m = np.moveaxis(np.asarray(got, np.float32), lane_axis + 1, 1)
    old_m = np.moveaxis(np.asarray(old, np.float32), lane_axis + 1, 1)
    assert np.array_equal(got_m[:, keep], old_m[:, keep])


def test_taylor_lanes_bf16_table_quantisation_bounded():
    """bf16 DIFFERENCE TABLES (half the storage of the serving engine's
    largest array): the fused lane kernels accumulate in f32, so a bf16
    table's prediction must sit within bf16 rounding of the f32-table
    prediction — the kernel adds no error beyond the storage format."""
    m1, feat, lane_axis = 4, (2, 2, 3, 13, 24), 2
    B = feat[lane_axis]
    key = jax.random.PRNGKey(0)
    diffs = jax.random.normal(key, (m1,) + feat, jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m1, B))
    got = ops.taylor_predict_lanes(diffs.astype(jnp.bfloat16), w,
                                   lane_axis=lane_axis)
    want = ops.taylor_predict_lanes(diffs, w, lane_axis=lane_axis)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)
    # masked refresh keeps the bf16 chain bit-identical to quantising
    # the staged oracle's bf16 chain (same dtype arithmetic)
    feats = jax.random.normal(jax.random.fold_in(key, 2), feat)
    mask = jnp.asarray([True, False, True])
    got = ops.taylor_update_lanes(diffs.astype(jnp.bfloat16),
                                  feats.astype(jnp.bfloat16), mask,
                                  lane_axis=lane_axis)
    want = R.taylor_update_lanes_ref(diffs.astype(jnp.bfloat16),
                                     feats.astype(jnp.bfloat16), mask,
                                     lane_axis=lane_axis)
    assert got.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


def test_predict_lanes_degenerate_equals_scalar_kernel():
    """Identical weight columns make the lane kernel the whole-batch
    forecast: with every lane on the same column it must agree with the
    scalar-weight oracle and ``core.taylor.predict`` to float32 rounding
    — the invariant that lets the sampler treat whole-batch anchors as
    the lanes=B degenerate case."""
    from repro.core import taylor as T
    key = jax.random.PRNGKey(0)
    feat = (2, 2, 3, 12, 24)
    diffs = jax.random.normal(key, (3,) + feat, jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (3,))
    wl = jnp.broadcast_to(w[:, None], (3, feat[2]))
    got = np.asarray(ops.taylor_predict_lanes(diffs, wl, lane_axis=2))
    np.testing.assert_allclose(got, np.asarray(R.taylor_predict_ref(diffs, w)),
                               **_tol(jnp.float32))
    # the same weights through the core forecast: a state whose anchor
    # metadata yields w at step d reproduces the kernel's prediction
    state = {"diffs": diffs, "n_anchors": jnp.asarray(3, jnp.int32),
             "anchor_step": jnp.asarray(0, jnp.int32),
             "gap": jnp.asarray(2.0, jnp.float32)}
    wt = T.prediction_weights(2, 3.0, 2.0, 3)
    want = np.asarray(T.predict(state, 3))
    got = np.asarray(ops.taylor_predict_lanes(
        diffs, jnp.broadcast_to(wt[:, None], (3, feat[2])), lane_axis=2))
    np.testing.assert_allclose(got, want, **_tol(jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [64, 127, 1000, 4096])
def test_verify_error_kernel(n, dtype):
    key = jax.random.PRNGKey(n)
    p = jax.random.normal(key, (3, n), jnp.float32).astype(dtype)
    r = p + 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (3, n)
                                     ).astype(dtype)
    got = ops.verify_error(p, r)
    want = R.verify_error_ref(p.astype(jnp.float32).reshape(3, -1),
                              r.astype(jnp.float32).reshape(3, -1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-5)


def test_verify_error_zero_pred_equals_ref():
    p = jnp.ones((2, 256))
    got = ops.verify_error(p, p)
    np.testing.assert_allclose(np.asarray(got), 0.0, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,block_c", [
    (128, 128),       # single-column grid: init and finalise in one program
    (384, 128),       # multi-column accumulation
    (1024, 256),
    (2048, 1024),
])
def test_verify_sums_matches_unfused_reference(n, block_c, dtype):
    """Fused one-pass sums vs the unfused two-read jnp version."""
    from repro.kernels.verify_error import verify_sums
    key = jax.random.PRNGKey(n + block_c)
    p = jax.random.normal(key, (4, n), jnp.float32).astype(dtype)
    r = (p + 0.1 * jax.random.normal(jax.random.fold_in(key, 1), (4, n))
         ).astype(dtype)
    got = verify_sums(p, r, block_c=block_c, interpret=True)
    pf, rf = p.astype(jnp.float32), r.astype(jnp.float32)
    want = jnp.stack([jnp.sum((pf - rf) ** 2, -1), jnp.sum(rf * rf, -1)],
                     axis=-1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n", [64, 127, 333, 1000])
def test_verify_accept_per_lane_thresholds(n, dtype):
    """The fused τ variant: per-lane err AND accept bit in one pass,
    odd (padded) edges included."""
    key = jax.random.PRNGKey(n)
    B = 6
    p = jax.random.normal(key, (B, n), jnp.float32).astype(dtype)
    r = (p + 0.07 * jax.random.normal(jax.random.fold_in(key, 1), (B, n))
         ).astype(dtype)
    want_err = R.verify_error_ref(p.astype(jnp.float32),
                                  r.astype(jnp.float32))
    # straddle each lane's own error so both outcomes appear
    tau = jnp.asarray(want_err) * jnp.asarray(
        [0.5, 2.0, 0.9, 1.1, 0.0, 10.0])
    err, ok = ops.verify_accept(p, r, tau)
    np.testing.assert_allclose(np.asarray(err), np.asarray(want_err),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-6)
    assert np.array_equal(np.asarray(ok),
                          np.asarray(err) <= np.asarray(tau))
    assert np.asarray(ok).dtype == bool


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_verify_accept_mixed_reduces_to_both_parents(dtype):
    """The slot-width kernel's two degenerate masks ARE the pre-v2
    kernels, bitwise: ``paired`` all-False == ``verify_accept`` (every
    lane on its own stream), all-True == ``verify_accept_pairs`` with
    each pair's value on both of its rows. These equalities are what
    keep the serving API v2 back-compat wrappers trajectory-identical."""
    key = jax.random.PRNGKey(5)
    W, F = 6, 300
    p = jax.random.normal(key, (W, F), jnp.float32).astype(dtype)
    r = (p + 0.05 * jax.random.normal(jax.random.fold_in(key, 1), (W, F))
         ).astype(dtype)
    tau = jnp.asarray([0.01, 0.2, 0.05, 0.5, 10.0, 0.0])
    gs = jnp.asarray([4.0, 4.0, 1.0, 1.0, 7.5, 7.5])
    # all-False == verify_accept
    em, am = ops.verify_accept_mixed(p, r, tau, gs,
                                     jnp.zeros((W,), bool))
    ep, ap = ops.verify_accept(p, r, tau)
    np.testing.assert_array_equal(np.asarray(em), np.asarray(ep))
    np.testing.assert_array_equal(np.asarray(am), np.asarray(ap))
    # all-True == verify_accept_pairs, pair values on both rows (τ must
    # be pair-equal where paired — the engine's fill invariant)
    tau = jnp.repeat(tau[0::2], 2)
    em, am = ops.verify_accept_mixed(p, r, tau, gs,
                                     jnp.ones((W,), bool))
    ep, ap = ops.verify_accept_pairs(p, r, tau[0::2], gs[0::2])
    np.testing.assert_array_equal(np.asarray(em)[0::2], np.asarray(ep))
    np.testing.assert_array_equal(np.asarray(em)[0::2],
                                  np.asarray(em)[1::2])
    np.testing.assert_array_equal(np.asarray(am)[0::2], np.asarray(ap))
    np.testing.assert_array_equal(np.asarray(am)[0::2],
                                  np.asarray(am)[1::2])


def test_verify_accept_mixed_composes_per_slot():
    """A mixed mask == the per-slot composition of the two parents, and
    an odd trailing lane is always unpaired."""
    key = jax.random.PRNGKey(9)
    W, F = 5, 257                       # odd lane count: lane 4 is tail
    p = jax.random.normal(key, (W, F), jnp.float32)
    r = p + 0.03 * jax.random.normal(jax.random.fold_in(key, 1), (W, F))
    tau = jnp.asarray([0.05, 0.05, 0.2, 0.02, 0.5])
    gs = jnp.asarray([3.0, 3.0, 1.0, 1.0, 1.0])
    paired = jnp.asarray([True, True, False, False, False])
    err, ok = ops.verify_accept_mixed(p, r, tau, gs, paired)
    # slot 0 (lanes 0,1): the pair kernel's single decision on both rows
    ep, ap = ops.verify_accept_pairs(p[:2], r[:2], tau[:1], gs[:1])
    np.testing.assert_array_equal(np.asarray(err)[:2],
                                  np.repeat(np.asarray(ep), 2))
    np.testing.assert_array_equal(np.asarray(ok)[:2],
                                  np.repeat(np.asarray(ap), 2))
    # lanes 2..4: per-lane decisions on their own streams
    el, al = ops.verify_accept(p[2:], r[2:], tau[2:])
    np.testing.assert_array_equal(np.asarray(err)[2:], np.asarray(el))
    np.testing.assert_array_equal(np.asarray(ok)[2:], np.asarray(al))


def test_verify_accept_mixed_sharded_width_guard():
    from repro.launch.mesh import make_lane_mesh

    mesh = make_lane_mesh(1)
    key = jax.random.PRNGKey(3)
    p = jax.random.normal(key, (4, 256), jnp.float32)
    r = p + 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (4, 256))
    tau = jnp.full((4,), 0.1)
    gs = jnp.ones((4,))
    paired = jnp.asarray([True, True, False, False])
    ge, ga = ops.verify_accept_mixed_sharded(p, r, tau, gs, paired,
                                             mesh=mesh)
    we, wa = ops.verify_accept_mixed(p, r, tau, gs, paired)
    np.testing.assert_array_equal(np.asarray(ge), np.asarray(we))
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(wa))
    with pytest.raises(ValueError, match="2·D"):
        ops.verify_accept_mixed_sharded(p[:1], r[:1], tau[:1], gs[:1],
                                        paired[:1], mesh=mesh)


@pytest.mark.parametrize("order", [1, 2, 3])
def test_taylor_predict_kernel_matches_core_predict(order):
    """ops.taylor_predict (Pallas, interpret) == core taylor.predict for
    a difference table built by real anchor updates, orders 1-3."""
    from repro.core import taylor as T
    feat = (2, 2, 1, 12, 24)          # (L, 2, B, T, D)
    key = jax.random.PRNGKey(order)
    state = T.init_state(order, feat, jnp.float32)
    for i, s in enumerate(range(0, 4 * (order + 1), 4)):
        f = jax.random.normal(jax.random.fold_in(key, i), feat)
        state = T.update(state, f, s)
    step = int(state["anchor_step"]) + 2
    want = T.predict(state, step)
    w = T.prediction_weights(order, step - state["anchor_step"],
                             state["gap"], state["n_anchors"])
    got = ops.taylor_predict(state["diffs"], w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("K", [1, 3])
@pytest.mark.parametrize("feat,lane_axis", [
    ((2, 2, 3, 13, 24), 2),    # serving layout (L, 2, B, T, D), odd T/D
    ((3, 5, 7), 1),            # odd everything, interior lane axis
    ((6, 129), 0),             # lane-leading, one past the 128 tile
])
def test_taylor_predict_chain_kernel(feat, lane_axis, K, dtype):
    """Fused chain forecast vs the einsum oracle, and per-position
    bitwise equality with the single-step lane kernel: position k of the
    chain must be THE SAME FMA sequence as ``taylor_predict_lanes`` with
    weight column k (the depth-K ≡ iterated depth-1 proof leans on
    this)."""
    m1 = 3
    B = feat[lane_axis]
    key = jax.random.PRNGKey(sum(feat) + K)
    diffs = jax.random.normal(key, (m1,) + feat, jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m1, K, B))
    got = ops.taylor_predict_chain_lanes(diffs, w, lane_axis=lane_axis)
    want = R.taylor_predict_chain_lanes_ref(diffs, w, lane_axis=lane_axis)
    assert got.shape == (K,) + feat and got.dtype == diffs.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))
    for k in range(K):
        single = ops.taylor_predict_lanes(diffs, w[:, k],
                                          lane_axis=lane_axis)
        assert np.array_equal(np.asarray(got[k], np.float32),
                              np.asarray(single, np.float32)), k


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("feat,lane_axis", [
    ((2, 2, 3, 13, 24), 2),
    ((3, 5, 7), 1),
    ((4, 2, 1, 33, 40), 2),
    ((6, 129), 0),
])
def test_lane_rollback_kernel_bitwise(feat, lane_axis, dtype):
    """Snapshot restore is EXACT COPIES — the kernel must match the
    staged jnp oracle bit-for-bit at every dtype (the rollback invariant:
    whichever snapshot a lane's accepted-prefix index selects comes back
    untouched)."""
    K = 3
    B = feat[lane_axis]
    key = jax.random.PRNGKey(sum(feat) + 7)
    chain = jax.random.normal(key, (K + 1,) + feat,
                              jnp.float32).astype(dtype)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (B,), 0, K + 1)
    got = ops.lane_rollback(chain, idx, lane_axis=lane_axis)
    want = R.lane_rollback_ref(chain, idx, lane_axis=lane_axis)
    assert got.shape == feat and got.dtype == chain.dtype
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    # each lane really is the selected snapshot, bit-for-bit
    gm = np.moveaxis(np.asarray(got, np.float32), lane_axis, 0)
    cm = np.moveaxis(np.asarray(chain, np.float32), lane_axis + 1, 1)
    for b in range(B):
        assert np.array_equal(gm[b], cm[int(idx[b])][b])


def test_chain_kernels_jnp_backend_and_sharded_wrappers():
    """The ``REPRO_TABLE_BACKEND=jnp`` oracle path of
    ``taylor.predict_chain_lanes`` agrees with the kernel path (allclose:
    einsum vs FMA), ``taylor.lane_rollback`` is bitwise across backends
    (copies are copies), and both 1-device shard_map wrappers ARE their
    unsharded kernels bit-for-bit (the D=4 case runs in the
    ``tests/test_draft_k.py`` subprocess)."""
    from repro.core import taylor as T
    from repro.launch.mesh import make_lane_mesh

    order, feat, lane_axis = 2, (2, 2, 4, 12, 24), 2
    B = feat[lane_axis]
    key = jax.random.PRNGKey(11)
    state = T.init_state(order, feat, jnp.float32, lanes=B)
    state["diffs"] = jax.random.normal(key, (order + 1,) + feat)
    state["n_anchors"] = jnp.full((B,), order + 2, jnp.int32)
    state["anchor_step"] = jnp.arange(B, dtype=jnp.int32)
    steps = state["anchor_step"][None, :] + 1 + jnp.arange(3)[:, None]
    got = T.predict_chain_lanes(state, steps, backend="kernel")
    want = T.predict_chain_lanes(state, steps, backend="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    chain = jax.random.normal(jax.random.fold_in(key, 1), (4,) + feat)
    idx = jnp.asarray([0, 3, 1, 2])
    assert np.array_equal(
        np.asarray(T.lane_rollback(chain, idx, backend="kernel")),
        np.asarray(T.lane_rollback(chain, idx, backend="jnp")))

    mesh = make_lane_mesh(1)
    w = jax.random.normal(jax.random.fold_in(key, 2), (order + 1, 3, B))
    assert np.array_equal(
        np.asarray(ops.taylor_predict_chain_lanes_sharded(
            state["diffs"], w, mesh=mesh, lane_axis=lane_axis)),
        np.asarray(ops.taylor_predict_chain_lanes(
            state["diffs"], w, lane_axis=lane_axis)))
    assert np.array_equal(
        np.asarray(ops.lane_rollback_sharded(chain, idx, mesh=mesh,
                                             lane_axis=lane_axis)),
        np.asarray(ops.lane_rollback(chain, idx, lane_axis=lane_axis)))


def test_chain_kernel_bf16_table_quantisation_bounded():
    """bf16 difference tables through the chain kernel: f32 accumulation
    keeps every chain position within bf16 rounding of the f32-table
    forecast, and the bf16 rollback is still exact copies."""
    m1, K, feat, lane_axis = 3, 3, (2, 2, 3, 13, 24), 2
    B = feat[lane_axis]
    key = jax.random.PRNGKey(17)
    diffs = jax.random.normal(key, (m1,) + feat, jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m1, K, B))
    got = ops.taylor_predict_chain_lanes(diffs.astype(jnp.bfloat16), w,
                                         lane_axis=lane_axis)
    want = ops.taylor_predict_chain_lanes(diffs, w, lane_axis=lane_axis)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)
    chain = jax.random.normal(jax.random.fold_in(key, 2),
                              (K + 1,) + feat).astype(jnp.bfloat16)
    idx = jnp.asarray([2, 0, 3])
    got = ops.lane_rollback(chain, idx, lane_axis=lane_axis)
    want = R.lane_rollback_ref(chain, idx, lane_axis=lane_axis)
    assert got.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,h,hd,causal,window", [
    (64, 2, 32, True, 0),
    (64, 2, 32, True, 16),
    (128, 4, 64, True, 0),
    (64, 2, 32, False, 0),
    (96, 1, 16, True, 8),
])
def test_flash_attention_kernel(s, h, hd, causal, window, dtype):
    key = jax.random.PRNGKey(s + h)
    q = jax.random.normal(key, (2, s, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, s, h, hd)
                          ).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, s, h, hd)
                          ).astype(dtype)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              block_q=32, block_k=32)
    want = R.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_flash_matches_model_attention_path():
    """use_flash=True in the backbone gives the same attention output."""
    from repro.layers import attention as A
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 64, 4, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 64, 2, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 64, 2, 32))
    naive = A.full_attention(q, k, v, 0)
    flash = A.full_attention(q, k, v, 0, use_flash=True)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(naive),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Spectral forecaster kernels: raw-anchor ring-shift + shared contraction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("feat,lane_axis", [
    ((2, 2, 3, 13, 24), 2),    # serving layout (L, 2, B, T, D), odd T/D
    ((3, 5, 7), 1),            # odd everything, interior lane axis
    ((4, 2, 1, 33, 40), 2),    # single lane
    ((6, 129), 0),             # lane-leading, one past the 128 tile
])
def test_spectral_update_lanes_kernel_bitwise(feat, lane_axis, dtype):
    """The masked ring-shift refresh is BIT-IDENTICAL to the staged
    (concatenate + where) oracle — refreshed lanes shift their ring one
    row (newest anchor in, oldest out), masked-out lanes pass through
    untouched. Exact copies at every dtype."""
    m1 = 4
    B = feat[lane_axis]
    key = jax.random.PRNGKey(sum(feat) + 13)
    ring = jax.random.normal(key, (m1,) + feat, jnp.float32).astype(dtype)
    feats = jax.random.normal(jax.random.fold_in(key, 1), feat,
                              jnp.float32).astype(dtype)
    mask = jnp.asarray([i % 2 == 0 for i in range(B)])
    got = ops.spectral_update_lanes(ring, feats, mask, lane_axis=lane_axis)
    want = R.spectral_update_lanes_ref(ring, feats, mask,
                                       lane_axis=lane_axis)
    assert got.shape == ring.shape and got.dtype == ring.dtype
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))
    gm = np.moveaxis(np.asarray(got, np.float32), lane_axis + 1, 1)
    rm = np.moveaxis(np.asarray(ring, np.float32), lane_axis + 1, 1)
    fm = np.moveaxis(np.asarray(feats, np.float32), lane_axis, 0)
    for b in range(B):
        if bool(mask[b]):
            # row 0 = new anchor, row i = old row i-1, oldest dropped
            assert np.array_equal(gm[0, b], fm[b])
            assert np.array_equal(gm[1:, b], rm[:-1, b])
        else:
            assert np.array_equal(gm[:, b], rm[:, b])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("feat,lane_axis", [
    ((2, 2, 3, 13, 24), 2),
    ((3, 5, 7), 1),
    ((6, 129), 0),
])
def test_spectral_predict_lanes_kernel_vs_oracle(feat, lane_axis, dtype):
    """The spectral prediction is the SAME fused per-lane contraction
    the Taylor kernels run (only the weight columns differ), and the
    spectral jnp oracle replays the kernel's sequential f32 accumulation
    order — agreement is at multiply-add FUSION rounding (XLA may
    contract mul+add into an FMA: ≤1 ulp per term), orders tighter than
    the einsum Taylor oracle's reduction-order tolerance."""
    m1 = 4
    B = feat[lane_axis]
    key = jax.random.PRNGKey(sum(feat) + 29)
    ring = jax.random.normal(key, (m1,) + feat, jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m1, B))
    got = ops.spectral_predict_lanes(ring, w, lane_axis=lane_axis)
    want = R.spectral_predict_lanes_ref(ring, w, lane_axis=lane_axis)
    assert got.shape == feat and got.dtype == ring.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("K", [1, 3])
def test_spectral_predict_chain_position_k_is_single_step(K):
    """Chain position k through the spectral kernel surface is the SAME
    FMA sequence as the single-step kernel with weight column k
    (BITWISE — both run the one kernel program), and the chain oracle
    tracks the chain kernel to multiply-add fusion rounding."""
    m1, feat, lane_axis = 3, (2, 2, 3, 13, 24), 2
    B = feat[lane_axis]
    key = jax.random.PRNGKey(K + 41)
    ring = jax.random.normal(key, (m1,) + feat, jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m1, K, B))
    got = ops.spectral_predict_chain_lanes(ring, w, lane_axis=lane_axis)
    want = R.spectral_predict_chain_lanes_ref(ring, w,
                                              lane_axis=lane_axis)
    assert got.shape == (K,) + feat
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    for k in range(K):
        single = ops.spectral_predict_lanes(ring, w[:, k],
                                            lane_axis=lane_axis)
        assert np.array_equal(np.asarray(got[k]), np.asarray(single)), k


def test_spectral_bf16_table_quantisation_bounded():
    """bf16 raw-anchor rings: the contraction accumulates in f32, so a
    bf16 ring's prediction stays within bf16 rounding of the f32-ring
    prediction, and the bf16 ring-shift is still exact copies."""
    m1, feat, lane_axis = 4, (2, 2, 3, 13, 24), 2
    B = feat[lane_axis]
    key = jax.random.PRNGKey(53)
    ring = jax.random.normal(key, (m1,) + feat, jnp.float32)
    w = jax.random.normal(jax.random.fold_in(key, 1), (m1, B))
    got = ops.spectral_predict_lanes(ring.astype(jnp.bfloat16), w,
                                     lane_axis=lane_axis)
    want = ops.spectral_predict_lanes(ring, w, lane_axis=lane_axis)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)
    feats = jax.random.normal(jax.random.fold_in(key, 2), feat)
    mask = jnp.asarray([True, False, True])
    got = ops.spectral_update_lanes(ring.astype(jnp.bfloat16),
                                    feats.astype(jnp.bfloat16), mask,
                                    lane_axis=lane_axis)
    want = R.spectral_update_lanes_ref(ring.astype(jnp.bfloat16),
                                       feats.astype(jnp.bfloat16), mask,
                                       lane_axis=lane_axis)
    assert got.dtype == jnp.bfloat16
    assert np.array_equal(np.asarray(got, np.float32),
                          np.asarray(want, np.float32))


def test_spectral_weights_semantics():
    """The frequency-band weights: exactly-at-anchor (d=0) selects the
    newest ring row; rows beyond a lane's anchor history get weight 0;
    ``order_cap`` masks high bands so a capped lane's weights change
    while an uncapped lane's are untouched."""
    from repro.core.forecaster import spectral_weights
    order = 3
    gap = jnp.full((4,), 2.0)
    n_anchors = jnp.asarray([5, 2, 5, 5], jnp.int32)
    w0 = spectral_weights(order, jnp.zeros((4,), jnp.int32), gap,
                          n_anchors)
    assert w0.shape == (order + 1, 4)
    np.testing.assert_allclose(np.asarray(w0[0]), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w0[1:, 0]), 0.0, atol=1e-6)
    # lane 1 has only 2 anchors: rows >= 2 are EXACTLY zero at any d
    wd = spectral_weights(order, jnp.full((4,), 3, jnp.int32), gap,
                          n_anchors)
    assert np.all(np.asarray(wd[2:, 1]) == 0.0)
    assert np.any(np.asarray(wd[1:, 0]) != 0.0)
    # order_cap: capped lane's weights differ, uncapped lane's bitwise
    cap = jnp.asarray([0, 3, 3, 3], jnp.int32)
    wc = spectral_weights(order, jnp.full((4,), 3, jnp.int32), gap,
                          n_anchors, order_cap=cap)
    assert not np.array_equal(np.asarray(wc[:, 0]), np.asarray(wd[:, 0]))
    assert np.array_equal(np.asarray(wc[:, 2:]), np.asarray(wd[:, 2:]))


def test_spectral_sharded_wrappers_bitwise_d1():
    """The 1-device shard_map wrappers of the spectral kernel surface
    ARE their unsharded kernels bit-for-bit (D ∈ {2, 4} runs in the
    ``tests/test_forecaster_seam.py`` subprocess)."""
    from repro.launch.mesh import make_lane_mesh

    mesh = make_lane_mesh(1)
    m1, feat, lane_axis = 3, (2, 2, 4, 12, 24), 2
    B = feat[lane_axis]
    key = jax.random.PRNGKey(61)
    ring = jax.random.normal(key, (m1,) + feat, jnp.float32)
    feats = jax.random.normal(jax.random.fold_in(key, 1), feat)
    mask = jnp.asarray([True, False, True, False])
    assert np.array_equal(
        np.asarray(ops.spectral_update_lanes_sharded(
            ring, feats, mask, mesh=mesh, lane_axis=lane_axis)),
        np.asarray(ops.spectral_update_lanes(ring, feats, mask,
                                             lane_axis=lane_axis)))
    w = jax.random.normal(jax.random.fold_in(key, 2), (m1, B))
    assert np.array_equal(
        np.asarray(ops.spectral_predict_lanes_sharded(
            ring, w, mesh=mesh, lane_axis=lane_axis)),
        np.asarray(ops.spectral_predict_lanes(ring, w,
                                              lane_axis=lane_axis)))
    wc = jax.random.normal(jax.random.fold_in(key, 3), (m1, 2, B))
    assert np.array_equal(
        np.asarray(ops.spectral_predict_chain_lanes_sharded(
            ring, wc, mesh=mesh, lane_axis=lane_axis)),
        np.asarray(ops.spectral_predict_chain_lanes(
            ring, wc, lane_axis=lane_axis)))


def test_spectral_forecaster_jnp_backend_parity(monkeypatch):
    """REPRO_TABLE_BACKEND=jnp routes the SpectralForecaster through the
    pure-jnp oracles: the masked ring update agrees BITWISE (exact
    copies), predictions to multiply-add fusion rounding (the oracles
    replay the kernel's sequential f32 accumulation order)."""
    from repro.core.forecaster import SpectralForecaster

    fc = SpectralForecaster()
    order, feat = 2, (2, 2, 4, 12, 24)
    B = feat[2]
    key = jax.random.PRNGKey(67)
    tstate = fc.init_state(order, feat, jnp.float32, lanes=B)
    tstate["diffs"] = jax.random.normal(key, (order + 1,) + feat)
    tstate["n_anchors"] = jnp.asarray([3, 1, 4, 2], jnp.int32)
    tstate["anchor_step"] = jnp.asarray([4, 6, 2, 0], jnp.int32)
    tstate["gap"] = jnp.full((B,), 2.0)
    steps = jnp.asarray([6, 7, 5, 3], jnp.int32)
    chain = tstate["anchor_step"][None] + 1 + jnp.arange(3)[:, None]
    feats = jax.random.normal(jax.random.fold_in(key, 1), feat)
    mask = jnp.asarray([True, False, True, False])
    outs = {}
    for backend in ("kernel", "jnp"):
        monkeypatch.setenv("REPRO_TABLE_BACKEND", backend)
        outs[backend] = (
            fc.predict_lanes(tstate, steps),
            fc.predict_chain_lanes(tstate, chain),
            fc.update_lanes(tstate, feats, steps, mask))
    for i, (a, b) in enumerate(zip(outs["kernel"], outs["jnp"])):
        ka, kb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        for la, lb in zip(ka, kb):
            if i < 2:  # predictions: FMA-contraction rounding only
                np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                           rtol=1e-6, atol=1e-6)
            else:  # update: exact copies
                assert np.array_equal(np.asarray(la), np.asarray(lb))

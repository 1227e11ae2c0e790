"""The ONE forecast-then-verify step (paper §3.2–3.4) over a lane batch.

Every SpeCa execution path — the reproduction sampler
(``repro.core.speca.speca_sample``, where the sample batch is the lane
batch), the batch=1 serving reference (``SpeCaEngine.run_request``, the
lanes=1 degenerate case) and the lane scheduler
(``SpeCaEngine.serve_batched`` / the v2 submit-poll lifecycle) — advances
its state through the step function built here. There is deliberately no
second implementation of the accept/refresh logic anywhere in the tree:
the four hand-copied variants that previously lived in ``speca.py`` (both
scan bodies) and ``engine.py`` (``_build`` + ``_build_lane_step``) are
collapsed into this module, so a semantics change (or bugfix) is a
single-site edit.

The loop itself is *workload-agnostic*: everything diffusion-specific —
what the dynamic payload is (the latent ``x``), how it advances on a
model output (the ``rf_euler_step`` sampler update), the
timestep-indexed τ schedule and the verify-layer forward — lives behind
the ``Workload`` adapter (``repro.core.workload``). ``build_workload_step``
builds the generic step for any adapter; ``build_lane_step`` /
``init_lane_state`` are the original diffusion entry points, now thin
wrappers over a ``DiffusionWorkload`` instance (bitwise the same trace —
the adapter hooks inline to exactly the pre-seam expressions). The
``DecodeWorkload`` adapter drives the SAME loop for self-speculative LLM
decoding: the payload is (input token, emitted-token buffer, KV/SSM
caches), advance is argmax-emit + cache write, and τ_t is constant at τ0.

One step, entirely inside the traced function:

  1. *Draft* (``lax.cond``, runs iff ANY lane is warm and under its draft
     budget): ``taylor.predict_lanes`` forecasts every lane's residual
     increments from its own anchor through the fused per-lane Pallas
     kernel, and the backbone executes with compute masked to the verify
     layer.
  2. *Verify*: each lane's relative error against its own τ_t — either the
     fused one-pass Pallas kernel (``verify_backend="fused"``, rel-L2
     only) or the metric-general jnp path. Every lane's τ_t comes from
     the per-lane ``tau0`` state vector (serving API v2: each request
     carries its own verification strictness), τ_t = τ0·β^((T−t)/T).
  3. *Accept combiner*: ``per_sample`` accepts each lane on its own bit;
     ``batch`` (reproduction parity) accepts iff every currently-drafting
     lane passes.
  4. *Masked refresh* (``lax.cond``, runs iff ANY active lane rejected):
     the full forward serves the rejected lanes and
     ``taylor.update_lanes`` refreshes only their table slices through the
     one-pass masked kernel; accepted lanes advance on the speculative
     output via a per-lane select.

Each phase runs under a ``jax.named_scope``, which the compiled ops keep
in their ``op_name`` metadata, so a device profile splits the step:
``speca.draft`` (the forecast and the verify-layer forward),
``speca.verify`` inside it (the verify-layer forward and the error
check), ``speca.full`` (the full forward), ``speca.update`` inside it
(the table refresh) and, for deep chains, ``speca.rollback`` (the
snapshot stack and restore). Scopes change no value and no operation.

State layout (all device-side; the host never has to read any of it to
decide the next dispatch). Shared, workload-independent keys:

  ``since``    [W] i32  consecutive accepted drafts since the last anchor
  ``step``     [W] i32  the lane's schedule step index
  ``active``   [W] bool lane occupancy (inactive lanes are frozen)
  ``tau0``     [W] f32  per-lane base verification threshold (filled from
                ``SpeCaConfig.tau0`` or the request's ``RequestPolicy``)
  ``diffs``    [m+1, L, 2, W, T, D] TaylorSeer difference table
  ``n_anchors``/``anchor_step``/``gap`` [W] per-lane anchor metadata
                (``taylor.init_state(lanes=W)``)
  ``draft_k``  [W] i32  per-lane draft horizon K (requests carry their own
                depth via ``RequestPolicy.draft_depth``; evaluated
                per-lane inside the traced chain like ``tau0``)
  ``max_step`` [W] i32  the lane's schedule length — a drafted chain never
                advances a lane past its final step

Per-workload payload keys (``Workload.dyn_keys`` — threaded through the
step, snapshotted by draft-K chains and restored by rollback):

  diffusion: ``x`` [W, …] latents (lane axis 0), plus ``cond``
             {k: [W, …]} conditioning rows and — pair modes only —
             ``gscale`` [W] f32 / ``paired`` [W] bool
  decode:    ``tok`` [W, 1] i32 current input token, ``tokens`` [W, S]
             i32 emitted-token buffer, ``k``/``v`` [L, W, S, kv, hd] and
             ``ssm_state``/``conv_state`` [L, W, …] caches (lane axis 1),
             plus the static ``pos0`` [W] i32 prompt length

Deep speculation (``max_draft_depth`` > 1) replaces the single
draft-verify round with a drafted CHAIN of up to ``K = max_draft_depth``
positions per tick (speculative-decoding style γ>1 drafting):

  1. ONE fused chain-forecast kernel extrapolates every lane's table to
     all K chain steps in a single table pass
     (``kernels.ops.taylor_predict_chain_lanes``).
  2. Position by position, lanes still alive in the chain verify their
     forecast exactly as the depth-1 step does (same masked verify-layer
     forward, same τ_t schedule at the position's step) and the payload
     advances speculatively; a lane leaves the chain the first time a
     position is rejected (→ served by the closing full forward) or its
     per-lane budget ``min(draft_k, max_step − step)`` runs out (→ stops
     clean at its accepted frontier).
  3. The accepted steps therefore always form a PREFIX of the drafted
     chain — position j only runs for lanes that accepted 0..j−1.
  4. *Rollback*: payload leaves advanced blindly during the chain are
     restored per lane to the snapshot at its accepted-prefix length
     through the exact-copy rollback kernel
     (``kernels.ops.lane_rollback``; integer leaves — decode token
     buffers — roll back through an equivalent jnp gather); ONE closing
     full forward then serves every rejected lane at its rolled-back
     step and refreshes only those lanes' table slices.

With every lane at ``draft_k = 1`` the chain is the legacy step: position
0 is the depth-1 draft/verify math term for term, and the closing full is
the legacy masked refresh — ``max_draft_depth=1`` builds the original
single-round program, byte-for-byte the same trace.

Classifier-free guidance packs one *request* into a lane **pair**: the
conditional stream at lane ``2k``, the unconditional (or negative-prompt)
stream at lane ``2k+1``. Both lanes share the SAME latent trajectory and
draft/verify together, but each keeps its own difference table (the two
feature streams are forecast independently). The verify residual is
computed on the guided combination ``u + s·(c − u)`` at the verify layer
and a single accept/reject decision drives both lanes, so the pair's
anchors can never de-synchronize — see ``docs/cfg.md`` for why one
decision per pair is required for anchor coherence. Pairing exists only
for workloads that declare ``supports_pairing`` (diffusion); guided
decode requests are rejected at policy resolution.

``guidance`` selects among three step programs:

  * ``False`` — no pair machinery at all: every lane is an independent
    unguided stream (the plain serving engine and unguided sampler).
  * ``True``  — every pair slot is a guided pair (``paired`` initialises
    all-True): the guided sampler's mode, and the engine's back-compat
    ``guidance=True`` construction.
  * ``"mixed"`` — slot-width serving (API v2): lanes (2k, 2k+1) form
    *pair slots* and the per-lane ``paired`` mask (pair-equal, written
    at fill time by the engine) decides slot by slot — a ``paired``
    slot is one guided request with ONE guided-residual decision; an
    unpaired slot is up to two independent unguided lanes, each with
    its own decision. Guided and unguided requests thereby mix freely
    in one batch. ``paired`` initialises all-False; with every slot
    paired the step is value-identical to ``guidance=True``, and with
    none paired it is value-identical to ``guidance=False`` — both
    equivalences are what keep the serving back-compat wrappers
    trajectory-identical. A trailing odd lane (odd ``lanes``, meshless
    only) is always unpaired.

Pair invariants (established by the engine's fill and preserved by every
step): ``x``/``since``/``step``/``active``/``gscale``/``tau0``/``paired``
are equal across the two lanes of a *paired* slot.

Flags returned per tick (all [W] unless noted): ``attempted`` (the lane
drafted — chain position 0), ``ok`` (position 0 passed its τ),
``accepted`` (position-0 post-combiner decision), ``full`` (the lane was
served by the full forward), ``err`` (position-0 verification error, NaN
where the lane did not draft — see the sentinel semantics in
``speca_sample``), ``tau`` (position-0 threshold) — the legacy keys keep
their depth-1 [W] shapes so every existing consumer reads them unchanged.
Depth-aware counters: ``n_spec`` i32 (accepted drafted steps this tick),
``n_drafted`` i32 (drafted positions this tick — the per-drafted-step
accounting denominator), ``advanced`` i32 (``n_spec`` + served-by-full —
total schedule steps the lane moved this tick). Chain detail (shape
[K, W]): ``chain_attempted``/``chain_accepted`` bool,
``chain_err``/``chain_tau`` f32. In a paired slot every flag is
pair-equal: both lanes report the pair's single decision and the pair's
guided-residual error.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.configs.base import DiffusionConfig, ModelConfig, SpeCaConfig
from repro.core import controller as _ctl
from repro.core import taylor
from repro.core.forecaster import get_forecaster
from repro.core.verify import relative_error, threshold_schedule
from repro.diffusion.pipeline import guided_output

ACCEPT_MODES = ("batch", "per_sample")
VERIFY_BACKENDS = ("fused", "jnp")
GUIDANCE_MODES = (False, True, "mixed")

# The per-tick flag keys engine accounting (and the observability
# accumulator — repro.obs.lane_metrics) consumes: every [W] counter a
# completed request's harvest materialises. One exported tuple so the
# engine's completion fetch and the telemetry layer can never read
# different layouts of the same flags dict.
COUNTER_FLAGS = ("attempted", "accepted", "full",
                 "n_spec", "n_drafted", "advanced")


def verify_layer(cfg: ModelConfig, scfg: SpeCaConfig) -> int:
    """Resolved verify-layer index (negative config values wrap)."""
    return scfg.verify_layer % cfg.num_layers


def num_tokens(cfg: ModelConfig, dcfg: DiffusionConfig) -> int:
    """Backbone sequence length: patches per frame × frames."""
    per_frame = (dcfg.latent_size // cfg.patch_size) ** 2
    return per_frame * max(dcfg.num_frames, 1)


def table_dtype(cfg: ModelConfig, scfg: SpeCaConfig):
    """Difference-table dtype: ``scfg.table_dtype`` override or the model
    dtype (bf16 tables halve storage; regression pinned in tests)."""
    if not scfg.table_dtype:
        return cfg.jnp_dtype
    try:
        return jnp.dtype(scfg.table_dtype)
    except TypeError as e:
        raise ValueError(
            f"SpeCaConfig.table_dtype={scfg.table_dtype!r} is not a "
            "dtype (use e.g. 'bfloat16' or '' for the model dtype)"
        ) from e


def _check_guidance(guidance: Union[bool, str], lanes: int) -> None:
    if guidance not in GUIDANCE_MODES:
        raise ValueError(f"unknown guidance mode {guidance!r} "
                         f"(have {GUIDANCE_MODES})")
    if guidance is True and lanes % 2 != 0:
        raise ValueError(f"guidance mode packs lane PAIRS: lanes={lanes} "
                         "must be even")


def init_workload_state(wl, lanes: int, cond_template: Dict[str, Any], *,
                        x: Optional[jnp.ndarray] = None,
                        active: bool = False,
                        guidance: Union[bool, str] = False,
                        forecaster: Optional[Any] = None,
                        controller: bool = False,
                        mesh: Optional[Any] = None) -> Dict[str, Any]:
    """Fresh lane-batch state for any ``Workload`` adapter.

    The shared keys (``since``/``step``/``active``/``tau0``/``draft_k``/
    ``max_step`` and the TaylorSeer table) are laid out identically for
    every workload; the adapter contributes its dynamic payload through
    ``wl.init_payload`` and decides whether per-lane conditioning rides
    in state (``wl.cond_in_state`` — diffusion) or is consumed host-side
    at fill time (decode prompts → prefill).

    ``tau0`` initialises to ``SpeCaConfig.tau0`` for every lane; the
    serving engine overwrites a lane's entry at fill time when its
    request carries a per-request τ policy.

    ``guidance=True`` adds the per-lane ``gscale`` vector (all ones until
    a request is filled) and the ``paired`` mask initialised all-True
    (every slot is a guided pair), and requires an even ``lanes`` — lanes
    ``2k``/``2k+1`` form the cond/uncond pair of one request.
    ``guidance="mixed"`` initialises ``paired`` all-False instead: pair
    slots switch between guided-pair and independent-lane semantics as
    the engine fills them. Pair modes require ``wl.supports_pairing``.

    With ``mesh`` every lane-indexed array is placed with its
    ``NamedSharding`` from the lane-axis rules in
    ``repro.sharding.specs`` — the difference table, decode caches and
    all per-lane vectors shard their lane axis over the mesh's ``'data'``
    axis, so a D-device mesh holds 1/D of the table per device. ``lanes``
    must then be divisible by the lane-shard count — and in any
    pair-capable mode by ``2 × lane_shard_count`` so a pair slot never
    straddles a shard boundary (the guided combination is a cross-lane op
    inside the pair; keeping pairs shard-local keeps it
    communication-free).

    ``forecaster`` selects the feature-forecast table implementation (a
    name or ``repro.core.forecaster.Forecaster`` instance; ``None`` →
    Taylor — bitwise the pre-seam state). ``controller=True`` adds the
    all-off closed-loop controller vectors
    (``repro.core.controller.CONTROLLER_KEYS``, all [W]) so a
    controller-capable step program can read them; they too shard their
    lane axis under ``mesh``.
    """
    W = lanes
    _check_guidance(guidance, W)
    pairing = bool(guidance)
    if pairing and not wl.supports_pairing:
        raise ValueError(f"workload {wl.tag!r} does not support guided "
                         "lane pairs")
    fc = get_forecaster(forecaster)
    feat_shape = taylor.feature_shape_for(wl.cfg.num_layers, W,
                                          wl.num_tokens, wl.cfg.d_model)
    tstate = fc.init_state(wl.scfg.taylor_order, feat_shape,
                           wl.table_dtype, lanes=W)
    if wl.cond_in_state:
        # copies, never the caller's arrays: the engine donates the state
        cond = {k: jnp.array(jnp.broadcast_to(jnp.asarray(v),
                                              (W,) + jnp.shape(v)[1:]))
                for k, v in cond_template.items()}
    else:
        cond = {}
    state = {
        "since": jnp.zeros((W,), jnp.int32),
        "step": jnp.zeros((W,), jnp.int32),
        "active": jnp.full((W,), bool(active)),
        "tau0": jnp.full((W,), float(wl.scfg.tau0), jnp.float32),
        # per-lane draft horizon (RequestPolicy.draft_depth at fill time)
        # and schedule length — both read only by depth-K chain steps
        "draft_k": jnp.ones((W,), jnp.int32),
        "max_step": jnp.full((W,), wl.num_steps, jnp.int32),
        "cond": cond,
        **wl.init_payload(W, x=x),
        **tstate,
    }
    if pairing:
        state["gscale"] = jnp.ones((W,), jnp.float32)
        state["paired"] = jnp.full((W,), guidance is True)
    if controller:
        state.update(_ctl.init_controller_state(W, wl.scfg.taylor_order))
    if mesh is not None:
        from repro.sharding import specs as SH
        mult = SH.lane_width_multiple(mesh, streams=2 if pairing else 1)
        if W % mult != 0:
            raise ValueError(
                f"lanes={W} not divisible by {mult} (lane-shard count "
                f"{SH.lane_shard_count(mesh)}"
                + (" × 2 streams — a pair slot must never straddle a "
                   "shard boundary)" if pairing else ")"))
        state = jax.device_put(state, SH.lane_state_shardings(mesh, state))
    return state


def init_lane_state(cfg: ModelConfig, dcfg: DiffusionConfig,
                    scfg: SpeCaConfig, lanes: int,
                    cond_template: Dict[str, Any], *,
                    x: Optional[jnp.ndarray] = None,
                    active: bool = False,
                    guidance: Union[bool, str] = False,
                    mesh: Optional[Any] = None) -> Dict[str, Any]:
    """Fresh DIFFUSION lane-batch state (the original entry point —
    ``init_workload_state`` over a ``DiffusionWorkload``).
    ``cond_template`` supplies per-key shapes (leading axis is replaced
    by ``lanes``); pass ``x`` to start from a concrete latent (the
    sampler) instead of zeros (the scheduler)."""
    from repro.core.workload import DiffusionWorkload
    wl = DiffusionWorkload(cfg, params=None, dcfg=dcfg, scfg=scfg)
    return init_workload_state(wl, lanes, cond_template, x=x,
                               active=active, guidance=guidance, mesh=mesh)


# What every filled lane starts from besides its request's own values: an
# empty table with fresh anchor metadata, step 0, active.
LANE_START = {"diffs": 0, "n_anchors": 0, "anchor_step": -1, "gap": 1.0,
              "since": 0, "step": 0, "active": True}


def write_lanes(state: Dict[str, Any], lane, rows: Dict[str, Any], *,
                count: int = 1,
                mesh: Optional[Any] = None) -> Dict[str, Any]:
    """Write ``rows`` into lanes ``lane .. lane + count - 1`` of ``state``.

    Traceable: ``lane`` may be a traced scalar, so one compiled program
    writes any lane. Each row is broadcast to its leaf's lane block (the
    leaf's shape with its lane axis, ``repro.sharding.specs.
    LANE_STATE_AXES``, cut to ``count``) and cast to the leaf's dtype —
    the values ``state[key].at[lane].set(row)`` writes. ``rows["cond"]``
    is a dict over the conditioning keys (lane axis 0). Leaves without a
    row pass through.

    Each write is a ``dynamic_update_slice`` on the lane axis: jitted
    with the state donated it runs in place, at the cost of the block's
    bytes. With ``mesh`` (a lane-sharded state) the writes run in a
    ``shard_map`` over the lane axis, the rows entering replicated: each
    shard rewrites one block of its own lanes, with the new rows where
    the lane is its own and its current contents elsewhere, so no
    collective is emitted. (An eager ``.at[lane].set`` at a traced index
    gathers the whole leaf; left to the partitioner, a row computed in
    the program, such as the seed's noise, is computed sharded and
    all-reduced.) The block must lie within one shard, which the lane
    width rules guarantee for a lane and for a pair slot.
    """
    from repro.sharding import specs as SH
    paths = [(k,) for k in rows if k != "cond"] \
        + [("cond", k) for k in rows.get("cond", {})]

    def at(tree, path):
        for p in path:
            tree = tree[p]
        return tree

    leaves = [at(state, p) for p in paths]
    vals = [jnp.asarray(at(rows, p)) for p in paths]
    axes = [0 if p[0] == "cond" else SH.LANE_STATE_AXES[p[0]]
            for p in paths]

    def put(leaves, vals, start, own):
        out = []
        for leaf, val, ax in zip(leaves, vals, axes):
            shape = leaf.shape[:ax] + (count,) + leaf.shape[ax + 1:]
            block = jnp.broadcast_to(val.astype(leaf.dtype), shape)
            if own is not None:
                block = jnp.where(own, block, jax.lax.dynamic_slice_in_dim(
                    leaf, start, count, ax))
            out.append(jax.lax.dynamic_update_slice_in_dim(
                leaf, block, start, ax))
        return out

    lane = jnp.asarray(lane, jnp.int32)
    if mesh is None:
        new = put(leaves, vals, lane, None)
    else:
        def local(leaves, vals, lane):
            n = leaves[0].shape[axes[0]]        # lanes on this shard
            base = jax.lax.axis_index(SH.LANE_AXIS) * n
            own = (lane >= base) & (lane < base + n)
            return put(leaves, vals,
                       jnp.clip(lane - base, 0, n - count), own)

        P = jax.sharding.PartitionSpec
        specs = [SH.lane_spec(leaf.ndim, ax)
                 for leaf, ax in zip(leaves, axes)]
        new = jax.shard_map(local, mesh=mesh,
                            in_specs=(specs, [P()] * len(vals), P()),
                            out_specs=specs, check_vma=False)(
                                leaves, vals, lane)
    out = dict(state)
    if "cond" in rows:
        out["cond"] = dict(state["cond"])
    for path, leaf in zip(paths, new):
        at(out, path[:-1])[path[-1]] = leaf
    return out


def build_workload_step(wl, *, lanes: int, draft_mode: str = "taylor",
                        accept_mode: str = "per_sample",
                        verify_backend: str = "jnp",
                        guidance: Union[bool, str] = False,
                        max_draft_depth: int = 1,
                        forecaster: Optional[Any] = None,
                        controller: bool = False,
                        mesh: Optional[Any] = None
                        ) -> Callable[[Dict[str, Any]],
                                      Tuple[Dict[str, Any], Dict[str, Any]]]:
    """Build the traced lane step for a ``Workload``: ``state -> (state,
    flags)``.

    Not jitted here — the sampler scans it inside one XLA program, the
    engine jits it per (workload, lane width).

    ``guidance`` selects the step program (see the module docstring):
    ``False`` is plain per-lane serving, ``True`` forces every pair slot
    guided (state from ``init_workload_state(..., guidance=True)``), and
    ``"mixed"`` reads the per-lane ``paired`` mask so guided pairs and
    independent unguided lanes share one batch. Pair modes require
    ``wl.supports_pairing``. In the pair modes lanes ``2k``/``2k+1``
    form slot k: where paired, both streams draft through their own
    tables in the same dispatch, verification compares the *guided*
    residual ``u + s·(c − u)`` at the verify layer against the pair's τ
    (one decision per pair — ``kernels.ops.verify_accept_mixed``), and
    the latent advances on the guided model output, identically for both
    lanes; a rejected pair's full forward refreshes BOTH lanes' table
    slices, so cond and uncond anchors stay in lock-step by
    construction. Where unpaired, each lane drafts, verifies and
    advances on its own stream exactly as in the plain program.

    ``mesh`` shards the lane axis over the mesh's ``'data'`` axis: the
    backbone, threshold schedule and lane selects partition natively
    under GSPMD (per-lane math is lane-independent), while the Pallas
    table/verify kernels — opaque custom calls the partitioner would
    otherwise gather — are routed through their ``shard_map`` wrappers so
    each shard runs the existing lane-masked kernel on its local lane
    block (those kernels are bit-identical per shard). Accept/reject
    sequences and all counters are exactly those of the unsharded step;
    latents agree to f32 reduction-order tolerance — XLA CPU picks gemm
    micro-kernels by the local batch shape, the same ulp-level boundary
    as the PR-2 kernel/tensordot note (tests/test_serving_sharded.py).
    In the pair modes the lane width must be a multiple of ``2·D`` so a
    pair never straddles a shard boundary — every pair-fold below is then
    a shard-local reshape.

    ``max_draft_depth`` is the COMPILED chain length K: the traced
    program unrolls K draft-verify positions per tick, and every lane's
    runtime horizon is its ``draft_k`` state entry clamped by this bound
    (the engine validates ``RequestPolicy.draft_depth ≤ max_draft_depth``
    at submit time). ``max_draft_depth=1`` builds the original depth-1
    program — the exact legacy trace, so the default is bit-for-bit the
    PR-5 engine.

    ``forecaster`` picks the table implementation behind the draft: a
    registered name (``"taylor"``/``"spectral"``), a
    ``repro.core.forecaster.Forecaster`` instance, or ``None`` for the
    Taylor default — whose built program is the IDENTICAL jaxpr to the
    pre-seam step (the ``TaylorForecaster`` hooks inline to exactly the
    expressions this module used to call; pinned in
    ``tests/test_forecaster_seam.py``).

    ``controller=True`` builds the closed-loop variant: state must carry
    the ``repro.core.controller`` vectors (``init_workload_state(...,
    controller=True)``), each lane's forecast weights are capped at its
    adapted ``ctl_order``, and after every tick the traced controller
    update adapts controller-on lanes' ``tau0``/``draft_k``/``ctl_order``
    from their own accept statistics (see ``core/controller.py`` for the
    SLO semantics). ``controller=False`` (default) adds no controller
    ops at all — the trace is unchanged.
    """
    scfg = wl.scfg
    fc = get_forecaster(forecaster)
    if accept_mode not in ACCEPT_MODES:
        raise ValueError(f"unknown accept_mode {accept_mode!r}")
    if verify_backend not in VERIFY_BACKENDS:
        raise ValueError(f"unknown verify_backend {verify_backend!r}")
    if max_draft_depth < 1:
        raise ValueError(f"max_draft_depth must be >= 1, "
                         f"got {max_draft_depth}")
    if scfg.error_metric != "rel_l2":
        verify_backend = "jnp"     # the fused kernel implements eq. 4 only
    _check_guidance(guidance, lanes)
    if bool(guidance) and not wl.supports_pairing:
        raise ValueError(f"workload {wl.tag!r} does not support guided "
                         "lane pairs")
    W = lanes
    NP = W // 2                    # number of pair slots (pair modes)
    pairing = bool(guidance) and NP > 0
    S = wl.num_steps
    vl = wl.verify_layer

    def pair_head(v):
        """[W, …] -> [NP, 2, …]: the pair-slot fold of the first 2·NP
        lanes (pairs are interleaved (2k, 2k+1) and never straddle a
        shard). A trailing odd lane is excluded — it is always
        unpaired."""
        return v[:2 * NP].reshape((NP, 2) + v.shape[1:])

    def with_tail(head2, v):
        """[NP, 2, …] -> [W, …], re-attaching ``v``'s unpaired trailing
        lane when W is odd."""
        out = head2.reshape((2 * NP,) + head2.shape[2:])
        if W % 2:
            out = jnp.concatenate([out, v[2 * NP:]], axis=0)
        return out

    def pair_select(paired, pair_val, lane_val):
        """Per-lane select between pair-slot and per-lane semantics."""
        pm = paired.reshape((W,) + (1,) * (lane_val.ndim - 1))
        return jnp.where(pm, pair_val, lane_val)

    def pair_combine(out, gscale, paired):
        """Guided pair combine of a (bare-array) model output: a paired
        slot advances on ``u + s·(c − u)``, identical for both lanes."""
        h = pair_head(out)
        gs_p = pair_head(gscale)[:, 0]
        g = guided_output(h[:, 0], h[:, 1], gs_p)
        gb = with_tail(jnp.broadcast_to(g[:, None],
                                        (NP, 2) + g.shape[1:]), out)
        return pair_select(paired, gb, out)

    def verify(pred_vl, real_vl, tau):
        """(err [W], ok [W]) — identical math on every execution path."""
        tau = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (W,))
        if verify_backend == "fused":
            from repro.kernels import ops
            if mesh is not None:
                return ops.verify_accept_sharded(pred_vl.reshape(W, -1),
                                                 real_vl.reshape(W, -1),
                                                 tau, mesh=mesh,
                                                 eps=scfg.eps)
            return ops.verify_accept(pred_vl.reshape(W, -1),
                                     real_vl.reshape(W, -1), tau,
                                     eps=scfg.eps)
        err = relative_error(pred_vl, real_vl, metric=scfg.error_metric,
                             eps=scfg.eps, batch_axis=0)
        return err, err <= tau

    def verify_mixed(pred_vl, real_vl, tau, gs, paired):
        """Slot-width verify: per-lane decisions for unpaired lanes, ONE
        guided-residual decision per paired slot (both its lanes report
        it). Returns (err [W], ok [W])."""
        tau = jnp.broadcast_to(jnp.asarray(tau, jnp.float32), (W,))
        if verify_backend == "fused":
            from repro.kernels import ops
            if mesh is not None:
                return ops.verify_accept_mixed_sharded(
                    pred_vl.reshape(W, -1), real_vl.reshape(W, -1),
                    tau, gs, paired, mesh=mesh, eps=scfg.eps)
            return ops.verify_accept_mixed(
                pred_vl.reshape(W, -1), real_vl.reshape(W, -1),
                tau, gs, paired, eps=scfg.eps)
        # jnp path (metric-general): unpaired lanes use EXACTLY the
        # plain program's math — per-lane error in the original feature
        # dtype — so a mixed session with no pairs is value-identical
        # to guidance=False even on bf16 features; paired slots combine
        # in f32 (matching both the fused kernel and the all-paired
        # PR-4 jnp path) and broadcast the pair error to both rows.
        err_lane = relative_error(pred_vl, real_vl,
                                  metric=scfg.error_metric,
                                  eps=scfg.eps, batch_axis=0)
        ph = pair_head(pred_vl).astype(jnp.float32)
        rh = pair_head(real_vl).astype(jnp.float32)
        gs_p = pair_head(gs)[:, 0]
        err_p = relative_error(
            guided_output(ph[:, 0], ph[:, 1], gs_p),
            guided_output(rh[:, 0], rh[:, 1], gs_p),
            metric=scfg.error_metric, eps=scfg.eps, batch_axis=0)
        err_pair = with_tail(jnp.broadcast_to(err_p[:, None], (NP, 2)),
                             err_lane)
        err = jnp.where(paired, err_pair, err_lane)
        return err, err <= tau

    def step(state: Dict[str, Any]
             ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        dyn = {k: state[k] for k in wl.dyn_keys}
        since, s, active = state["since"], state["step"], state["active"]
        cond = state["cond"]
        tstate = {k: state[k] for k in fc.state_keys}
        order_cap = state["ctl_order"] if controller else None
        s_eff = jnp.minimum(s, S - 1)
        ctx = wl.step_context(state, s_eff)                       # [W]
        warm = fc.warm(tstate, scfg)
        want = active & warm & (since < scfg.max_draft)
        if pairing:
            # a paired slot drafts iff BOTH its streams can (with the
            # pair invariants held the two bits are already equal; the
            # AND makes the pair decision explicit and robust)
            h = pair_head(want)
            both = h[:, 0] & h[:, 1]
            pw = with_tail(jnp.broadcast_to(both[:, None], (NP, 2)), want)
            want = jnp.where(state["paired"], pw, want)
        # per-lane τ_t = τ0·β^((T−t)/T): every request carries its own
        # base threshold (state["tau0"]) at its own schedule step
        tau = threshold_schedule(wl.t_frac(s_eff), state["tau0"],
                                 scfg.beta)                       # [W]

        def attempt(dyn):
            with jax.named_scope("speca.draft"):
                preds = fc.predict_lanes(tstate, s_eff, mode=draft_mode,
                                         mesh=mesh, order_cap=order_cap)
                with jax.named_scope("speca.verify"):
                    out, real_vl = wl.spec_forward(dyn, cond, ctx, preds)
                    pred_vl = preds[vl][0] + preds[vl][1]
                    if pairing:
                        err, ok = verify_mixed(pred_vl, real_vl, tau,
                                               state["gscale"],
                                               state["paired"])
                    else:
                        err, ok = verify(pred_vl, real_vl, tau)
                # NaN marks "did not draft": it cannot poison downstream
                # means/percentiles the way the old inf sentinel did, and
                # it still fails every `err <= tau` comparison.
                return out, jnp.where(want, err, jnp.nan), ok & want

        def skip(dyn):
            return (wl.zero_out(W),
                    jnp.full((W,), jnp.nan, jnp.float32),
                    jnp.zeros((W,), bool))

        out_spec, err, ok = jax.lax.cond(jnp.any(want), attempt, skip, dyn)
        if accept_mode == "batch":
            # parity mode: every drafting lane must pass or all reject
            accept = want & jnp.all(ok | ~want)
        else:
            accept = want & ok
        need_full = jnp.any(active & ~accept)

        def do_full(opers):
            with jax.named_scope("speca.full"):
                dyn, tstate = opers
                out, branches = wl.full_forward(dyn, cond, ctx)
                with jax.named_scope("speca.update"):
                    tstate = fc.update_lanes(tstate, branches,
                                             s_eff, active & ~accept,
                                             mesh=mesh)
                return out, tstate

        def keep(opers):
            dyn, tstate = opers
            return wl.zero_out(W), tstate

        out_full, tstate = jax.lax.cond(need_full, do_full, keep,
                                        (dyn, tstate))
        out = wl.select_out(accept, out_spec, out_full)
        if pairing:
            # a paired slot's latent advances on the guided model output;
            # both its lanes receive the identical value (x stays
            # pair-equal). Unpaired lanes advance on their own output.
            out = pair_combine(out, state["gscale"], state["paired"])
        dyn_next = wl.advance(dyn, out, ctx, s_eff)
        dyn = wl.select_dyn(active, dyn_next, dyn)
        since = jnp.where(accept, since + 1, jnp.where(active, 0, since))
        s = s + active.astype(jnp.int32)
        new_state = dict(state)
        new_state.update(since=since, step=s, active=active,
                         **dyn, **tstate)
        if controller:
            new_state.update(_ctl.controller_update(
                state, step_new=s,
                n_spec=accept.astype(jnp.int32),
                n_drafted=want.astype(jnp.int32),
                advanced=active.astype(jnp.int32), active=active))
        full = active & ~accept
        flags = {"attempted": want, "ok": ok, "accepted": accept,
                 "full": full, "err": err, "tau": tau,
                 # depth-aware counters (trivial at depth 1) so engine
                 # accounting reads one flag layout for every K
                 "n_spec": accept.astype(jnp.int32),
                 "n_drafted": want.astype(jnp.int32),
                 "advanced": active.astype(jnp.int32),
                 "chain_attempted": want[None], "chain_accepted": accept[None],
                 "chain_err": err[None], "chain_tau": tau[None]}
        return new_state, flags

    if max_draft_depth == 1:
        return step
    K = int(max_draft_depth)

    def chain_step(state: Dict[str, Any]
                   ) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        dyn = {k: state[k] for k in wl.dyn_keys}
        since, s, active = state["since"], state["step"], state["active"]
        cond = state["cond"]
        tstate = {k: state[k] for k in fc.state_keys}
        order_cap = state["ctl_order"] if controller else None
        draft_k, max_step = state["draft_k"], state["max_step"]
        warm = fc.warm(tstate, scfg)
        # ONE fused table pass forecasts every lane at all K chain steps;
        # a lane alive at position j has accepted 0..j−1, so its step
        # there is exactly step₀ + j (clamped to the schedule end).
        steps_chain = jnp.minimum(
            s[None, :] + jnp.arange(K, dtype=jnp.int32)[:, None], S - 1)
        with jax.named_scope("speca.draft"):
            preds_chain = fc.predict_chain_lanes(tstate, steps_chain,
                                                 mode=draft_mode, mesh=mesh,
                                                 order_cap=order_cap)
        alive = active
        stop_full = jnp.zeros((W,), bool)
        n_acc = jnp.zeros((W,), jnp.int32)
        n_drafted = jnp.zeros((W,), jnp.int32)
        snaps = [dyn]
        c_att, c_acc, c_err, c_tau = [], [], [], []
        ok0 = None
        for j in range(K):
            s_eff = jnp.minimum(s, S - 1)
            ctx = wl.step_context(state, s_eff)
            budget = (draft_k > j) & (s < max_step)
            want = alive & budget & warm & (since < scfg.max_draft)
            if pairing:
                h = pair_head(want)
                both = h[:, 0] & h[:, 1]
                pw = with_tail(jnp.broadcast_to(both[:, None], (NP, 2)),
                               want)
                want = jnp.where(state["paired"], pw, want)
            tau = threshold_schedule(wl.t_frac(s_eff), state["tau0"],
                                     scfg.beta)
            preds = preds_chain[j]

            def attempt(dyn, want=want, tau=tau, ctx=ctx, preds=preds):
                with jax.named_scope("speca.draft"), \
                        jax.named_scope("speca.verify"):
                    out, real_vl = wl.spec_forward(dyn, cond, ctx, preds)
                    pred_vl = preds[vl][0] + preds[vl][1]
                    if pairing:
                        err, ok = verify_mixed(pred_vl, real_vl, tau,
                                               state["gscale"],
                                               state["paired"])
                    else:
                        err, ok = verify(pred_vl, real_vl, tau)
                    return out, jnp.where(want, err, jnp.nan), ok & want

            def skip(dyn):
                return (wl.zero_out(W),
                        jnp.full((W,), jnp.nan, jnp.float32),
                        jnp.zeros((W,), bool))

            out_spec, err, ok = jax.lax.cond(jnp.any(want), attempt, skip,
                                             dyn)
            if accept_mode == "batch":
                acc = want & jnp.all(ok | ~want)
            else:
                acc = want & ok
            # a lane with budget at j that did not advance (could not
            # draft, or drafted and failed) is served by the closing
            # full; a lane whose budget ran out stops clean at its
            # accepted frontier
            stop_full = stop_full | (alive & budget & ~acc)
            out = out_spec
            if pairing:
                out = pair_combine(out, state["gscale"], state["paired"])
            # blind speculative advance: EVERY row steps on the drafted
            # output (rows are sample-independent, so garbage rows of
            # stopped lanes perturb nothing); the rollback below
            # restores each lane to its accepted-prefix snapshot
            dyn = wl.advance(dyn, out, ctx, s_eff)
            snaps.append(dyn)
            since = jnp.where(acc, since + 1, since)
            s = s + acc.astype(jnp.int32)
            n_acc = n_acc + acc.astype(jnp.int32)
            n_drafted = n_drafted + want.astype(jnp.int32)
            alive = acc
            if j == 0:
                ok0 = ok
            c_att.append(want)
            c_acc.append(acc)
            c_err.append(err)
            c_tau.append(tau)
        # rollback: per-lane exact-copy restore to the snapshot at the
        # lane's accepted-prefix length (inactive/rejected-at-0 lanes get
        # snapshot 0 — their pre-tick payload, bit-exactly)
        with jax.named_scope("speca.rollback"):
            chain = {k: jnp.stack([sn[k] for sn in snaps])
                     for k in wl.dyn_keys}
            dyn = wl.rollback(chain, n_acc, mesh=mesh)
        # ONE closing full forward serves every rejected lane at its
        # rolled-back step and refreshes only those lanes' table slices
        s_eff = jnp.minimum(s, S - 1)
        ctx = wl.step_context(state, s_eff)
        need_full = jnp.any(stop_full)

        def do_full(opers):
            with jax.named_scope("speca.full"):
                dyn, tstate = opers
                out, branches = wl.full_forward(dyn, cond, ctx)
                with jax.named_scope("speca.update"):
                    tstate = fc.update_lanes(tstate, branches,
                                             s_eff, stop_full, mesh=mesh)
                return out, tstate

        def keep(opers):
            dyn, tstate = opers
            return wl.zero_out(W), tstate

        out_full, tstate = jax.lax.cond(need_full, do_full, keep,
                                        (dyn, tstate))
        if pairing:
            out_full = pair_combine(out_full, state["gscale"],
                                    state["paired"])
        dyn_f = wl.advance(dyn, out_full, ctx, s_eff)
        dyn = wl.select_dyn(stop_full, dyn_f, dyn)
        since = jnp.where(stop_full, 0, since)
        s = s + stop_full.astype(jnp.int32)
        new_state = dict(state)
        new_state.update(since=since, step=s, active=active,
                         **dyn, **tstate)
        if controller:
            new_state.update(_ctl.controller_update(
                state, step_new=s, n_spec=n_acc, n_drafted=n_drafted,
                advanced=n_acc + stop_full.astype(jnp.int32),
                active=active))
        flags = {"attempted": c_att[0], "ok": ok0, "accepted": c_acc[0],
                 "full": stop_full, "err": c_err[0], "tau": c_tau[0],
                 "n_spec": n_acc, "n_drafted": n_drafted,
                 "advanced": n_acc + stop_full.astype(jnp.int32),
                 "chain_attempted": jnp.stack(c_att),
                 "chain_accepted": jnp.stack(c_acc),
                 "chain_err": jnp.stack(c_err),
                 "chain_tau": jnp.stack(c_tau)}
        return new_state, flags

    return chain_step


def build_lane_step(cfg: ModelConfig, params: Dict[str, Any],
                    dcfg: DiffusionConfig, scfg: SpeCaConfig, *,
                    lanes: int, draft_mode: str = "taylor",
                    accept_mode: str = "per_sample",
                    verify_backend: str = "jnp",
                    use_flash: bool = False,
                    guidance: Union[bool, str] = False,
                    max_draft_depth: int = 1,
                    forecaster: Optional[Any] = None,
                    controller: bool = False,
                    mesh: Optional[Any] = None
                    ) -> Callable[[Dict[str, Any]],
                                  Tuple[Dict[str, Any], Dict[str, Any]]]:
    """Build the traced DIFFUSION lane step (the original entry point —
    ``build_workload_step`` over a ``DiffusionWorkload``): ``state ->
    (state, flags)``. See ``build_workload_step`` for the knobs; the
    adapter hooks inline to exactly the pre-seam expressions, so the
    built program is the same trace as before the workload seam."""
    from repro.core.workload import DiffusionWorkload
    wl = DiffusionWorkload(cfg, params=params, dcfg=dcfg, scfg=scfg,
                           use_flash=use_flash)
    return build_workload_step(wl, lanes=lanes, draft_mode=draft_mode,
                               accept_mode=accept_mode,
                               verify_backend=verify_backend,
                               guidance=guidance,
                               max_draft_depth=max_draft_depth,
                               forecaster=forecaster,
                               controller=controller, mesh=mesh)

"""Per-architecture partition rules (DESIGN.md §5).

Scheme on the production mesh (pod?, data, model):
  * batch over ('pod','data'); tensor parallel over 'model' on attention
    heads / FFN hidden / MoE experts; vocab-parallel embeddings/head when
    divisible.
  * GQA KV projections replicate when kv_heads doesn't divide the model
    axis (standard KV duplication).
  * decode KV caches: batch over data when divisible, else (long_500k,
    batch=1) the cache *sequence* is sharded over every mesh axis —
    flash-decoding-style distributed softmax, XLA inserts the reductions.
  * optimizer moments follow their parameter's spec; scalars replicate.

All rules are divisibility-guarded: a dimension is sharded only if the
axis size divides it, so every (arch × shape) lowers on both the 256- and
512-chip meshes without padding.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        n = 1
        for a in name:
            n *= mesh.shape[a]
        return n
    return mesh.shape[name]


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _key_path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def param_spec(cfg: ModelConfig, mesh: Mesh, path: str, shape: Tuple[int, ...]
               ) -> P:
    """Partition spec for one parameter leaf (path in the params tree)."""
    ms = mesh.shape["model"]

    def last_if(dim: int) -> P:
        """Shard the last axis over 'model' if divisible, else replicate."""
        nones = (None,) * (len(shape) - 1)
        return P(*nones, "model") if dim % ms == 0 else P()

    name = path.split("/")[-1]

    # --- embeddings & heads ---
    if path.startswith("embed/tok") or path.startswith("embed/codebooks"):
        v, d = shape[-2], shape[-1]
        lead = (None,) * (len(shape) - 2)
        if v % ms == 0:
            return P(*lead, "model", None)
        # Perf iteration A/E1 (EXPERIMENTS.md §Perf): sharding D here makes
        # the (tied) LM head a contracting-dim matmul whose f32 logits
        # [B,T,V] get all-reduced — 12.9 GB/device wire for granite-moe.
        # Replicating the embedding (≤100 MB) keeps logits local.
        return P()
    if path.startswith("embed/"):
        return P()                       # patch/time/label/cond: tiny
    if path == "final_norm":
        return P()
    if path.startswith("head/"):
        if name == "w" and len(shape) >= 2 and cfg.vocab_size \
                and shape[-1] == cfg.padded_vocab:
            return last_if(shape[-1])
        return P()

    # --- stacked blocks (leading L axis) ---
    if path.startswith("blocks/"):
        if name in ("ln1", "ln2", "mod_b"):
            return P()
        if name in ("wq", "wk", "wv"):
            return P(None, None, "model") if shape[-1] % ms == 0 else P()
        if name in ("bq", "bk", "bv"):
            return P(None, "model") if shape[-1] % ms == 0 else P()
        if name == "wo":
            return P(None, "model", None) if shape[-2] % ms == 0 else P()
        if name == "mod_w":
            return P(None, None, "model") if shape[-1] % ms == 0 else P()
        if "moe" in path:
            if name == "router":
                return P()
            e = shape[1]
            if name in ("w_gate", "w_up"):       # [L, E, D, F]
                if e % ms == 0:
                    return P(None, "model", None, None)
                return P(None, None, None, "model") \
                    if shape[-1] % ms == 0 else P()
            if name == "w_down":                  # [L, E, F, D]
                if e % ms == 0:
                    return P(None, "model", None, None)
                return P(None, None, "model", None) \
                    if shape[-2] % ms == 0 else P()
        if "mlp" in path:
            if name in ("w_gate", "w_up"):
                return P(None, None, "model") if shape[-1] % ms == 0 else P()
            if name == "w_down":
                return P(None, "model", None) if shape[-2] % ms == 0 else P()
        if "ssm" in path:
            return P()                   # recurrent mixer params replicate
        return P()
    return P()


def params_shardings(cfg: ModelConfig, mesh: Mesh, params_shape) -> Any:
    """NamedSharding pytree matching a params (or moments) shape tree."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    out = []
    for path, leaf in flat:
        spec = param_spec(cfg, mesh, _key_path_str(path), tuple(leaf.shape))
        out.append(NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def opt_state_shardings(cfg: ModelConfig, mesh: Mesh, params_shape) -> Dict:
    psh = params_shardings(cfg, mesh, params_shape)
    return {"mu": psh, "nu": psh,
            "count": NamedSharding(mesh, P())}


def train_state_shardings(cfg: ModelConfig, mesh: Mesh, params_shape
                          ) -> Dict:
    return {"params": params_shardings(cfg, mesh, params_shape),
            "opt": opt_state_shardings(cfg, mesh, params_shape),
            "step": NamedSharding(mesh, P())}


def batch_sharding(mesh: Mesh, batch: int, ndim: int) -> NamedSharding:
    """Shard the leading batch dim over the data axes when divisible."""
    dp = data_axes(mesh)
    if batch % _axis_size(mesh, dp) == 0:
        return NamedSharding(mesh, P(dp, *(None,) * (ndim - 1)))
    return NamedSharding(mesh, P())


def cache_shardings(cfg: ModelConfig, mesh: Mesh, batch: int,
                    cache_shape) -> Dict:
    """KV/SSM cache specs: [L, B, S, KV, hd] / [L, B, nh, hp, ns] etc."""
    dp = data_axes(mesh)
    dp_size = _axis_size(mesh, dp)
    ms = mesh.shape["model"]
    batch_ok = batch % dp_size == 0

    def kv_spec(shape):
        # [L, B, S, KV, hd]
        if batch_ok:
            if shape[3] % ms == 0:
                return P(None, dp, None, "model", None)
            return P(None, dp, "model", None, None)   # shard sequence
        # batch=1 long-context: shard the sequence over EVERY axis
        all_axes = tuple(mesh.axis_names)
        return P(None, None, all_axes, None, None)

    def ssm_spec(shape):
        # [L, B, nh, hp, ns]
        if batch_ok:
            if shape[2] % ms == 0:
                return P(None, dp, "model", None, None)
            return P(None, dp, None, None, None)
        if shape[2] % ms == 0:
            return P(None, None, "model", None, None)
        return P()

    def conv_spec(shape):
        # [L, B, W, C]
        if batch_ok:
            return P(None, dp, None, None)
        return P()

    out = {}
    for key, leaf in cache_shape.items():
        if key in ("k", "v"):
            out[key] = NamedSharding(mesh, kv_spec(leaf.shape))
        elif key == "ssm_state":
            out[key] = NamedSharding(mesh, ssm_spec(leaf.shape))
        else:
            out[key] = NamedSharding(mesh, conv_spec(leaf.shape))
    return out


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Lane-axis rules (sharded serving)
# ---------------------------------------------------------------------------
# The serving engine packs W concurrent requests into a lane batch; every
# per-lane computation (draft, verify, refresh, advance) is lane-
# independent, so the lane axis shards over the data axes of the mesh and
# one engine serves W×D lanes across D devices. What shards vs replicates:
#
#   array                  | layout              | spec
#   -----------------------|---------------------|---------------------------
#   latents ``x``          | [W, C, H, Wd]       | P(data, None, ...)
#   difference table       | [m+1, L, 2, W, T, D]| P(None, None, None, data,
#                          |                     |   None, None)
#   ``since``/``step``/    | [W]                 | P(data)
#   ``active``/``n_anchors``/``anchor_step``/``gap``
#   conditioning values    | [W, ...]            | P(data, None, ...)
#   model params           | (tree)              | P() — replicated
#
# The table is the big operand: lane-sharding it means a D-device engine
# holds 1/D of the table per device, and the refill path
# (``lane_step.write_lanes`` in a ``shard_map``) rewrites a lane block on
# each shard — the table is never gathered.
#
# CFG pair rule: a guided request occupies the lane PAIR (2k, 2k+1) —
# cond and uncond streams. The guided combination ``u + s·(c − u)`` and
# the pair verify are cross-lane ops *within* a pair, so a pair must
# never straddle a shard boundary: whenever guided requests can be
# admitted (engine ``guidance=True``, or an API-v2 mixed session) the
# lane width rounds up to a multiple of ``2·D``
# (``lane_width_multiple(mesh, streams=2)``), making every pair-fold a
# shard-local reshape with zero cross-device traffic. In a mixed
# session a pair slot may instead hold one or two independent unguided
# lanes — the per-lane ``paired`` mask selects the semantics slot by
# slot, and the same 2·D rule keeps that select shard-local too.

LANE_AXIS = "data"

# lane-state key -> lane-axis position (post-leading-dim for ``diffs``,
# where axis 0 is the m+1 difference-order axis and the lane lives at
# position 3 of the (L, 2, W, T, D) feature layout). ``gscale`` is the
# per-lane guidance scale and ``paired`` the per-lane pair-slot mask
# (pair modes only; both pair-equal by invariant); ``tau0`` is the
# per-lane base verification threshold (serving API v2 — every request
# carries its own τ policy); ``draft_k`` is the per-lane draft horizon
# (``RequestPolicy.draft_depth``) and ``max_step`` the lane's schedule
# length — both read by depth-K chain steps.
#
# Decode-workload payload keys (``repro.core.workload.DecodeWorkload``):
# ``tok``/``tokens``/``pos0`` are per-lane token vectors (lane axis 0);
# the KV/SSM caches are laid out [L, W, ...] so their lane axis is 1 —
# lane-sharding them is exactly the "decode state sharded like the
# table" rule: each shard owns its lanes' cache slices, and the fill
# path's shard-local write never gathers the cache.
LANE_STATE_AXES = {
    "x": 0, "since": 0, "step": 0, "active": 0,
    "diffs": 3, "n_anchors": 0, "anchor_step": 0, "gap": 0,
    "gscale": 0, "paired": 0, "tau0": 0,
    "draft_k": 0, "max_step": 0,
    "tok": 0, "tokens": 0, "pos0": 0,
    "k": 1, "v": 1, "ssm_state": 1, "conv_state": 1,
    # closed-loop controller vectors (repro.core.controller): all [W]
    # lane-local statistics/bounds, updated inside the traced step with
    # no cross-lane traffic — plain axis-0 lane shards
    "ctl_on": 0, "ctl_dl": 0, "ctl_rate": 0, "ctl_adv": 0,
    "ctl_target": 0, "ctl_gain": 0, "ctl_ema": 0,
    "ctl_tau_lo": 0, "ctl_tau_hi": 0, "ctl_tau_base": 0,
    "ctl_k_lo": 0, "ctl_k_hi": 0,
    "ctl_order": 0, "ctl_order_lo": 0, "ctl_order_hi": 0,
    "ctl_ticks": 0, "ctl_deadline": 0,
}


def lane_spec(ndim: int, lane_dim: int, axis=LANE_AXIS) -> P:
    """PartitionSpec placing ``axis`` at ``lane_dim`` of an ndim array."""
    return P(*(axis if i == lane_dim else None for i in range(ndim)))


def lane_state_shardings(mesh: Mesh, state: Dict[str, Any],
                         axis=LANE_AXIS) -> Dict[str, Any]:
    """NamedSharding tree for a lane-state dict (``init_lane_state``).

    ``cond`` values shard their leading (lane) axis; ``diffs`` shards lane
    position 3 (the W of (m+1, L, 2, W, T, D)); every [W] metadata vector
    shards axis 0. Unknown keys replicate.
    """
    out: Dict[str, Any] = {}
    for key, leaf in state.items():
        if key == "cond":
            out[key] = {k: NamedSharding(mesh, lane_spec(jnp_ndim(v), 0,
                                                         axis))
                        for k, v in leaf.items()}
        elif key in LANE_STATE_AXES:
            out[key] = NamedSharding(
                mesh, lane_spec(jnp_ndim(leaf), LANE_STATE_AXES[key],
                                axis))
        else:
            out[key] = NamedSharding(mesh, P())
    return out


def jnp_ndim(x: Any) -> int:
    return len(getattr(x, "shape", np.shape(x)))


def lane_shard_count(mesh: Optional[Mesh], axis=LANE_AXIS) -> int:
    """How many ways the lane axis splits on ``mesh`` (1 for no mesh)."""
    if mesh is None:
        return 1
    return _axis_size(mesh, axis)


def lane_width_multiple(mesh: Optional[Mesh], *, streams: int = 1,
                        axis=LANE_AXIS) -> int:
    """The serving lane width must be a multiple of this.

    ``streams`` is the number of lanes one request occupies: 1 for plain
    serving, 2 for CFG pairs (cond + uncond). The width rounds to
    ``streams × D`` so every shard owns an equal lane block AND no
    request's lane group straddles a shard boundary (the CFG pair rule
    above)."""
    return streams * lane_shard_count(mesh, axis)

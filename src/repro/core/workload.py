"""Workload adapters for the workload-agnostic lane core.

The forecast-then-verify loop in ``repro.core.lane_step`` is workload-
agnostic: TaylorSeer difference tables, the per-lane τ schedule, the
accept combiner, draft-K chains with snapshot/rollback and the masked
refresh all operate on an opaque *dynamic payload* (the pytree a lane
advances each step) plus a verify-layer feature pair. Everything that is
actually specific to a workload — what a "model output" is, how the
payload advances on it, what the verify features are, how a lane is
filled from a request and harvested into a sample — lives behind the
``Workload`` adapter defined here.

Two workloads ship:

``DiffusionWorkload``
    The original SpeCa serving semantics, extracted verbatim from the
    pre-seam ``lane_step``: payload = the latent ``x`` (lane axis 0),
    model output = the denoiser prediction, advance = the
    ``rf_euler_step`` sampler update at the lane's timestep, τ_t follows
    the timestep-indexed σ schedule, verify features are the verify
    layer's residual increments over image tokens. The extraction is a
    refactor, not a change — every diffusion trajectory pin (depth-1
    legacy step, CFG pairs, sharded parity) holds bitwise through the
    seam.

``DecodeWorkload``
    SpecDiff-style *self-speculative* LLM decoding (PAPERS.md,
    arxiv 2509.13848): the TaylorSeer table extrapolates each lane's
    per-position residual increments ACROSS DECODE STEPS (feature layout
    (L, 2, W, 1, D) — one token per step), the drafted feature runs the
    same masked verify-layer forward and accept combiner as diffusion,
    accepted steps emit their token from the forecast stream's logits,
    and rejected lanes take the full decode forward. The payload is the
    decode state: current input token, emitted-token buffer, and the
    KV/SSM caches (lane axis 1 of the [L, W, ...] cache layout) — all
    snapshotted and restored by the existing draft-K rollback machinery,
    so a depth-K chain's rejected positions roll tokens AND caches back
    bitwise. Speculative steps still write cache entries, derived from
    the forecast stream (K/V projections + RoPE at the lane's position;
    SSM/conv state advance), which is what makes the drafted chain's
    attention self-consistent. τ_t is constant at τ0 (``t_frac`` ≡ 1 —
    decoding has no noise-level schedule). No pairing: classifier-free
    guidance is a diffusion concept, guided decode requests are rejected
    at policy resolution.

Host-side hooks (``fill_payload`` / ``emit``) keep the engine's
host/device discipline: filling a decode lane runs ONE prefill forward
for the request's prompt and scatters the resulting cache into the
lane's slice; harvesting reads back the emitted token row.
"""
from __future__ import annotations

import copy
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DiffusionConfig, ModelConfig, SpeCaConfig
from repro.core import taylor
from repro.core.complexity import (decode_forward_flops, decode_verify_flops,
                                   forward_flops, verify_flops)
from repro.core.lane_step import num_tokens as _diff_num_tokens
from repro.core.lane_step import table_dtype as _table_dtype
from repro.core.lane_step import verify_layer as _verify_layer
from repro.diffusion.pipeline import latent_shape, make_stepper, model_inputs
from repro.layers import blocks as blk
from repro.layers import model as M
from repro.obs.trace import span


def _axis_where(mask: jnp.ndarray, axis: int, a: jnp.ndarray,
                b: jnp.ndarray) -> jnp.ndarray:
    """Per-lane select with the lane mask broadcast at ``axis``."""
    shape = [1] * a.ndim
    shape[axis] = mask.shape[0]
    return jnp.where(mask.reshape(shape), a, b)


def _gather_rollback(chain: jnp.ndarray, idx: jnp.ndarray,
                     lane_axis: int) -> jnp.ndarray:
    """jnp rollback for integer payload leaves (exact copy, like the
    kernel): chain [K+1, ...feat], idx [B] -> chain[idx[lane]] per
    lane."""
    feat_ndim = chain.ndim - 1
    shape = tuple(idx.shape[0] if i == lane_axis else 1
                  for i in range(feat_ndim))
    idxb = jnp.broadcast_to(idx.reshape((1,) + shape),
                            (1,) + chain.shape[1:])
    return jnp.take_along_axis(chain, idxb, axis=0)[0]


class Workload:
    """Adapter interface consumed by ``lane_step.build_workload_step``.

    Static attributes (read at build time):
      tag               unique workload name (``RequestPolicy.workload``)
      cfg / scfg        backbone + SpeCa configs
      num_steps         schedule length S (denoising steps / new tokens)
      num_tokens        token count T of the (L, 2, W, T, D) feature table
      supports_pairing  whether guided CFG lane pairs exist
      cond_in_state     whether per-lane conditioning rides in lane state
      verify_layer      resolved verify-layer index
      table_dtype       difference-table dtype
      dyn_keys          state keys of the dynamic payload (threaded
                        through the step, snapshotted and rolled back by
                        draft-K chains)
      dyn_axes          payload key -> lane-axis position
      full_flops / verify_flops   per-step analytic cost (accounting)

    Traced hooks (called inside the jitted step): ``t_frac``,
    ``step_context``, ``spec_forward``, ``full_forward``, ``zero_out``,
    ``select_out``, ``advance``, ``rollback``. Host hooks (engine
    validate / fill / harvest): ``validate_request``, ``init_payload``,
    ``fill_payload``, ``emit``.
    """

    tag: str = "?"
    supports_pairing = False
    cond_in_state = True

    # --- traced hooks ----------------------------------------------------
    def t_frac(self, s_eff: jnp.ndarray) -> jnp.ndarray:
        raise NotImplementedError

    def step_context(self, state: Dict[str, Any], s_eff: jnp.ndarray):
        raise NotImplementedError

    def spec_forward(self, dyn, cond, ctx, preds):
        raise NotImplementedError

    def full_forward(self, dyn, cond, ctx):
        raise NotImplementedError

    def zero_out(self, lanes: int):
        raise NotImplementedError

    def select_out(self, mask, a, b):
        raise NotImplementedError

    def advance(self, dyn, out, ctx, s_eff):
        raise NotImplementedError

    def rollback(self, chain, n_acc, *, mesh=None):
        out = {}
        for k, v in chain.items():
            ax = self.dyn_axes[k]
            if jnp.issubdtype(v.dtype, jnp.inexact):
                out[k] = taylor.lane_rollback(v, n_acc, lane_axis=ax,
                                              mesh=mesh)
            else:
                # integer leaves (token buffers): plain gather — rollback
                # is an exact copy on every backend
                out[k] = _gather_rollback(v, n_acc, ax)
        return out

    def with_params(self, params) -> "Workload":
        """A shallow copy of this adapter serving ``params``. The engine
        traces its lane step through one whose params are the step's
        argument, so the weights enter the compiled program as an input
        rather than as constants embedded in it."""
        wl = copy.copy(self)
        wl.params = params
        return wl

    def select_dyn(self, mask, new, cur):
        return {k: _axis_where(mask, self.dyn_axes[k], new[k], v)
                for k, v in cur.items()}

    # --- host hooks ------------------------------------------------------
    def validate_request(self, request, steps: int) -> None:
        """Reject a request whose payload this workload cannot serve
        (raise ``ValueError``). Called by the engine BEFORE any side
        effect of admission — session start, ticket issue, queue push —
        so a bad request (e.g. a malformed decode prompt) fails the
        ``submit()`` itself instead of blowing up ``fill_payload``
        mid-tick inside a live session. Default: accept everything."""

    def init_payload(self, lanes: int, *, x=None) -> Dict[str, Any]:
        raise NotImplementedError

    def fill_payload(self, state: Dict[str, Any], lane: int, request,
                     steps: int) -> Dict[str, Any]:
        raise NotImplementedError

    def emit(self, state: Dict[str, Any], lane: int, done: int):
        raise NotImplementedError


class DiffusionWorkload(Workload):
    """The original SpeCa diffusion semantics behind the adapter seam."""

    tag = "diffusion"
    supports_pairing = True
    cond_in_state = True

    def __init__(self, cfg: ModelConfig, params, dcfg: DiffusionConfig,
                 scfg: SpeCaConfig, *, use_flash: bool = False) -> None:
        self.cfg, self.params = cfg, params
        self.dcfg, self.scfg = dcfg, scfg
        self.stepper = make_stepper(dcfg)
        self.num_steps = self.stepper.num_steps
        self.num_tokens = _diff_num_tokens(cfg, dcfg)
        self.verify_layer = _verify_layer(cfg, scfg)
        self.table_dtype = _table_dtype(cfg, scfg)
        self.use_flash = use_flash
        self.dyn_keys: Tuple[str, ...] = ("x",)
        self.dyn_axes = {"x": 0}
        self.full_flops = forward_flops(cfg, self.num_tokens)
        self.verify_flops = verify_flops(cfg, self.num_tokens)
        self._cmask = jnp.arange(cfg.num_layers) == self.verify_layer

    # --- traced ----------------------------------------------------------
    def t_frac(self, s_eff):
        return self.stepper.t_frac[s_eff]

    def step_context(self, state, s_eff):
        return self.stepper.t_model[s_eff]

    def spec_forward(self, dyn, cond, ctx, preds):
        inputs = model_inputs(self.cfg, dyn["x"], ctx, cond)
        out, extras = M.dit_forward(self.cfg, self.params, inputs,
                                    branch_preds=preds,
                                    compute_mask=self._cmask,
                                    collect_branches=True,
                                    use_flash=self.use_flash)
        vl = self.verify_layer
        real_vl = extras["branches"][vl][0] + extras["branches"][vl][1]
        return out.astype(jnp.float32), real_vl

    def full_forward(self, dyn, cond, ctx):
        inputs = model_inputs(self.cfg, dyn["x"], ctx, cond)
        out, extras = M.dit_forward(self.cfg, self.params, inputs,
                                    collect_branches=True,
                                    use_flash=self.use_flash)
        return out.astype(jnp.float32), extras["branches"]

    def zero_out(self, lanes):
        return jnp.zeros(latent_shape(self.cfg, self.dcfg, lanes),
                         jnp.float32)

    def select_out(self, mask, a, b):
        sel = mask.reshape((mask.shape[0],) + (1,) * (a.ndim - 1))
        return jnp.where(sel, a, b)

    def advance(self, dyn, out, ctx, s_eff):
        return {"x": self.stepper.advance(dyn["x"], out, s_eff)}

    def rollback(self, chain, n_acc, *, mesh=None):
        return {"x": taylor.lane_rollback(chain["x"], n_acc, lane_axis=0,
                                          mesh=mesh)}

    # --- host ------------------------------------------------------------
    def init_payload(self, lanes, *, x=None):
        if x is None:
            x = jnp.zeros(latent_shape(self.cfg, self.dcfg, lanes),
                          jnp.float32)
        return {"x": x}

    def fill_payload(self, state, lane, request, steps):
        # both lanes of a guided pair call this with the SAME request, so
        # recomputing the noise per lane keeps the pair's latent rows
        # identical (PRNGKey(seed) is deterministic)
        noise = jax.random.normal(jax.random.PRNGKey(request.seed),
                                  latent_shape(self.cfg, self.dcfg, 1),
                                  jnp.float32)
        state = dict(state)
        state["x"] = state["x"].at[lane].set(noise[0])
        return state

    def emit(self, state, lane, done):
        with span("speca.sync.emit", lane=lane):
            return jax.device_get(state["x"][lane:lane + 1])


class DecodeWorkload(Workload):
    """Self-speculative LLM decode lanes (SpecDiff-style, no drafter).

    ``max_new_tokens`` is the lane schedule length S (a request's
    ``RequestPolicy.max_steps`` serves a prefix, exactly as in
    diffusion); ``max_seq_len`` sizes the per-lane KV cache — a
    request's prompt length P must satisfy P + steps ≤ max_seq_len.
    """

    tag = "decode"
    supports_pairing = False
    cond_in_state = False

    def __init__(self, cfg: ModelConfig, params, scfg: SpeCaConfig, *,
                 max_new_tokens: int, max_seq_len: int) -> None:
        if cfg.is_diffusion:
            raise ValueError("DecodeWorkload serves autoregressive LMs; "
                             f"arch_type={cfg.arch_type!r} is a diffusion "
                             "backbone (use DiffusionWorkload)")
        if cfg.arch_type == "audio":
            raise ValueError("DecodeWorkload does not serve multi-codebook "
                             "audio decode yet (tokens are [B, K, 1])")
        if blk.uses_ring_cache(cfg):
            raise ValueError(
                "DecodeWorkload uses absolute-position lane caches; "
                "ring-buffer decode caches (attn_window>0, global_every=0) "
                "are not supported — serve this config through "
                "lm_decode_step")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, "
                             f"got {max_new_tokens}")
        self.cfg, self.params, self.scfg = cfg, params, scfg
        self.num_steps = int(max_new_tokens)
        self.num_tokens = 1
        self.max_seq_len = int(max_seq_len)
        self.verify_layer = _verify_layer(cfg, scfg)
        self.table_dtype = _table_dtype(cfg, scfg)
        self._cache_keys: Tuple[str, ...] = ()
        if cfg.has_attention:
            self._cache_keys += ("k", "v")
        if cfg.is_ssm or cfg.is_hybrid:
            self._cache_keys += ("ssm_state", "conv_state")
        self.dyn_keys = ("tok", "tokens") + self._cache_keys
        self.dyn_axes = {"tok": 0, "tokens": 0,
                         **{k: 1 for k in self._cache_keys}}
        self.full_flops = decode_forward_flops(cfg, self.max_seq_len)
        self.verify_flops = decode_verify_flops(cfg, self.max_seq_len)
        self._cmask = jnp.arange(cfg.num_layers) == self.verify_layer
        # params are an argument, not constants embedded in the program
        self._prefill = jax.jit(self._prefill_impl)

    def _prefill_impl(self, params, tokens):
        logits, extras = M.lm_forward(self.cfg, params,
                                      {"tokens": tokens},
                                      collect_cache=True)
        return logits[:, -1], extras["cache"]

    # --- traced ----------------------------------------------------------
    def t_frac(self, s_eff):
        # no noise-level schedule: τ_t ≡ τ0 (t_frac = 1 ⇒ β exponent 0)
        return jnp.ones(s_eff.shape, jnp.float32)

    def step_context(self, state, s_eff):
        # each lane's absolute query position this step
        return state["pos0"] + s_eff

    def _forward(self, dyn, ctx, preds):
        cache = {k: dyn[k] for k in self._cache_keys}
        return M.decode_branches_step(self.cfg, self.params, dyn["tok"],
                                      cache, ctx, branch_preds=preds,
                                      compute_mask=None if preds is None
                                      else self._cmask,
                                      collect_branches=True)

    def spec_forward(self, dyn, cond, ctx, preds):
        logits, new_cache, branches = self._forward(dyn, ctx, preds)
        vl = self.verify_layer
        real_vl = branches[vl][0] + branches[vl][1]
        return {"logits": logits, **new_cache}, real_vl

    def full_forward(self, dyn, cond, ctx):
        logits, new_cache, branches = self._forward(dyn, ctx, None)
        return {"logits": logits, **new_cache}, branches

    def zero_out(self, lanes):
        out = {"logits": jnp.zeros((lanes, 1, self.cfg.padded_vocab),
                                   self.cfg.jnp_dtype)}
        out.update(M.init_cache(self.cfg, lanes, self.max_seq_len))
        return out

    def select_out(self, mask, a, b):
        return {k: _axis_where(mask, 0 if k == "logits" else 1, a[k], b[k])
                for k in a}

    def advance(self, dyn, out, ctx, s_eff):
        W = s_eff.shape[0]
        tok = jnp.argmax(out["logits"][:, 0, :], axis=-1).astype(jnp.int32)
        new = {"tok": tok[:, None],
               "tokens": dyn["tokens"].at[jnp.arange(W), s_eff].set(tok)}
        for k in self._cache_keys:
            new[k] = out[k]
        return new

    # --- host ------------------------------------------------------------
    def init_payload(self, lanes, *, x=None):
        if x is not None:
            raise ValueError("DecodeWorkload lanes start from a prompt "
                             "prefill, not a latent")
        payload = {"tok": jnp.zeros((lanes, 1), jnp.int32),
                   "tokens": jnp.zeros((lanes, self.num_steps), jnp.int32),
                   "pos0": jnp.zeros((lanes,), jnp.int32)}
        payload.update(M.init_cache(self.cfg, lanes, self.max_seq_len))
        return payload

    def _prompt_of(self, request, steps) -> np.ndarray:
        """The request's normalised [1, P] prompt, or ``ValueError``
        when malformed / too long for the lane cache — shared by
        ``validate_request`` (submit time) and ``fill_payload``
        (admission time) so the two can never disagree."""
        try:
            prompt = np.asarray(request.cond["tokens"], np.int32)
        except (KeyError, TypeError, ValueError) as e:
            raise ValueError("decode request needs an integer "
                             f"cond['tokens'] prompt: {e}") from None
        if prompt.ndim == 1:
            prompt = prompt[None]
        if prompt.ndim != 2 or prompt.shape[0] != 1 or prompt.shape[1] < 1:
            raise ValueError("decode request cond['tokens'] must be a "
                             f"[1, P] prompt, got shape {prompt.shape}")
        P = prompt.shape[1]
        if P + steps > self.max_seq_len:
            raise ValueError(
                f"prompt length {P} + {steps} new tokens exceeds the "
                f"workload's max_seq_len={self.max_seq_len}")
        return prompt

    def validate_request(self, request, steps):
        self._prompt_of(request, steps)

    def fill_payload(self, state, lane, request, steps):
        prompt = self._prompt_of(request, steps)
        P = prompt.shape[1]
        logits, cache = self._prefill(self.params, jnp.asarray(prompt))
        with span("speca.sync.prefill", lane=lane):
            tok0 = int(np.argmax(np.asarray(jax.device_get(logits))[0]))
        state = dict(state)
        for key in self._cache_keys:
            # clear the lane's slice (previous occupant), then scatter the
            # prefix (on a mesh each eager scatter at a traced lane index
            # gathers the whole lane-sharded leaf first)
            cleared = state[key].at[:, lane].set(0)
            if key in ("k", "v"):
                state[key] = cleared.at[:, lane, :P].set(cache[key][:, 0])
            else:
                state[key] = cleared.at[:, lane].set(cache[key][:, 0])
        state["tok"] = state["tok"].at[lane, 0].set(tok0)
        state["tokens"] = state["tokens"].at[lane].set(0)
        state["pos0"] = state["pos0"].at[lane].set(P)
        return state

    def emit(self, state, lane, done):
        with span("speca.sync.emit", lane=lane):
            toks = np.asarray(jax.device_get(state["tokens"][lane]))
        return toks[:max(min(done, self.num_steps), 0)].copy()


def make_diffusion_workload(cfg, params, dcfg, scfg, *,
                            use_flash: bool = False) -> DiffusionWorkload:
    return DiffusionWorkload(cfg, params, dcfg, scfg, use_flash=use_flash)

"""Plain float32 reference of Mamba-2 (arXiv:2405.21060) greedy decode
with SpeCa's self-speculative schedule, teacher-forced on served tokens.

Written from the published recurrence in straightforward ``jax.numpy``;
it imports nothing of the program. One block: ``x = RMSNorm(h)``; the
input projection gives the gate ``z``, the conv stream ``xBC`` and the
step sizes ``dt``; ``xBC`` passes a depthwise causal conv (width 4) and
SiLU and splits into ``x, B, C`` (one group); per head
``S ← exp(dt·A)·S + dt·x⊗B`` and ``y = S·C + D·x``; the output is
``W_out · RMSNorm(y ⊙ silu(z))``, added to the residual stream. The LM
head is tied to the embedding; padding rows of the vocabulary are not
scored. The prompt is consumed by the same recurrence token by token,
which equals the chunked (SSD) form in exact arithmetic.

SpeCa: a full decode step stores each layer's residual increment in a
TaylorSeer table; a drafted step adds the forecast increment instead,
except at the verify layer, which runs for real on the forecast stream.
Every layer's state still advances on the forecast stream's input,
drafted or not. With the benchmark's threshold every draft is
accepted, so which steps draft is a fixed function of the step index.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.numerics import F32, Sizes, TaylorTable, draft_schedule


def dims(s):
    di = s["ssm_expand"] * s["d_model"]
    nh = di // s["ssm_head_dim"]
    return di, s["ssm_state"], nh, s["ssm_head_dim"]


def padded_vocab(s):
    return -(-s["vocab_size"] // 256) * 256


def weight_shapes(s):
    d, L = s["d_model"], s["num_layers"]
    di, ns, nh, _ = dims(s)
    cc = di + 2 * ns
    dt = s["dtype"]
    return {
        "embed": {"tok": ((padded_vocab(s), d), dt, "normal", 0.02)},
        "blocks": {
            "ln1": ((L, d), dt, "zeros", 0),
            "ssm": {
                "w_in": ((L, d, 2 * di + 2 * ns + nh), dt, "normal",
                         1 / math.sqrt(d)),
                "conv_w": ((L, s["ssm_conv"], cc), dt, "normal",
                           1 / math.sqrt(s["ssm_conv"])),
                "conv_b": ((L, cc), dt, "zeros", 0),
                "A_log": ((L, nh), "float32", "a_log", 0),
                "Dp": ((L, nh), "float32", "ones", 0),
                "dt_bias": ((L, nh), "float32", "dt_bias", 0),
                "ssm_norm": ((L, di), dt, "zeros", 0),
                "w_out": ((L, di, d), dt, "normal", 1 / math.sqrt(di)),
            },
        },
        "final_norm": ((d,), dt, "zeros", 0),
    }


def make_weights(sizes, seed):
    """Mamba-2's usual init: A = -U(1, 16), dt = U(1e-3, 1e-1) through
    softplus, D = 1, zero norms (scale 1) and conv bias, normal
    projections at 1/sqrt(fan-in), embedding at 0.02. One jitted call on
    the device, each leaf in the dtype it is served in."""
    spec = weight_shapes(sizes)
    paths = jax.tree_util.tree_leaves_with_path(
        spec, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def build(seed):
        key = jax.random.PRNGKey(seed)
        flat = []
        for i, (_, (shape, dtype, kind, scale)) in enumerate(paths):
            k = jax.random.fold_in(key, i)
            if kind == "normal":
                leaf = jax.random.normal(k, shape, jnp.float32) * scale
            elif kind == "zeros":
                leaf = jnp.zeros(shape, jnp.float32)
            elif kind == "ones":
                leaf = jnp.ones(shape, jnp.float32)
            elif kind == "a_log":
                leaf = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                  1.0, 16.0))
            else:  # dt_bias: inverse softplus of U(1e-3, 1e-1)
                leaf = jnp.log(jnp.expm1(jax.random.uniform(
                    k, shape, jnp.float32, 1e-3, 1e-1)))
            flat.append(leaf.astype(dtype))
        treedef = jax.tree_util.tree_structure(
            spec, is_leaf=lambda x: isinstance(x, tuple))
        return jax.tree_util.tree_unflatten(treedef, flat)

    return build(jnp.int32(seed))


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + w)


def mixer(lw, x, state, conv, s, nx):
    """One token through one layer's mixer: (out [B,D], state, conv)."""
    di, ns, nh, p = dims(s)
    w = lw["ssm"]
    zx = nx.mm("bd,de->be", x, w["w_in"])
    z, xbc, dt = zx[:, :di], zx[:, di:2 * di + 2 * ns], zx[:, 2 * di + 2 * ns:]
    conv = jnp.concatenate([conv[:, 1:], xbc[:, None]], axis=1)
    xc = jax.nn.silu(jnp.einsum("bwc,wc->bc", conv, w["conv_w"],
                                precision=jax.lax.Precision.HIGHEST)
                     + w["conv_b"])
    xp, Bm, Cm = xc[:, :di], xc[:, di:di + ns], xc[:, di + ns:]
    dt = jax.nn.softplus(dt + w["dt_bias"])
    dA = jnp.exp(dt * -jnp.exp(w["A_log"]))
    xh = xp.reshape(-1, nh, p)
    state = dA[:, :, None, None] * state \
        + dt[:, :, None, None] * xh[..., None] * Bm[:, None, None, :]
    y = nx.mm("bhpn,bn->bhp", state, Cm) + w["Dp"][None, :, None] * xh
    y = rms(y.reshape(-1, di) * jax.nn.silu(z), w["ssm_norm"], s["norm_eps"])
    return nx.mm("be,ed->bd", y, w["w_out"]), state, conv


def logits_of(W, h, s, nx):
    h = rms(h, W["final_norm"], s["norm_eps"])
    return nx.mm("bd,vd->bv", h, W["embed"]["tok"][:s["vocab_size"]])


def _full(W, tok, states, convs, s, nx):
    """(logits [B,V], states, convs, increments [L, 2, B, D])."""
    h = W["embed"]["tok"][tok]

    def body(h, xs):
        lw, st, cv = xs
        out, st, cv = mixer(lw, rms(h, lw["ln1"], s["norm_eps"]), st, cv,
                            s, nx)
        return h + out, (st, cv, jnp.stack([out, jnp.zeros_like(out)]))

    h, (states, convs, br) = jax.lax.scan(body, h,
                                          (W["blocks"], states, convs))
    return logits_of(W, h, s, nx), states, convs, br


full_step = jax.jit(_full, static_argnames=("s", "nx"))


@partial(jax.jit, static_argnames=("s", "nx", "vl"))
def draft_step(W, tok, states, convs, preds, *, s, nx, vl):
    h = W["embed"]["tok"][tok]
    vmask = jnp.arange(s["num_layers"]) == vl

    def body(h, xs):
        lw, st, cv, pr, real = xs
        out, st, cv = mixer(lw, rms(h, lw["ln1"], s["norm_eps"]), st, cv,
                            s, nx)
        inc = jnp.where(real, out, pr[0]) + jnp.where(real, 0.0, pr[1])
        return h + inc, (st, cv)

    h, (states, convs) = jax.lax.scan(
        body, h, (W["blocks"], states, convs, preds, vmask))
    return logits_of(W, h, s, nx), states, convs


@partial(jax.jit, static_argnames=("s", "nx"))
def prefill(W, prompts, *, s, nx):
    """prompts [B, P] -> (last logits [B,V], states, convs)."""
    di, ns, nh, p = dims(s)
    L, B = s["num_layers"], prompts.shape[0]
    states = jnp.zeros((L, B, nh, p, ns), jnp.float32)
    convs = jnp.zeros((L, B, s["ssm_conv"], di + 2 * ns), jnp.float32)

    def body(carry, tok):
        states, convs, _ = carry
        logits, states, convs, _ = _full(W, tok, states, convs, s, nx)
        return (states, convs, logits), None

    V = s["vocab_size"]
    (states, convs, logits), _ = jax.lax.scan(
        body, (states, convs, jnp.zeros((B, V), jnp.float32)), prompts.T)
    return logits, states, convs


def as_f32(W):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), W)


CANDIDATES = 8      # most first-token candidates tried per prompt


def first_tokens(W, prompts, served0, sizes, nx=F32):
    """The first generated token of each prompt, which the engine feeds
    at decode step 0 but does not return. Of the ``CANDIDATES`` tokens
    with the best prefill logits (the program may break a near-tie
    either way), the one kept is the candidate under which the first
    served token lies least below the best logit of step 0."""
    s = Sizes(sizes)
    W = as_f32(W)
    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, first in zip(prompts, served0):
            lg, st, cv = prefill(W, jnp.asarray(prompt, jnp.int32)[None],
                                 s=s, nx=nx)
            cands = np.argsort(-np.asarray(lg[0], np.float64))[:CANDIDATES]
            lg1, _, _, _ = full_step(
                W, jnp.asarray(cands, jnp.int32),
                jnp.repeat(st, CANDIDATES, axis=1),
                jnp.repeat(cv, CANDIDATES, axis=1), s=s, nx=nx)
            lg1 = np.asarray(lg1, np.float64)
            gap = lg1.max(-1) - lg1[:, int(first)]
            out.append(int(cands[int(np.argmin(gap))]))
    return out


@jax.jit
def _gaps(judge, served, other):
    """Per row: how far below the judge's best logit lie the served
    token and the token ``other`` puts first."""
    best = judge.max(-1)
    pick = lambda lg, t: jnp.take_along_axis(lg, t[:, None], -1)[:, 0]  # noqa
    return best - pick(judge, served), \
        best - pick(judge, jnp.argmax(other, -1))


def teacher_forced(W, rows, sizes, speca, nxs=(F32,), pad_to=None):
    """SpeCa greedy decode forced along each row's served tokens.

    ``rows`` is a list of (prompt [P] int, tok0 int, served [n] int):
    step k feeds ``tok0`` (k = 0) or ``served[k-1]`` and should put
    ``served[k]`` first. Each prompt is prefilled alone, then the rows
    decode as one batch padded to ``pad_to`` rows, so the programs keep
    one shape from run to run. ``nxs[0]`` is the judge; each further
    numerics runs the same steps alongside it.

    Returns (served_gap [rows, n_max], other_gaps): how far the served
    token's logit lies below the judge's best at each step, and per
    further numerics how far the token it puts first lies below that
    best (NaN past a row's end)."""
    s = Sizes(sizes)
    W = as_f32(W)
    vl = speca["verify_layer"] % sizes["num_layers"]
    n_max = max(len(r[2]) for r in rows)
    R = max(pad_to or 0, len(rows))
    plan = draft_schedule(n_max, speca["taylor_order"], speca["max_draft"])
    inputs = np.zeros((R, n_max), np.int32)
    served = np.zeros((R, n_max), np.int32)
    for j, (_, tok0, toks) in enumerate(rows):
        n = len(toks)
        inputs[j, 0] = tok0
        inputs[j, 1:n] = toks[:n - 1]
        served[j, :n] = toks
    with jax.default_matmul_precision("highest"):
        runs = []
        for nx in nxs:
            st, cv = [], []
            for j in range(R):
                prompt = rows[min(j, len(rows) - 1)][0]
                _, a, b = prefill(W, jnp.asarray(prompt, jnp.int32)[None],
                                  s=s, nx=nx)
                st.append(a)
                cv.append(b)
            runs.append([jnp.concatenate(st, axis=1),
                         jnp.concatenate(cv, axis=1),
                         TaylorTable(speca["taylor_order"])])
        g_served = np.zeros((R, n_max))
        g_other = np.zeros((len(nxs) - 1, R, n_max))
        for k, draft in enumerate(plan):
            tok = jnp.asarray(inputs[:, k])
            lgs = []
            for nx, run in zip(nxs, runs):
                states, convs, table = run
                if draft:
                    lg, states, convs = draft_step(
                        W, tok, states, convs, table.predict(k), s=s,
                        nx=nx, vl=vl)
                else:
                    lg, states, convs, br = full_step(
                        W, tok, states, convs, s=s, nx=nx)
                    table.update(br, k)
                run[0], run[1] = states, convs
                lgs.append(lg)
            srv = jnp.asarray(served[:, k])
            for i, other in enumerate(lgs[1:] or lgs[:1]):
                a, b = _gaps(lgs[0], srv, other)
                if i == 0:
                    g_served[:, k] = np.asarray(a)
                if len(lgs) > 1:
                    g_other[i, :, k] = np.asarray(b)
    for j, (_, _, toks) in enumerate(rows):
        g_served[j, len(toks):] = np.nan
        g_other[:, j, len(toks):] = np.nan
    return g_served[:len(rows)], g_other[:, :len(rows)]


"""Plain float32 reference of class-conditional DiT (arXiv:2212.09748)
sampled by DDIM with SpeCa's forecast-then-verify schedule.

Written from the published equations in straightforward ``jax.numpy``;
it imports nothing of the program. The weights come from
``make_weights``, the benchmark's own recipe, which the harness also
hands to the program: the reference rebuilds them from the seed rather
than reading the program's copy.

Model: patchify (p x p x C -> token), linear patch embedding plus fixed
sin/cos positions; the conditioning vector is an MLP of the sinusoidal
timestep embedding plus a class embedding (last row = null class). Each
block is AdaLN-Zero: six modulation vectors from ``silu(c)``; attention
branch ``g_a * attn(LN(h)(1+s_a)+b_a)`` then MLP branch ``g_m *
mlp(LN(h)(1+s_m)+b_m)`` with tanh-GELU; LayerNorms carry no parameters.
The head is AdaLN (shift, scale) + linear + unpatchify.

SpeCa: a full step stores every layer's two branch increments in a
TaylorSeer table; a drafted step forecasts them, computes only the
verify layer for real on the forecast stream, and takes the head of
the result. With the benchmark's threshold every draft is accepted, so
which steps draft is a fixed function of the step index
(``numerics.draft_schedule``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.numerics import F32, Sizes, TaylorTable, draft_schedule


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def weight_shapes(s):
    """(path, shape, dtype, scale) of every leaf; scale 0 = zeros."""
    d, L, ff = s["d_model"], s["num_layers"], s["d_ff"]
    hd = d // s["num_heads"]
    pc = s["patch_size"] ** 2 * s["in_channels"]
    dt = s["dtype"]
    f32 = "float32"
    inv = lambda n: 1.0 / math.sqrt(n)  # noqa: E731
    return [
        (("embed", "patch_w"), (pc, d), dt, inv(pc)),
        (("embed", "patch_b"), (d,), dt, 0.0),
        (("embed", "time", "w1"), (d, d), f32, inv(d)),
        (("embed", "time", "b1"), (d,), f32, 0.0),
        (("embed", "time", "w2"), (d, d), f32, inv(d)),
        (("embed", "time", "b2"), (d,), f32, 0.0),
        (("embed", "label"), (s["num_classes"] + 1, d), dt, 0.02),
        (("blocks", "wq"), (L, d, d), dt, inv(d)),
        (("blocks", "wk"), (L, d, d), dt, inv(d)),
        (("blocks", "wv"), (L, d, d), dt, inv(d)),
        (("blocks", "wo"), (L, d, d), dt, inv(d)),
        (("blocks", "mlp", "w_up"), (L, d, ff), dt, inv(d)),
        (("blocks", "mlp", "w_down"), (L, ff, d), dt, inv(ff)),
        (("blocks", "mod_w"), (L, d, 6 * d), dt, inv(d)),
        (("blocks", "mod_b"), (L, 6 * d), dt, inv(6 * d)),
        (("head", "w"), (d, pc), dt, inv(d)),
        (("head", "b"), (pc,), dt, inv(pc)),
        (("head", "mod_w"), (d, 2 * d), dt, inv(d)),
        (("head", "mod_b"), (2 * d,), dt, inv(2 * d)),
    ]


def make_weights(sizes, seed):
    """Every leaf N(0, 1)·scale from ``fold_in(PRNGKey(seed), i)``, in
    the dtype it is served in; one jitted call on the device."""
    leaves = weight_shapes(sizes)

    @jax.jit
    def build(seed):
        key = jax.random.PRNGKey(seed)
        out = {}
        for i, (path, shape, dtype, scale) in enumerate(leaves):
            if scale == 0.0:
                leaf = jnp.zeros(shape, dtype)
            else:
                leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * scale).astype(dtype)
            node = out
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = leaf
        return out

    return build(jnp.int32(seed))


# ---------------------------------------------------------------------------
# DDIM (cosine schedule, eps prediction)
# ---------------------------------------------------------------------------

def ddim_tables(diff):
    T, S = diff["num_train_timesteps"], diff["num_inference_steps"]
    s = 0.008
    ts = np.arange(T + 1, dtype=np.float64) / T
    f = np.cos((ts + s) / (1 + s) * math.pi / 2) ** 2
    ab = f / f[0]
    betas = np.clip(1 - ab[1:] / ab[:-1], 0, 0.999)
    alphas_bar = np.cumprod(1.0 - betas).astype(np.float32)
    steps = (np.arange(S) * (T // S))[::-1].copy()
    prev = np.concatenate([steps[1:], [-1]])
    ab_t = alphas_bar[steps]
    ab_p = np.where(prev >= 0, alphas_bar[np.maximum(prev, 0)], 1.0)
    return steps.astype(np.float32), ab_t.astype(np.float32), \
        ab_p.astype(np.float32)


def ddim_update(x, eps, ab_t, ab_p):
    x0 = (x - jnp.sqrt(1.0 - ab_t) * eps) / jnp.sqrt(ab_t)
    return jnp.sqrt(ab_p) * x0 + jnp.sqrt(1.0 - ab_p) * eps


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------

def sincos(t, dim, max_period=10_000.0):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def layer_norm(x, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def patchify(x, p):
    b, h, w, c = x.shape
    x = x.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def unpatchify(tok, p, h, w, c):
    b = tok.shape[0]
    x = tok.reshape(b, h // p, w // p, p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h, w, c)


def embed(W, x, t, labels, s, nx):
    h = nx.mm("btp,pd->btd", patchify(x, s["patch_size"]),
              W["embed"]["patch_w"]) + W["embed"]["patch_b"]
    h = h + sincos(jnp.arange(h.shape[1]), s["d_model"])[None]
    tw = W["embed"]["time"]
    c = jax.nn.silu(nx.mm("bd,de->be", sincos(t, s["d_model"]), tw["w1"])
                    + tw["b1"])
    c = nx.mm("bd,de->be", c, tw["w2"]) + tw["b2"]
    c = c + W["embed"]["label"][labels]
    return h, c


def block(bw, h, c, s, nx):
    """One AdaLN-Zero block: returns its two branch increments."""
    eps, H = s["norm_eps"], s["num_heads"]
    mod = nx.mm("bd,de->be", jax.nn.silu(c), bw["mod_w"]) + bw["mod_b"]
    sh_a, sc_a, g_a, sh_m, sc_m, g_m = jnp.split(mod, 6, axis=-1)
    x = layer_norm(h, eps) * (1 + sc_a[:, None]) + sh_a[:, None]
    B, T, D = x.shape
    q = nx.mm("btd,de->bte", x, bw["wq"]).reshape(B, T, H, D // H)
    k = nx.mm("btd,de->bte", x, bw["wk"]).reshape(B, T, H, D // H)
    v = nx.mm("btd,de->bte", x, bw["wv"]).reshape(B, T, H, D // H)
    a = jax.nn.softmax(nx.mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(D // H),
                       axis=-1)
    o = nx.mm("bhqk,bkhd->bqhd", a, v).reshape(B, T, D)
    inc0 = g_a[:, None] * nx.mm("btd,de->bte", o, bw["wo"])
    h1 = h + inc0
    x = layer_norm(h1, eps) * (1 + sc_m[:, None]) + sh_m[:, None]
    u = jax.nn.gelu(nx.mm("btd,df->btf", x, bw["mlp"]["w_up"]),
                    approximate=True)
    inc1 = g_m[:, None] * nx.mm("btf,fd->btd", u, bw["mlp"]["w_down"])
    return inc0, inc1


def head(W, h, c, s, nx):
    hw = W["head"]
    mod = nx.mm("bd,de->be", jax.nn.silu(c), hw["mod_w"]) + hw["mod_b"]
    shift, scale = jnp.split(mod, 2, axis=-1)
    x = layer_norm(h, s["norm_eps"]) * (1 + scale[:, None]) + shift[:, None]
    x = nx.mm("btd,dp->btp", x, hw["w"]) + hw["b"]
    n = s["latent_size"]
    return unpatchify(x, s["patch_size"], n, n, s["in_channels"])


def _layer(W, l):
    return jax.tree_util.tree_map(lambda a: a[l], W["blocks"])


@partial(jax.jit, static_argnames=("s", "nx"))
def full_step(W, x, t, labels, *, s, nx):
    """Full forward: (eps [B,H,W,C], branches [L, 2, B, T, D])."""
    h, c = embed(W, x, t, labels, s, nx)

    def body(h, bw):
        inc0, inc1 = block(bw, h, c, s, nx)
        return h + inc0 + inc1, jnp.stack([inc0, inc1])

    h, br = jax.lax.scan(body, h, W["blocks"])
    return head(W, h, c, s, nx), br


@partial(jax.jit, static_argnames=("s", "nx", "vl"))
def draft_step(W, x, t, labels, preds, *, s, nx, vl):
    """Drafted forward: every layer but ``vl`` takes its forecast
    increments; ``vl`` runs for real on the forecast stream."""
    h, c = embed(W, x, t, labels, s, nx)
    for l in range(s["num_layers"]):
        if l == vl:
            inc0, inc1 = block(_layer(W, l), h, c, s, nx)
        else:
            inc0, inc1 = preds[l, 0], preds[l, 1]
        h = h + inc0 + inc1
    return head(W, h, c, s, nx)


def sample(W, noise, labels, sizes, diff, speca, nx=F32):
    """SpeCa DDIM samples of a batch of requests: noise [B,H,W,C] f32,
    labels [B] -> latents [B,H,W,C] f32."""
    if (diff.get("schedule", "cosine"), diff.get("prediction", "epsilon")) \
            != ("cosine", "epsilon"):
        raise ValueError("the reference samples DDIM on a cosine schedule "
                         "with eps prediction only")
    s = Sizes(sizes)
    W = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), W)
    t_model, ab_t, ab_p = ddim_tables(diff)
    plan = draft_schedule(len(t_model), speca["taylor_order"],
                          speca["max_draft"])
    vl = speca["verify_layer"] % sizes["num_layers"]
    table = TaylorTable(speca["taylor_order"])
    x = jnp.asarray(noise, jnp.float32)
    labels = jnp.asarray(labels, jnp.int32)
    B = x.shape[0]
    with jax.default_matmul_precision("highest"):
        for i, draft in enumerate(plan):
            t = jnp.full((B,), t_model[i], jnp.float32)
            if draft:
                eps = draft_step(W, x, t, labels, table.predict(i), s=s,
                                 nx=nx, vl=vl)
            else:
                eps, br = full_step(W, x, t, labels, s=s, nx=nx)
                table.update(br, i)
            x = ddim_update(x, eps, ab_t[i], ab_p[i])
    return x

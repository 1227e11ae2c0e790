"""Arithmetic shared by the plain references: matmul precision, the
TaylorSeer difference table and the accept schedule.

The references compute in float32 with every matmul at ``highest``
precision. ``round_bits`` is the lower-precision control: it rounds each
matmul operand to the mantissa of a narrower float (3 bits: fp8 e4m3)
before an f32-accumulated product, the step a change that moved the
model to fp8 matmuls would take.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# mantissa bits of the control's matmul operands (fp8 e4m3)
CONTROL_MANTISSA_BITS = 3


def round_mantissa(x, bits: int):
    """``x`` rounded to ``bits`` explicit mantissa bits (round half to
    even), exponent range unbounded."""
    m, e = jnp.frexp(x.astype(jnp.float32))
    scale = float(2 ** (bits + 1))
    return jnp.ldexp(jnp.round(m * scale) / scale, e)


class Numerics:
    """How the reference multiplies: ``mm(spec, a, b)`` is an einsum in
    f32 at highest precision, with both operands first rounded to
    ``bits`` mantissa bits when ``bits`` is set (the control)."""

    def __init__(self, bits=None):
        self.bits = bits

    def q(self, x):
        x = x.astype(jnp.float32)
        return x if self.bits is None else round_mantissa(x, self.bits)

    def mm(self, spec: str, a, b):
        return jnp.einsum(spec, self.q(a), self.q(b),
                          precision=jax.lax.Precision.HIGHEST)


F32 = Numerics()
CONTROL = Numerics(CONTROL_MANTISSA_BITS)


class Sizes(dict):
    """A configuration's sizes, usable as a static argument of ``jit``."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def draft_schedule(num_steps: int, order: int, max_draft: int):
    """Per schedule step, whether the lane drafts it (True) or runs the
    full forward (False), for a lane whose every draft is accepted: it
    drafts once its table holds more than ``order`` anchors and while it
    has drafted fewer than ``max_draft`` steps in a row."""
    out, anchors, since = [], 0, 0
    for _ in range(num_steps):
        draft = anchors > order and since < max_draft
        out.append(draft)
        if draft:
            since += 1
        else:
            anchors += 1
            since = 0
    return out


class TaylorTable:
    """TaylorSeer difference table of one batch whose rows share an
    anchor history (every row follows the same schedule). Planes hold
    Δ⁰..Δᵐ of the per-layer branch increments at the newest anchor."""

    def __init__(self, order: int):
        self.order = order
        self.diffs = None
        self.n_anchors = 0
        self.anchor_step = -1
        self.gap = 1.0

    def update(self, feats, step: int):
        feats = feats.astype(jnp.float32)
        if self.diffs is None:
            old = [jnp.zeros_like(feats)] * (self.order + 1)
        else:
            old = self.diffs
        rows = [feats]
        for i in range(1, self.order + 1):
            rows.append(rows[i - 1] - old[i - 1])
        self.diffs = rows
        gap = float(step - self.anchor_step) if self.anchor_step >= 0 else 1.0
        self.gap = max(gap, 1.0)
        self.anchor_step = step
        self.n_anchors += 1

    def weights(self, step: int):
        d = float(step - self.anchor_step)
        return [d ** i / (math.factorial(i) * self.gap ** i)
                if i < self.n_anchors else 0.0
                for i in range(self.order + 1)]

    def predict(self, step: int):
        w = self.weights(step)
        return _weighted_sum(jnp.asarray(w, jnp.float32), self.diffs)


@jax.jit
def _weighted_sum(w, planes):
    acc = w[0] * planes[0]
    for i in range(1, len(planes)):
        acc = acc + w[i] * planes[i]
    return acc

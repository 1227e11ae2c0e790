"""Device-side lane telemetry accumulation — the zero-sync half.

Every scheduler tick the lane step already returns a flags pytree
(``n_spec``/``n_drafted``/``full``/``advanced``/``err``/... — see
``repro.core.lane_step.COUNTER_FLAGS``). The engine keeps those arrays
on device and only materialises them when a request completes. The
``LaneAccumulator`` rides exactly that discipline:

  * ``update(flags)`` folds one tick's flags into a small on-device
    accumulator pytree with ONE jitted call. JAX dispatch is
    asynchronous, so this never blocks the host — observed traffic adds
    **zero extra host syncs** (the house rule this module exists to
    keep).
  * ``flush_into(metrics, **labels)`` is the single materialisation
    point: it pulls the accumulator to host (``np.asarray`` — the only
    sync, and only when the caller explicitly asks for a snapshot),
    merges the totals and the pre-binned ``chain_err`` histogram into a
    ``MetricsRegistry``, and resets the accumulator (delta semantics —
    flushing twice never double-counts).

The chain-err histogram is binned ON DEVICE with ``searchsorted`` +
scatter-add over log-spaced edges, so quantiles of millions of per-lane
errors cost a fixed ~2·(len(edges)+1) floats of transfer at flush time,
not O(observations).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .registry import MetricsRegistry

# Log-spaced relative-error bucket edges: SpeCa accept thresholds live
# around 1e-2..1e0, so the grid brackets them with headroom both ways.
DEFAULT_ERR_EDGES: Tuple[float, ...] = tuple(
    float(x) for x in np.geomspace(1e-6, 1e2, 25))

_SUM_KEYS = ("n_spec", "n_drafted", "full", "advanced", "attempted")


def _zero_acc(n_edges: int) -> Dict[str, jnp.ndarray]:
    return {
        "sums": jnp.zeros((len(_SUM_KEYS),), jnp.float64
                          if jax.config.jax_enable_x64 else jnp.float32),
        "ticks": jnp.zeros((), jnp.int32),
        "err_counts": jnp.zeros((n_edges + 1,), jnp.float32),
        "err_sum": jnp.zeros((), jnp.float32),
        "err_count": jnp.zeros((), jnp.float32),
    }


@functools.partial(jax.jit, static_argnames=("edges",), donate_argnums=(0,))
def speca_lane_accumulate(acc: Dict[str, jnp.ndarray],
                          flat: Dict[str, jnp.ndarray],
                          edges: Tuple[float, ...]
                          ) -> Dict[str, jnp.ndarray]:
    """Fold one tick's counter flags into the accumulator (pure, jitted,
    buffers donated so steady-state accumulation allocates nothing new).
    Its program shows in a profile as ``jit_speca_lane_accumulate``.
    """
    sums = acc["sums"] + jnp.stack(
        [jnp.sum(flat[k].astype(acc["sums"].dtype)) for k in _SUM_KEYS])
    err = flat["err"].reshape(-1).astype(jnp.float32)
    finite = jnp.isfinite(err)
    # searchsorted over the shared edge grid; masked rows are parked in
    # a scratch bucket one past +Inf and dropped.
    e = jnp.asarray(edges, jnp.float32)
    idx = jnp.searchsorted(e, err, side="left")
    idx = jnp.where(finite, idx, e.shape[0] + 1)
    hist = jnp.zeros((e.shape[0] + 2,), jnp.float32).at[idx].add(1.0)
    err_ok = jnp.where(finite, err, 0.0)
    return {
        "sums": sums,
        "ticks": acc["ticks"] + 1,
        "err_counts": acc["err_counts"] + hist[:-1],
        "err_sum": acc["err_sum"] + jnp.sum(err_ok),
        "err_count": acc["err_count"] + jnp.sum(finite.astype(jnp.float32)),
    }


class LaneAccumulator:
    """Per-session on-device counter accumulation (see module docstring).

    One instance per engine session (per workload tag); ``labels`` are
    merged into every metric it flushes.
    """

    def __init__(self, err_edges: Tuple[float, ...] = DEFAULT_ERR_EDGES
                 ) -> None:
        self.err_edges = tuple(float(e) for e in err_edges)
        self._acc = _zero_acc(len(self.err_edges))

    def update(self, flags: Dict[str, Any]) -> None:
        """Fold one tick's lane-step flags in. Device-only: dispatches
        one jitted program and returns without waiting on it."""
        flat = {k: flags[k] for k in _SUM_KEYS}
        flat["err"] = flags["chain_err"] if "chain_err" in flags \
            else flags["err"]
        self._acc = speca_lane_accumulate(self._acc, flat,
                                          self.err_edges)

    def flush_into(self, metrics: MetricsRegistry, **labels: Any) -> None:
        """Materialise (the one host sync), merge into ``metrics``,
        reset. Counter totals land as ``speca_<key>_total``; the binned
        errors as the ``speca_chain_err`` histogram."""
        acc, self._acc = self._acc, _zero_acc(len(self.err_edges))
        host = {k: np.asarray(v) for k, v in jax.device_get(acc).items()}
        for i, k in enumerate(_SUM_KEYS):
            metrics.counter(f"speca_{k}_total", **labels).inc(
                float(host["sums"][i]))
        metrics.counter("speca_obs_ticks_total", **labels).inc(
            float(host["ticks"]))
        metrics.histogram("speca_chain_err", edges=self.err_edges,
                          **labels).add_counts(
            host["err_counts"], float(host["err_sum"]),
            float(host["err_count"]))
        n_spec = float(host["sums"][_SUM_KEYS.index("n_spec")])
        n_drafted = float(host["sums"][_SUM_KEYS.index("n_drafted")])
        if n_drafted > 0:
            metrics.gauge("speca_draft_accept_rate", **labels).set(
                n_spec / n_drafted)

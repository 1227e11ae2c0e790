"""What the per-layer metric files read. Each metric file under
``bench/metrics/`` names one of these and the configuration family it
belongs to; a reader returns None where it finds nothing to read, and
the harness then leaves the metric out."""
from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import Any, Optional


@dataclass
class Context:
    suffix: str          # the family's metric suffix: "dit" or "decode"
    window: Any          # loop.Window of the traced window
    reduced: Any         # trace.Reduced of the same window
    system: Any
    peak: dict
    chips: int


def lane_occupancy(ctx: Context) -> Optional[float]:
    """Mean share of lanes holding a request after each tick, in %."""
    occ = ctx.window.occupancy
    return 100.0 * statistics.fmean(occ) if occ else None


def tick_host_ms(ctx: Context) -> Optional[float]:
    """Median host wall time of one ``engine.tick()``, in ms."""
    t = ctx.window.ticks
    return 1e3 * statistics.median(b - a for a, b in t) if t else None


def device_ms_per_tick(ctx: Context) -> Optional[float]:
    """Device busy time per traced tick, in ms."""
    r = ctx.reduced
    return 1e3 * r.busy_s / r.ticks if r.ticks and r.busy_s > 0 else None


def full_branch_share(ctx: Context) -> Optional[float]:
    """Share of traced ticks whose lane step ran the full-forward branch
    (counted by the table-update kernel, which runs only there), in %."""
    r = ctx.reduced
    n = r.kernel_calls.get("update", 0)
    return 100.0 * n / r.ticks if n and r.ticks else None


def predict_roofline(ctx: Context) -> Optional[float]:
    """Least time of one Taylor predict call over its measured time, in
    %. The least time is the larger of FLOPs over the bf16 peak and
    bytes over the HBM peak, counted from the live table leaf and the
    forecast it writes; the kernel is bound by memory."""
    r = ctx.reduced
    n = r.kernel_calls.get("predict", 0)
    if not n:
        return None
    flops, nbytes = ctx.system.predict_cost()
    least = max(flops / ctx.peak["bf16_flop_per_s"],
                nbytes / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (r.kernel_seconds["predict"] / n)


def mfu(ctx: Context) -> Optional[float]:
    """FLOPs of the requests finished in the traced window (full steps
    at the full forward's count, drafted steps at the drafted step's),
    over the window, over the chips' bf16 peak, in %."""
    w = ctx.window
    if not w.done:
        return None
    total = sum(d.flops for d in w.done)
    return 100.0 * total / w.seconds / (ctx.peak["bf16_flop_per_s"]
                                        * ctx.chips)


def idle_share(ctx: Context) -> Optional[float]:
    """Share of the traced window with no operation on the device, in %
    (averaged over the chips used)."""
    r = ctx.reduced
    return 100.0 * r.idle_share if r.window_s > 0 and r.busy_s > 0 else None


def for_family(reader, suffix: str):
    """``reader`` restricted to cells of one family."""
    def read(ctx: Context):
        return reader(ctx) if ctx.suffix == suffix else None
    read.__doc__ = reader.__doc__
    return read

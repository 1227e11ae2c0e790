"""One run of one cell: set-up, the window, the check, the result line.

``run`` takes the command line of ``bench/run.py``. Tests reach it with
``require_chip=False`` and a ``spec`` dict that stands in for
``BENCHMARK.json`` and the configuration and traffic files, and may
``patch`` the built system to break its timed path; none of that is on
the command line.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TRACE_SECONDS = 4.0          # longest traced window of a --trace 1 run
TRACE_SETTLE_SECONDS = 0.5   # traffic run under the profiler before it
WARM_TICKS = 60              # least warm-up, in ticks


class NoChip(SystemExit):
    pass


def _args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    """BENCHMARK.json with each cell's configuration and traffic files."""
    bench = _load_json(ROOT / "BENCHMARK.json")
    return {"bench": bench,
            "configs": {c["name"]: _load_json(ROOT / c["file"])
                        for c in bench["configs"]},
            "traffic": {w["traffic"]: _load_json(
                BENCH / "traffic" / f"{w['traffic']}.json")
                for w in bench["workloads"]}}


def require_chip(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU, JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX sees "
                     f"{len(devs)}")


def peaks_for(kind: str) -> dict:
    peaks = _load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return peaks[kind]


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def p95(values):
    """95th percentile, linear between order statistics."""
    v = sorted(values)
    if len(v) == 1:
        return v[0]
    return statistics.quantiles(v, n=20, method="inclusive")[18]


def _passes(value, limit, kind) -> bool:
    if value is None or (isinstance(value, float) and math.isnan(value)):
        return False
    return value >= limit if kind == "min" else value <= limit


def _per_layer(bench, cell, family, system, drv, counter, seconds,
               peak, chips):
    """The traced window: per-layer metrics, device times, breakdown."""
    import jax
    from bench.harness import trace as TR
    from bench.harness.readers import Context
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tdir)
        # the profiler's first moments stall the device: let them pass
        # outside the traced window, which starts at the first tick span
        drv.measure(TRACE_SETTLE_SECONDS)
        window = drv.measure(min(seconds, TRACE_SECONDS), annotate=True,
                             counter=counter)
        jax.block_until_ready(system.table())
        jax.profiler.stop_trace()
        reduced = TR.reduce(TR.load(tdir), family.KERNELS)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    ctx = Context(suffix=family.SUFFIX, window=window, reduced=reduced,
                  system=system, peak=peak, chips=chips)
    metrics = {}
    for m in bench["per_layer"]:
        if cell["name"] in m.get("workloads", [cell["name"]]):
            value = _reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"busy_s": reduced.busy_s, "window_s": reduced.window_s}
    breakdown = {"device_ops": [list(kv) for kv in reduced.top_ops()],
                 "idle_gaps": [list(g) for g in reduced.idle_gaps]}
    return window, metrics, device, breakdown


def _end_to_end(bench, cell, family, drv, counter, seconds, t_start):
    """The untraced window: the cell's end-to-end metrics."""
    window = drv.measure(seconds, counter=counter)
    lat = [d.latency_s for d in window.done]
    values = {family.RATE: sum(d.units for d in window.done)
              / window.seconds,
              "latency_p95_s": p95(lat) if lat else None,
              "setup_s": window.t0 - t_start}
    metrics = {}
    for m in bench["end_to_end"]:
        if cell["name"] in m.get("workloads", [cell["name"]]) \
                and values.get(m["name"]) is not None:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    return window, metrics


def run(argv=None, *, spec: Optional[dict] = None, require: bool = True,
        patch: Optional[Callable] = None, t_start: Optional[float] = None,
        out=None) -> dict:
    """One run; prints the result line and returns it as a dict."""
    t_start = time.perf_counter() if t_start is None else t_start
    args = _args(argv)
    spec = spec or load_spec()
    bench = spec["bench"]
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    chips = int(cell["chips"])
    if require:
        require_chip(chips)
    import jax
    if require:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        # cache every program, however quick to compile, so that a
        # second run in this checkout compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from bench.harness import loop
    from bench.harness.traffic import ClosedLoop

    conf = spec["configs"][cell["config"]]
    family = importlib.import_module(f"bench.families.{conf['family']}")
    devs = jax.devices()[:chips]
    system = family.System(conf, args.seed, chips)
    if patch is not None:
        patch(system)
    drv = loop.Driver(system, ClosedLoop(spec["traffic"][cell["traffic"]],
                                         system.lanes, args.seed))
    counter = loop.CompileCounter()
    drv.warm_up(WARM_TICKS)
    breakdown = None
    if args.trace:
        peak = peaks_for(devs[0].device_kind) if require else spec["peak"]
        window, metrics, device, breakdown = _per_layer(
            bench, cell, family, system, drv, counter, args.seconds, peak,
            chips)
    else:
        window, metrics = _end_to_end(bench, cell, family, drv, counter,
                                      args.seconds, t_start)
        device = {}
    device.update(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs), memory_peak_bytes=int(max(
                      (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)))
    # the reference runs once the program's state is gone
    done = window.done
    system.free()
    drv = None
    gc.collect()
    limits = conf["check"]["limits"]
    checks = system.check(done, limits)
    failed = checks["counter_mismatches"][0] + sum(
        1 for d in done if d.check is not None and not all(
            _passes(v, limits[k], "max") for k, v in d.check.items()))
    result = {"correct": all(_passes(*c) for c in checks.values()),
              "attempted": len(done), "failed": int(failed),
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": lim, "bound": kind}
                        for k, (v, lim, kind) in checks.items()}
    print(f"window: {window.seconds:.3f} s, {len(window.ticks)} ticks, "
          f"{len(done)} requests, {window.compiles} compiles",
          file=sys.stderr)
    for k, (v, lim, kind) in checks.items():
        print(f"check {k}: {v} (limit {'>=' if kind == 'min' else '<='} "
              f"{lim})", file=sys.stderr)
    print(json.dumps(result), file=out or sys.stdout, flush=True)
    return result

"""Readings that set the limits of a cell's check, and its control.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --seconds 8

For each seed, one process serves the cell's traffic for a short window
at the cell's own load and compares a seeded sample of the finished
requests with the plain reference, as a run does (the lower reading).
For each control seed it also puts the reference at the control's
precision (fp8 e4m3 matmul operands) in the program's place on the same
requests (the upper reading). Benchmark runs never run the control.
Writes one JSON line per seed to standard output.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None, *, spec=None, require=True):
    import importlib
    from bench.harness import cell as C
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    spec = spec or C.load_spec()
    w = next(x for x in spec["bench"]["workloads"]
             if x["name"] == args.workload)
    chips = int(w["chips"])
    if require:
        C.require_chip(chips)
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness import loop
    from bench.harness.traffic import ClosedLoop
    conf = spec["configs"][w["config"]]
    family = importlib.import_module(f"bench.families.{conf['family']}")
    limits = conf["check"]["limits"]
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        system = family.System(conf, seed, chips)
        drv = loop.Driver(system, ClosedLoop(
            spec["traffic"][w["traffic"]], system.lanes, seed))
        drv.warm_up(C.WARM_TICKS)
        done = drv.measure(args.seconds).done
        system.free()
        drv = None
        gc.collect()
        row = {"seed": seed, "program": {
            k: v[0] for k, v in system.check(done, limits).items()},
            "per_request": [d.check for d in done if d.check]}
        if seed in ctl:
            row["control"] = {k: v[0] for k, v in system.check(
                done, limits, control=True).items()}
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
        system = None
        gc.collect()
    return rows


if __name__ == "__main__":
    main()

"""Benchmark entrypoint: one function per paper table/figure.

``python -m benchmarks.run``            — everything (slow: trains 3 models)
``python -m benchmarks.run --quick``    — reduced method lists
``python -m benchmarks.run --only table3_dit,roofline``
"""
from __future__ import annotations

import argparse
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    args = ap.parse_args()

    from benchmarks import ablations, analysis, perf_compare, roofline
    from benchmarks import table1_flux, table2_video, table3_dit

    quick_methods = ["full", "steps_0.2", "fora_5", "taylorseer_5_2",
                     "speca_0.3"]
    benches = {
        "roofline": lambda: roofline.run(),
        "perf_compare": perf_compare.run,
        "table3_dit": lambda: table3_dit.run(
            methods=quick_methods if args.quick else None),
        "table1_flux": lambda: table1_flux.run(
            methods=quick_methods if args.quick else None),
        "table2_video": lambda: table2_video.run(
            methods=quick_methods if args.quick else None,
            n_requests=4 if args.quick else 12),
        "table4_decay": ablations.table4_decay,
        "table5_threshold": ablations.table5_threshold,
        "table6_verify_layer": ablations.table6_verify_layer,
        "table7_draft": ablations.table7_draft,
        "table8_metrics": ablations.table8_metrics,
        "speedup_model": ablations.speedup_model_check,
        "table9_beyond_paper": ablations.table9_beyond_paper,
        "fig2_quality_curve": analysis.fig2_quality_curve,
        "fig6_layer_correlation": analysis.fig6_layer_correlation,
        "trajectory_analysis": analysis.trajectory_analysis,
    }
    selected = list(benches)
    if args.only:
        selected = [s.strip() for s in args.only.split(",")]

    failures = []
    for name in selected:
        t0 = time.time()
        print(f"\n######## {name} ########")
        try:
            benches[name]()
            print(f"[{name}] done in {time.time()-t0:.1f}s")
        except Exception:  # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")
    print("\nall benchmarks completed")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()

"""Table 2 analogue: text-to-video on the reduced HunyuanVideo-like model
(3D tokens: 4 frames × 16 tokens). VBench-proxy = conditioning score +
temporal consistency. Also runs the serving engine per-request to report
the sample-adaptive allocation split (paper §1: 57.5% of samples at 6.48×,
42.5% at 5.82×)."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks import common as C

METHODS = [
    "full",
    "steps_0.22",
    "fora_5",
    "taylorseer_5_1",
    "teacache_2.7",
    "speca_0.3", "speca_0.6",
]


def run(batch: int = 8, methods=None, seed: int = 5,
        n_requests: int = 12):
    cfg, dcfg, params = C.get_model("video")
    cond = C.make_cond(cfg, dcfg, batch)
    key = jax.random.PRNGKey(seed)
    templates = C.class_templates(cfg, dcfg)

    rows = []
    x_full = None
    for name in (methods or METHODS):
        res = C.run_method(name, cfg, dcfg, params, cond, batch, key)
        if name == "full":
            x_full = res.samples
        rows.append(C.evaluate(res, x_full, cfg, dcfg, cond, templates,
                               None))
    C.print_table("table2_video (t2v, RF 50 steps, 4 frames)", rows)
    C.write_result("table2_video", rows)

    # --- sample-adaptive allocation via the serving engine --------------
    from repro.configs import SpeCaConfig
    from repro.core.complexity import forward_flops
    from repro.serving import Request, SpeCaEngine, allocation_report
    import jax.numpy as jnp

    scfg = SpeCaConfig(taylor_order=2, max_draft=8, tau0=0.4, beta=0.9)
    engine = SpeCaEngine(cfg, params, dcfg, scfg)
    reqs = []
    for i in range(n_requests):
        c = C.make_cond(cfg, dcfg, 1, seed=1000 + i)
        reqs.append(Request(request_id=i, cond=c, seed=i))
    results = engine.serve(reqs)
    n_tok = (dcfg.latent_size // cfg.patch_size) ** 2 * dcfg.num_frames
    report = allocation_report(results, forward_flops(cfg, n_tok))
    report = {k: round(v, 4) for k, v in report.items()}
    print("\n== sample-adaptive allocation (serving engine) ==")
    print(report)
    C.write_result("table2_allocation", [report])
    return rows, report


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()

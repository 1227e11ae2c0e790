"""Table 1 analogue: text-to-image on the reduced FLUX-like model
(rectified flow, 50 steps, conditioning stub). Claim under test: at
matched acceleration SpeCa preserves ImageReward-proxy far better than
FORA/TeaCache/TaylorSeer (paper: 0.9355 vs 0.73–0.82 at 6.2–6.3×)."""
from __future__ import annotations

import jax

from benchmarks import common as C

METHODS = [
    "full",
    "steps_0.6", "steps_0.4", "steps_0.34",
    "fora_4", "fora_6",
    "taylorseer_5_2", "taylorseer_7_2",
    "teacache_1.8", "teacache_3.5", "teacache_5.3",
    "speca_0.1", "speca_0.3", "speca_0.6",
]


def run(batch: int = 16, methods=None, seed: int = 3):
    cfg, dcfg, params = C.get_model("flux")
    cond = C.make_cond(cfg, dcfg, batch)
    key = jax.random.PRNGKey(seed)
    templates = C.class_templates(cfg, dcfg)
    ref = C.reference_latents(cfg, dcfg, n=64)

    rows = []
    x_full = None
    for name in (methods or METHODS):
        res = C.run_method(name, cfg, dcfg, params, cond, batch, key)
        if name == "full":
            x_full = res.samples
        rows.append(C.evaluate(res, x_full, cfg, dcfg, cond, templates, ref))
    C.print_table("table1_flux (t2i, rectified flow 50 steps)", rows)
    C.write_result("table1_flux", rows)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()

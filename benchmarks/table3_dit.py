"""Table 3 analogue: class-conditional generation on the reduced DiT.

Methods: DDIM step reduction, FORA, TaylorSeer, AB2, TeaCache, SpeCa at
three aggressiveness levels. Reported: FLOPs speedup, trajectory deviation,
FID-proxy, conditioning score. Claim under test: SpeCa holds quality at
accelerations where unverified caching degrades (paper: FID 2.72 @5× vs
FORA 9.24, ToCa 12.86; catastrophic at 6.8×+).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C

METHODS = [
    "full",
    "steps_0.5", "steps_0.2", "steps_0.14",
    "fora_4", "fora_7",
    "taylorseer_4_2", "taylorseer_7_2",
    "ab2_5",
    "teacache_1.8", "teacache_3.5",
    "speca_0.1", "speca_0.3", "speca_0.6",
]


def run(batch: int = 16, methods=None, seed: int = 7):
    cfg, dcfg, params = C.get_model("dit")
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
    C.print_table("table3_dit (class-conditional, DDIM-50 base)", rows)
    C.write_result("table3_dit", rows)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()

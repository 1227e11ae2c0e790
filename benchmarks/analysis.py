"""Fig. 2 (quality vs acceleration), Fig. 6 (layer correlation), and the
Appendix-C trajectory analysis."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common as C
from repro.configs import SpeCaConfig
from repro.core import taylor
from repro.core.speca import speca_sample
from repro.core.verify import relative_error
from repro.diffusion.pipeline import (latent_shape, make_stepper,
                                      model_inputs, sample_full)
from repro.layers import model as M


def fig2_quality_curve(batch=16):
    """Quality (FID-proxy) vs acceleration for SpeCa and baselines."""
    cfg, dcfg, params = C.get_model("dit")
    cond = C.make_cond(cfg, dcfg, batch)
    key = jax.random.PRNGKey(2)
    ref = C.reference_latents(cfg, dcfg, 64)
    tpl = C.class_templates(cfg, dcfg)
    x_full = C.run_method("full", cfg, dcfg, params, cond, batch,
                          key).samples
    rows = []
    sweeps = {
        "speca": ["speca_0.05", "speca_0.1", "speca_0.3", "speca_0.6",
                  "speca_1.0"],
        "taylorseer": ["taylorseer_2_2", "taylorseer_4_2", "taylorseer_7_2",
                       "taylorseer_10_2"],
        "fora": ["fora_2", "fora_4", "fora_7", "fora_10"],
        "steps": ["steps_0.5", "steps_0.25", "steps_0.14", "steps_0.1"],
    }
    for family, methods in sweeps.items():
        for name in methods:
            res = C.run_method(name, cfg, dcfg, params, cond, batch, key)
            row = C.evaluate(res, x_full, cfg, dcfg, cond, tpl, ref)
            row["family"] = family
            rows.append(row)
    C.print_table("fig2_quality_vs_acceleration", rows)
    C.write_result("fig2_quality_curve", rows)
    return rows


def fig6_layer_correlation(batch=8, interval=4):
    """Correlation between per-layer draft errors and final-output error.

    Replicates the paper's Fig. 6 analysis: deeper layers' activation
    errors correlate best with the final output error, justifying deep
    verification (r=0.842 at layer 27 in the paper)."""
    cfg, dcfg, params = C.get_model("dit")
    cond = C.make_cond(cfg, dcfg, batch)
    key = jax.random.PRNGKey(4)
    stepper = make_stepper(dcfg)
    L = cfg.num_layers
    n_tok = (dcfg.latent_size // cfg.patch_size) ** 2

    x = jax.random.normal(key, latent_shape(cfg, dcfg, batch), jnp.float32)
    feat_shape = taylor.feature_shape_for(L, batch, n_tok, cfg.d_model)
    tstate = taylor.init_state(2, feat_shape, cfg.jnp_dtype)

    fwd = jax.jit(lambda x, t: M.dit_forward(
        cfg, params, model_inputs(cfg, x, t, cond), collect_branches=True))

    layer_errs = []   # per predicted step: [L, B]
    out_errs = []     # per predicted step: [B]
    for s in range(stepper.num_steps):
        out, ex = fwd(x, stepper.t_model[s])
        warm = int(tstate["n_anchors"]) > 2
        if warm and s % interval != 0:
            preds = taylor.predict(tstate, s)
            # per-layer relative error between predicted and real branches
            errs = []
            for l in range(L):
                pred_l = preds[l][0] + preds[l][1]
                real_l = ex["branches"][l][0] + ex["branches"][l][1]
                errs.append(np.asarray(relative_error(pred_l, real_l)))
            layer_errs.append(np.stack(errs))
            # final-output error: model output from drafted features
            out_spec, _ = M.dit_forward(
                cfg, params, model_inputs(cfg, x, stepper.t_model[s], cond),
                branch_preds=preds,
                compute_mask=jnp.zeros((L,), bool))
            out_errs.append(np.asarray(relative_error(out_spec, out)))
        else:
            tstate = taylor.update(tstate, ex["branches"], s)
        x = stepper.advance(x, out, s)

    layer_errs = np.concatenate(layer_errs, axis=1)  # [L, N]
    out_errs = np.concatenate(out_errs)              # [N]
    rows = []
    for l in range(L):
        r = float(np.corrcoef(layer_errs[l], out_errs)[0, 1])
        rows.append({"layer": l, "pearson_r": round(r, 4)})
    C.print_table("fig6_layer_error_correlation", rows)
    C.write_result("fig6_layer_correlation", rows)
    return rows


def trajectory_analysis(batch=4):
    """Appendix C: PCA trajectories — SpeCa should hug the full-compute
    path while unverified caching drifts."""
    cfg, dcfg, params = C.get_model("dit")
    cond = C.make_cond(cfg, dcfg, batch)
    key = jax.random.PRNGKey(6)

    x_full, traj_full = jax.jit(lambda k: sample_full(
        cfg, params, dcfg, k, cond, batch, collect_trajectory=True))(key)
    from repro.core.baselines import cached_sample, fora, taylorseer
    scfg = SpeCaConfig(taylor_order=2, max_draft=8, tau0=0.4, beta=0.9)
    _, st_sp = jax.jit(lambda k: speca_sample(
        cfg, params, dcfg, scfg, k, cond, batch,
        collect_trajectory=True))(key)
    _, st_fo = jax.jit(lambda k: cached_sample(
        cfg, params, dcfg, fora(5), k, cond, batch,
        collect_trajectory=True))(key)
    _, st_ts = jax.jit(lambda k: cached_sample(
        cfg, params, dcfg, taylorseer(5), k, cond, batch,
        collect_trajectory=True))(key)

    ref = np.asarray(traj_full).reshape(dcfg.num_inference_steps, -1)
    rows = []
    for name, st in [("speca", st_sp), ("taylorseer_5", st_ts),
                     ("fora_5", st_fo)]:
        t = np.asarray(st["trajectory"]).reshape(len(ref), -1)
        per_step = np.linalg.norm(t - ref, axis=1) \
            / (np.linalg.norm(ref, axis=1) + 1e-9)
        rows.append({
            "method": name,
            "mean_traj_dev": round(float(per_step.mean()), 5),
            "final_dev": round(float(per_step[-1]), 5),
            "max_dev": round(float(per_step.max()), 5),
        })
    C.print_table("trajectory_analysis (Appendix C)", rows)
    C.write_result("trajectory_analysis", rows)
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    fig2_quality_curve()
    fig6_layer_correlation()
    trajectory_analysis()

"""Diffusion configurations: class-conditional DiT lanes served through
``SpeCaEngine`` with the ``DiffusionWorkload``."""
from __future__ import annotations

import numpy as np

from bench.families import common
from bench.harness import flops as F
from bench.reference import dit as ref
from bench.reference.numerics import CONTROL, F32

RATE = "samples_per_s"
SUFFIX = "dit"
TAG = "diffusion"
# substrings of the trace names of the kernels the readers time
KERNELS = {"predict": "taylor_predict_lanes", "update": "taylor_update_lanes"}


class System:
    def __init__(self, conf: dict, seed: int, chips: int) -> None:
        import jax
        from repro.configs import DiffusionConfig
        from repro.serving import SpeCaEngine
        self.conf, self.seed, self.chips = conf, seed, chips
        self.sizes = dict(conf["sizes"])
        self.diff = dict(conf["diffusion"])
        self.speca = dict(conf["speca"])
        self.cfg = common.model_config(conf)
        self.dcfg = DiffusionConfig(latent_size=self.sizes["latent_size"],
                                    **self.diff)
        self.weights = ref.make_weights(self.sizes,
                                        common.weight_seed(seed))
        jax.block_until_ready(self.weights)
        self.lanes = conf["engine"]["lanes_per_chip"] * chips
        self.engine = SpeCaEngine(
            self.cfg, self.weights, self.dcfg, common.speca_config(conf),
            lanes=self.lanes, mesh=common.lane_mesh(conf, chips),
            max_draft_depth=conf["engine"].get("max_draft_depth", 1))
        self.engine.start(lanes=self.lanes, workload=TAG)
        self.steps = self.dcfg.num_inference_steps
        self.full_flops = F.dit_full_flops(self.sizes)
        self.draft_flops = F.dit_draft_flops(self.sizes)

    # --- traffic -----------------------------------------------------
    def request(self, spec):
        from repro.serving import Request, RequestPolicy
        label = spec.seed % self.sizes["num_classes"]
        return Request(request_id=spec.rid, seed=spec.seed,
                       cond={"labels": np.asarray([label], np.int32)},
                       policy=RequestPolicy(tau0=self.speca["tau0"]))

    def done(self, spec, res, latency_s: float) -> common.Done:
        return common.Done(
            spec=spec, sample=np.asarray(res.sample[0], np.float32),
            num_full=res.num_full, num_spec=res.num_spec,
            num_drafted=res.num_drafted, latency_s=latency_s, units=1,
            flops=res.num_full * self.full_flops
            + res.num_drafted * self.draft_flops)

    # --- live operands ------------------------------------------------
    def table(self):
        """The engine's difference-table leaf on one chip."""
        diffs = self.engine._sessions[TAG].state["diffs"]
        return diffs.addressable_shards[0].data

    def predict_cost(self):
        t = self.table()
        return F.taylor_predict_cost(t.shape, t.dtype.itemsize,
                                     t.dtype.itemsize, positions=1)

    # --- correctness --------------------------------------------------
    def check(self, done, limits: dict, *, control: bool = False):
        """Compare a seeded sample of the finished requests with the
        plain reference; returns {name: (value, limit, "max"|"min")}. With
        ``control`` the reference at the control's precision stands in
        for the program's samples."""
        import jax
        import jax.numpy as jnp
        n = self.conf["check"]["sample_requests"]
        picked = common.sample_requests(done, n, self.seed)
        out = common.base_checks(done, limits, self.speca,
                                 lambda d: self.steps)
        if not picked:
            return out
        rows = picked + [picked[-1]] * (n - len(picked))
        noise = jnp.concatenate([
            jax.random.normal(jax.random.PRNGKey(d.spec.seed),
                              (1,) + rows[0].sample.shape, jnp.float32)
            for d in rows])
        labels = np.asarray([d.spec.seed % self.sizes["num_classes"]
                             for d in rows], np.int32)
        W = ref.make_weights(self.sizes, common.weight_seed(self.seed))
        want = np.asarray(ref.sample(W, noise, labels, self.sizes,
                                     self.diff, self.speca, F32))
        if control:
            got = np.asarray(ref.sample(W, noise, labels, self.sizes,
                                        self.diff, self.speca, CONTROL))
        else:
            got = np.stack([d.sample for d in rows])
        errs = [common.rel_l2(got[i], want[i]) for i in range(len(picked))]
        for d, e in zip(picked, errs):
            d.check = {"latent_rel_l2": e}
        out["latent_rel_l2"] = (max(errs), limits["latent_rel_l2"], "max")
        return out

    def free(self):
        self.engine = None
        self.weights = None

"""Decode configurations: self-speculative LM decode lanes served
through ``SpeCaEngine`` with the ``DecodeWorkload``."""
from __future__ import annotations

import numpy as np

from bench.families import common
from bench.harness import flops as F
from bench.reference import mamba2 as ref
from bench.reference.numerics import CONTROL, F32

RATE = "tokens_per_s"
SUFFIX = "decode"
TAG = "decode"
# substrings of the trace names of the kernels the readers time
KERNELS = {"predict": "taylor_predict_chain", "update": "taylor_update_lanes"}


def prompt_tokens(seed: int, length: int, vocab: int) -> np.ndarray:
    return np.random.default_rng([seed, 3]).integers(
        0, vocab, (length,)).astype(np.int32)


class System:
    def __init__(self, conf: dict, seed: int, chips: int) -> None:
        import jax
        from repro.serving import DecodeWorkload, SpeCaEngine
        self.conf, self.seed, self.chips = conf, seed, chips
        self.sizes = dict(conf["sizes"])
        self.speca = dict(conf["speca"])
        self.dec = dict(conf["decode"])
        self.cfg = common.model_config(conf)
        self.weights = ref.make_weights(self.sizes,
                                        common.weight_seed(seed))
        jax.block_until_ready(self.weights)
        self.lanes = conf["engine"]["lanes_per_chip"] * chips
        self.depth = conf["engine"]["max_draft_depth"]
        wl = DecodeWorkload(self.cfg, self.weights,
                            common.speca_config(conf),
                            max_new_tokens=self.dec["max_new_tokens"],
                            max_seq_len=self.dec["max_seq_len"])
        self.engine = SpeCaEngine(
            workloads={TAG: wl}, lanes=self.lanes,
            mesh=common.lane_mesh(conf, chips), max_draft_depth=self.depth)
        self.engine.start(lanes=self.lanes, workload=TAG)
        self.full_flops = F.decode_full_flops(self.sizes)
        self.draft_flops = F.decode_draft_flops(self.sizes)

    # --- traffic -----------------------------------------------------
    def prompt(self, spec):
        return prompt_tokens(spec.seed, spec.prompt_len,
                             self.sizes["vocab_size"])

    def request(self, spec):
        from repro.serving import Request, RequestPolicy
        return Request(request_id=spec.rid,
                       cond={"tokens": self.prompt(spec)[None]},
                       policy=RequestPolicy(workload=TAG,
                                            draft_depth=self.depth,
                                            tau0=self.speca["tau0"],
                                            max_steps=spec.steps))

    def done(self, spec, res, latency_s: float) -> common.Done:
        toks = np.asarray(res.sample, np.int32)
        return common.Done(
            spec=spec, sample=toks, num_full=res.num_full,
            num_spec=res.num_spec, num_drafted=res.num_drafted,
            latency_s=latency_s, units=int(toks.shape[0]),
            flops=res.num_full * self.full_flops
            + res.num_drafted * self.draft_flops)

    # --- live operands ------------------------------------------------
    def table(self):
        diffs = self.engine._sessions[TAG].state["diffs"]
        return diffs.addressable_shards[0].data

    def predict_cost(self):
        t = self.table()
        return F.taylor_predict_cost(t.shape, t.dtype.itemsize,
                                     t.dtype.itemsize,
                                     positions=self.depth)

    # --- correctness --------------------------------------------------
    def check(self, done, limits: dict, *, control: bool = False):
        """Teacher-force the plain reference along a seeded sample of the
        finished requests (the longest among them) and compare the
        widest gap by which a served token's logit lies below the
        reference's best. With ``control`` the gap is that of the token
        the control's precision puts first at each position."""
        n = self.conf["check"]["sample_requests"]
        out = common.base_checks(done, limits, self.speca,
                                 lambda d: d.spec.steps)
        picked = common.sample_requests(done, n, self.seed,
                                        longest_first=True)
        if not picked:
            return out
        W = ref.make_weights(self.sizes, common.weight_seed(self.seed))
        prompts = [self.prompt(d.spec) for d in picked]
        tok0 = ref.first_tokens(W, prompts, [d.sample[0] for d in picked],
                                self.sizes)
        rows = [(p, t, d.sample) for p, t, d in zip(prompts, tok0, picked)]
        nxs = (F32, CONTROL) if control else (F32,)
        served, other = ref.teacher_forced(W, rows, self.sizes, self.speca,
                                           nxs=nxs, pad_to=n)
        worst = np.nanmax(other[0] if control else served, axis=1)
        for d, g in zip(picked, worst):
            d.check = {"token_gap": float(g)}
        out["token_gap"] = (float(worst.max()), limits["token_gap"], "max")
        out["tokens_compared"] = (int(sum(d.units for d in picked)), 1,
                                  "min")
        return out

    def free(self):
        self.engine = None
        self.weights = None

"""Tiny stand-ins for the benchmark's configurations, for runs on the
CPU (Pallas in interpret mode). Same families, traffic mixes, metric
files and checks as the cells; only the sizes shrink."""
from __future__ import annotations

import copy
import json
from pathlib import Path

from bench.harness.cell import load_spec

ROOT = Path(__file__).resolve().parents[2]

TINY_SIZES = {
    "dit_xl2": {"num_layers": 2, "d_model": 64, "num_heads": 4,
                "num_kv_heads": 4, "d_ff": 128, "num_classes": 8,
                "latent_size": 8},
    "mamba2_130m": {"num_layers": 2, "d_model": 64, "vocab_size": 512,
                    "ssm_state": 16, "ssm_head_dim": 16, "ssm_chunk": 8},
}
PEAK = {"bf16_flop_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# A fault that spoils half of the lanes escapes a check of n sampled
# requests only when all n ran on the other half (about 2**-n), so runs
# with a planted fault check more requests.
SAMPLE, FAULT_SAMPLE = 3, 12


def tiny_spec(lanes: int = 4, steps: int = 12,
              sample: int = SAMPLE) -> dict:
    """The benchmark's spec with every configuration shrunk."""
    spec = copy.deepcopy(load_spec())
    for name, conf in spec["configs"].items():
        base = name if name in TINY_SIZES else "dit_xl2"
        conf["sizes"].update(TINY_SIZES[base])
        conf["engine"]["lanes_per_chip"] = lanes
        if "diffusion" in conf:
            conf["diffusion"]["num_inference_steps"] = steps
        if "decode" in conf:
            conf["decode"] = {"max_new_tokens": 24, "max_seq_len": 48}
        conf["check"]["sample_requests"] = sample
    for t in spec["traffic"].values():
        if "steps" in t:
            t["steps"].update(min=4, max=24)
            t["steps"]["lognormal"]["median"] = 10
        if "prompt_lens" in t:
            t["prompt_lens"] = [8, 16]
        if t.get("start_span"):
            t["start_span"] = steps
    spec["peak"] = PEAK
    return spec


def run_tiny(workload: str, *, seed: int = 2 ** 31 + 12345,
             seconds: float = 1.5, trace: int = 0, patch=None,
             spec=None) -> dict:
    """One run of a cell at tiny size on the CPU, through the same
    harness as ``bench/run.py`` with the chip check skipped."""
    import io
    from bench.harness import cell
    out = io.StringIO()
    res = cell.run(["--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(trace)],
                   spec=spec or tiny_spec(
                       sample=FAULT_SAMPLE if patch else SAMPLE),
                   require=False, patch=patch,
                   out=out)
    last = out.getvalue().strip().splitlines()[-1]
    assert json.loads(last) == json.loads(json.dumps(res))
    return res


# --- faults planted under the timed path -------------------------------

def state_unchanged(system):
    """The lane step returns the state it was given (its flags are the
    real ones, so only the answers can show it)."""
    sess = system.engine._sessions[_tag(system)]
    real = sess.step_fn

    def step(state):
        _, flags = real(state)
        return state, flags
    sess.step_fn = step


def half_batch(system):
    """The lane step computes only the first half of the lanes; the
    other half keep their payload."""
    sess = system.engine._sessions[_tag(system)]
    real = sess.step_fn
    keys = ("x",) if _tag(system) == "diffusion" else ("tok", "tokens")

    def step(state):
        new, flags = real(state)
        new = dict(new)
        h = sess.W // 2
        for k in keys:
            new[k] = new[k].at[h:].set(state[k][h:])
        return new, flags
    sess.step_fn = step


def answer_altered(system):
    """Each answer is altered where the workload produces it: a latent
    scaled by 1.1, a decode answer's first token moved by one."""
    wl = system.engine.workloads[_tag(system)]
    real = wl.emit

    def emit(state, lane, done):
        out = real(state, lane, done)
        if _tag(system) == "diffusion":
            return out * 1.1
        out = out.copy()
        out[0] = (out[0] + 1) % system.sizes["vocab_size"]
        return out
    wl.emit = emit


def _tag(system):
    from importlib import import_module
    fam = system.conf["family"]
    return import_module(f"bench.families.{fam}").TAG


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "answer_altered": answer_altered}

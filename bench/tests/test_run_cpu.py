"""The harness end to end at tiny size on the CPU, and its refusal to
report anything off a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench.tests.tiny import run_tiny

ROOT = Path(__file__).resolve().parents[2]


def test_refuses_to_run_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "dit_xl2.wave", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr


@pytest.mark.parametrize("workload", ["dit_xl2.wave", "mamba2_130m.chat"])
def test_rehearse_cell(workload):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    res = run_tiny(workload)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_rehearse_traced_stagger():
    res = run_tiny("dit_xl2.stagger", trace=1)
    assert res["correct"] is True, res["checks"]
    # host-side readers report; device readers find no TPU and stay out
    assert {"lane_occupancy.dit", "tick_host_ms.dit",
            "mfu.dit"} <= set(res["metrics"])
    assert not any(k.endswith(".decode") for k in res["metrics"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["device"]["window_s"] > 0

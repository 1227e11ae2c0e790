"""The four-chip lane-mesh cell end to end at tiny size on four forced
CPU devices (in a child process: the device count is fixed when JAX
starts), sound and with the lanes of the last two devices left out."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]

CHILD = """
import json, sys
from bench.tests.tiny import FAULTS, run_tiny
fault = sys.argv[1]
res = run_tiny("dit_xl2.stagger.mesh4", seconds=1.5,
               patch=FAULTS[fault] if fault != "none" else None)
print("RESULT " + json.dumps(res))
"""


@pytest.mark.parametrize("fault", ["none", "half_batch"])
def test_rehearse_lane_mesh_cell(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run([sys.executable, "-c", CHILD, fault], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    res = json.loads(line[-1][len("RESULT "):])
    assert res["device"]["count"] == 4
    assert res["correct"] is (fault == "none"), res["checks"]
    assert res["metrics"]["samples_per_s"]["value"] > 0

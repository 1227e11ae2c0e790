"""``chip_smoke.py``'s code path on the CPU at a tiny size.

The script itself refuses to run off a TPU; these tests drive its phase
functions directly with reduced configurations (Pallas in interpret
mode), so a wrong path, argument or check is found here and not on the
chip. The Mosaic check is the one part that needs the chip's compiler —
``tests/test_tpu_compile.py`` covers it — and is stubbed out here.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _tiny_dit():
    from repro.configs import DiffusionConfig, get_config, reduced
    cfg = dataclasses.replace(reduced(get_config("dit-xl2")), num_layers=2,
                              d_model=64, d_ff=128, num_heads=4,
                              num_kv_heads=4, num_classes=8)
    return cfg, DiffusionConfig(num_inference_steps=10, latent_size=8)


@pytest.fixture
def no_mosaic_check(monkeypatch):
    monkeypatch.setattr(chip_smoke, "assert_mosaic", lambda *a: None)


def test_refuses_to_run_off_a_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code != 0
    assert "'cpu'" in str(e.value.code)
    assert capsys.readouterr().out == ""       # no result line


def test_dit_params_refill_zero_leaves():
    import numpy as np
    cfg, _ = _tiny_dit()
    params = chip_smoke.dit_params(cfg, seed=3)
    for leaf in (params["blocks"]["mod_w"], params["blocks"]["mod_b"],
                 params["head"]["w"], params["head"]["mod_w"]):
        assert np.abs(np.asarray(leaf, np.float32)).max() > 0


def test_phase_diffusion_tiny(no_mosaic_check, capsys):
    cfg, dcfg = _tiny_dit()
    chip_smoke.phase_diffusion(cfg, dcfg, chip_smoke.dit_params(cfg, 0),
                               seed=0)
    out = capsys.readouterr().out
    assert "phase A (diffusion): passed" in out
    assert "compile cold" in out


def test_phase_decode_tiny(no_mosaic_check, capsys):
    import jax
    from repro.configs import get_config, reduced
    from repro.layers import model as M
    cfg = reduced(get_config("mamba2-130m"))
    chip_smoke.phase_decode(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                            seed=0, prompt_len=8, new_tokens=6)
    assert "phase B (decode): passed" in capsys.readouterr().out


def test_first_divergence_tie_rule():
    import numpy as np
    rows = [np.asarray([0.0, 2.0, 2.01, -1.0], np.float32)] * 3
    assert chip_smoke.first_divergence([2, 2, 2], [2, 2, 2], rows) is None
    j, gap, tie = chip_smoke.first_divergence([2, 1, 0], [2, 2, 2], rows)
    assert (j, tie) == (1, True) and gap == pytest.approx(0.01, abs=1e-6)
    # a token outside the near-tied top two is a real mismatch
    assert chip_smoke.first_divergence([0], [2], rows)[2] is False


def test_phase_mesh_tiny_on_four_host_devices():
    """The ``--chips 4`` comparison on four forced host devices (a fresh
    process: the device count is fixed at JAX start-up)."""
    code = textwrap.dedent(f"""
        import os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {str(ROOT)!r})
        sys.path.insert(0, {str(Path(__file__).parent)!r})
        import chip_smoke
        from test_chip_smoke import _tiny_dit
        chip_smoke.assert_mosaic = lambda *a: None
        cfg, dcfg = _tiny_dit()
        chip_smoke.phase_mesh(cfg, dcfg, chip_smoke.dit_params(cfg, 0),
                              seed=0, devices=4)
    """)
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")])}
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "mesh phase (4 devices vs 1): passed" in proc.stdout

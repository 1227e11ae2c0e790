"""Roofline, MFU and peak arithmetic against hand-worked values."""
import math
from types import SimpleNamespace

import pytest

from bench.harness import flops as F
from bench.harness import readers as R
from bench.harness.cell import peaks_for

DIT = {"num_layers": 28, "d_model": 1152, "num_heads": 16, "d_ff": 4608,
       "patch_size": 2, "in_channels": 4, "latent_size": 32}
M2 = {"num_layers": 24, "d_model": 768, "vocab_size": 50280,
      "ssm_state": 128, "ssm_head_dim": 64, "ssm_expand": 2}


def test_predict_kernel_cost_at_phase_a_widths():
    # PR 11 phase A: the DiT-XL/2 table [3, 28, 2, 4 lanes, 256, 1152]
    # in bf16; one call reads 3 planes and writes one forecast plane
    plane = 28 * 2 * 4 * 256 * 1152                  # 66,060,288
    flops, nbytes = F.taylor_predict_cost((3, 28, 2, 4, 256, 1152), 2, 2)
    assert nbytes == 4 * plane * 2 == 528_482_304
    assert flops == 5 * plane == 330_301_440
    # the decode chain kernel writes K forecasts from one table read
    flops, nbytes = F.taylor_predict_cost((3, 24, 2, 32, 1, 768), 2, 2,
                                          positions=3)
    plane = 24 * 2 * 32 * 768
    assert nbytes == (3 + 3) * plane * 2 and flops == 3 * 5 * plane


def test_dit_flops_hand_worked():
    t = 256
    block = 2 * t * 1152 * 72 * 64 + 2 * t * t * 16 * 72 * 2 \
        + 2 * t * 1152 * 4608 * 2
    glue = 2 * t * 1152 + 2 * t * 16 * 1152 * 2 + 2 * 28 * 1152 * 6 * 1152
    assert F.dit_full_flops(DIT) == 28 * block + glue
    assert F.dit_draft_flops(DIT) == block + glue + 4 * 28 * 2 * t * 1152
    assert 236e9 < F.dit_full_flops(DIT) < 239e9


def test_mamba2_flops_count_one_group():
    d, di, ns, nh = 768, 1536, 128, 24
    adv = 2 * d * (2 * di + 2 * ns + nh) + 4 * di * ns
    mixer = adv + 2 * di * ns + 2 * di * d
    glue = 2 * d + 2 * d * 50280
    assert F.decode_full_flops(M2) == 24 * mixer + glue
    assert F.decode_draft_flops(M2) == mixer + 23 * adv + glue \
        + 4 * 24 * 2 * d


def test_peaks_lookup():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        peaks_for("TPU v9")


def _ctx(**kw):
    base = dict(suffix="dit", window=None, reduced=None, system=None,
                peak={"bf16_flop_per_s": 1e12, "hbm_bytes_per_s": 1e9},
                chips=2)
    base.update(kw)
    return R.Context(**base)


def test_mfu_formula_on_made_up_counters():
    done = [SimpleNamespace(flops=3e11), SimpleNamespace(flops=1e11)]
    window = SimpleNamespace(done=done, seconds=2.0)
    # 4e11 FLOPs over 2 s over 2 chips x 1e12 = 10 %
    assert math.isclose(R.mfu(_ctx(window=window)), 10.0)
    assert R.mfu(_ctx(window=SimpleNamespace(done=[], seconds=1.0))) is None


def test_roofline_share_memory_bound():
    system = SimpleNamespace(predict_cost=lambda: (1e6, 2e9))
    red = SimpleNamespace(kernel_calls={"predict": 4},
                          kernel_seconds={"predict": 16.0})
    # least time max(1e-6, 2 s) = 2 s per call, measured 4 s per call
    assert math.isclose(R.predict_roofline(_ctx(system=system,
                                                reduced=red)), 50.0)
    red.kernel_calls["predict"] = 0
    assert R.predict_roofline(_ctx(system=system, reduced=red)) is None


def test_family_filter():
    read = R.for_family(R.lane_occupancy, "decode")
    w = SimpleNamespace(occupancy=[0.5, 1.0])
    assert read(_ctx(window=w)) is None
    assert read(_ctx(window=w, suffix="decode")) == 75.0

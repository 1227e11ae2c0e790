"""The trace reduction on a hand-made event list and on a small trace
recorded on a TPU v5e (three ticks of ``dit_xl2.stagger``)."""
from pathlib import Path

import pytest

from bench.harness import trace as TR

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json"
KERNELS = {"predict": "taylor_predict_lanes", "update": "taylor_update_lanes"}

D0, D1, OPS = "/device:TPU:0", "/device:TPU:1", "XLA Ops"
HOST, PY = "/host:CPU", "python3"


def _hand_made():
    ms = 1_000_000
    return [
        [HOST, PY, "bench.tick", 0, 10 * ms],
        [HOST, PY, "bench.tick", 10 * ms, 10 * ms],
        [HOST, PY, "$engine.py:335 _fill", 12 * ms, 3 * ms],
        # device 0: a conditional holding the predict kernel and a fusion
        [D0, OPS, "%cond.1 = (bf16[8]) conditional(...)", 1 * ms, 4 * ms],
        [D0, OPS, "%taylor_predict_lanes.1 = bf16[4] custom-call(...)",
         1 * ms, 2 * ms],
        [D0, OPS, "%fusion.7 = bf16[4] fusion(...)", 3 * ms, 1 * ms],
        [D0, OPS, "%taylor_update_lanes.1 = bf16[4] custom-call(...)",
         16 * ms, 2 * ms],
        # device 1: 6 ms busy inside the window, 1 ms outside it
        [D1, OPS, "%copy.3 = bf16[4] copy(...)", 2 * ms, 6 * ms],
        [D1, OPS, "%copy.4 = bf16[4] copy(...)", 20 * ms, 1 * ms],
    ]


def test_hand_made_numbers():
    r = TR.reduce(_hand_made(), KERNELS)
    assert r.ticks == 2 and r.window_s == pytest.approx(0.020)
    assert r.devices == [D0, D1]
    # device 0: [1, 5) + [16, 18) = 6 ms; device 1: [2, 8) = 6 ms
    assert r.busy_s == pytest.approx(0.006)
    assert r.idle_share == pytest.approx(0.7)
    assert r.kernel_calls == {"predict": 1, "update": 1}
    assert r.kernel_seconds["predict"] == pytest.approx(0.002)
    # the conditional's own time excludes the two ops nested in it
    assert r.op_seconds["cond.1"] == pytest.approx(0.001)
    assert r.op_seconds["taylor_predict_lanes.1"] == pytest.approx(0.002)
    # gaps of device 0: [0,1) [5,16) [18,20); the 11 ms gap is named by
    # the most specific host span covering at least half of it
    assert [round(s, 6) for _, s in r.idle_gaps] == [0.011, 0.002, 0.001]
    assert r.idle_gaps[0][0] == "bench.tick"
    assert TR.op_name("%fusion.7 = bf16[4] fusion(...)") == "fusion.7"


def test_gap_named_by_the_narrowest_covering_span():
    ms = 1_000_000
    ev = _hand_made()
    ev.append([HOST, PY, "$engine.py:451 _fetch", 5 * ms, 9 * ms])
    r = TR.reduce(ev, KERNELS)
    assert r.idle_gaps[0][0] == "$engine.py:451 _fetch"


def test_recorded_chip_trace():
    r = TR.reduce(TR.read(str(DATA)), KERNELS)
    assert r.ticks == 3 and r.devices == [D0]
    assert r.window_s == pytest.approx(0.04272998, abs=1e-9)
    assert r.busy_s == pytest.approx(0.008303548, abs=1e-9)
    # the first tick admits a request; its drafted step calls the
    # predict kernel once and the full branch never runs
    assert r.kernel_calls == {"predict": 1, "update": 0}
    assert r.kernel_seconds["predict"] == pytest.approx(0.002249462,
                                                        abs=1e-9)
    # self times partition the busy time
    assert sum(r.op_seconds.values()) == pytest.approx(r.busy_s, abs=1e-9)
    assert r.idle_gaps[0] == ("$engine.py:371 _fill_lane",
                              pytest.approx(0.00301993, abs=1e-9))

"""The engine's ``speca.*`` spans and the lane step's scopes: recorded by
tiny engines under the profiler on the CPU, and reduced by
``bench/harness/spans.py`` from a hand-made event list and from a small
trace recorded on a TPU v5e (one tick of ``mamba2_130m.chat``: an
admission with its prefill, a flag fetch, and the lane step's draft,
rollback and full phases; scope paths cut after their last ``speca.``
scope)."""
import gzip
import json
import types
from pathlib import Path

import pytest

from bench.harness import spans as SP
from bench.harness import trace as TR
from bench.tests.tiny import run_tiny

DATA = Path(__file__).resolve().parent / "data" / "trace_spans.json.gz"
D0, D1, OPS = "/device:TPU:0", "/device:TPU:1", "XLA Ops"
HOST, PY = "/host:CPU", "python3"
STEP = "jit(speca_lane_step)/cond/branch_1_fun/"
READERS = ("admit_host_ms", "sync_wait_ms_per_tick", "draft_ms_per_tick",
           "full_ms_per_tick")


def _read(events):
    ctx = types.SimpleNamespace(spans=SP.reduce(events))
    return {n: getattr(SP, n)(ctx) for n in READERS}


def _hand_made():
    ms = 1_000_000
    h = lambda name, a, b: [HOST, PY, name, int(a * ms), int((b - a) * ms),
                            ""]
    d = lambda dev, name, a, b, stack: [dev, OPS, f"%{name} = bf16[4] op()",
                                        int(a * ms), int((b - a) * ms),
                                        stack]
    return [
        h("bench.tick", 0, 10), h("bench.tick", 10, 20),
        # tick 1: an admission with its prefill readback, then a
        # dispatch and a flag fetch
        h("speca.tick", 0.1, 9.9), h("speca.admit", 0.2, 3.2),
        h("speca.sync.prefill", 2, 3), h("speca.dispatch", 3.3, 3.5),
        h("speca.sync.flags", 3.6, 5.6),
        # tick 2: a harvest (flags and the answer read back), a release
        # and the next admission
        h("speca.tick", 10.1, 19.9), h("speca.dispatch", 10.2, 10.4),
        h("speca.harvest", 10.5, 14.5), h("speca.sync.flags", 10.6, 12.6),
        h("speca.sync.emit", 13, 14), h("speca.release", 14.6, 15),
        h("speca.admit", 15.1, 16.1),
        # device 0: a drafted tick (a conditional holding the forecast
        # and the verify layer), a full tick and the chain's rollback
        d(D0, "cond.1", 4, 8, "jit(speca_lane_step)/cond"),
        d(D0, "taylor_predict_lanes.1", 4, 5, STEP + "speca.draft/"
          "jit(taylor_predict_lanes)/taylor_predict_lanes/pallas_call"),
        d(D0, "fusion.2", 5, 7, STEP + "speca.draft/speca.verify/mul"),
        d(D0, "fusion.9", 11, 14, STEP + "speca.full/dot_general"),
        d(D0, "taylor_update_lanes.1", 14, 15, STEP + "speca.full/"
          "speca.update/jit(taylor_update_lanes)/pallas_call"),
        d(D0, "fusion.5", 16, 17,
          "jit(speca_lane_step)/speca.rollback/concatenate"),
        d(D0, "copy.3", 17, 18, "jit(speca_lane_step)/copy"),
        # device 1 is not read by the phase split
        d(D1, "fusion.9", 11, 19, STEP + "speca.full/dot_general"),
    ]


def test_phase_of_takes_the_outermost_phase_scope():
    assert SP.phase_of(STEP + "speca.draft/speca.verify/add") \
        == "speca.draft"
    assert SP.phase_of(STEP + "speca.full/speca.update/x") == "speca.full"
    assert SP.phase_of("jit(speca_lane_step)/speca.rollback/gather") \
        == "speca.rollback"
    assert SP.phase_of("jit(speca_lane_step)/speca.drafts/add") is None
    assert SP.phase_of("") is None


def test_hand_made_numbers():
    got = _read(_hand_made())
    # admissions of 3 and 1 ms; 1 + 2 + 2 + 1 ms of syncs over 2 ticks
    assert got["admit_host_ms"] == pytest.approx(2.0)
    assert got["sync_wait_ms_per_tick"] == pytest.approx(3.0)
    # draft: predict 1 + verify 2 + rollback 1; full: forward 3 + update
    # 1; the conditional's own 1 ms and the copy are neither
    assert got["draft_ms_per_tick"] == pytest.approx(2.0)
    assert got["full_ms_per_tick"] == pytest.approx(2.0)
    s = SP.reduce(_hand_made())
    assert s.ticks == 2 and s.window_s == pytest.approx(0.020)
    assert s.scoped_ops == 5
    # device 0 idle: [0,4) [8,11) [15,16) [18,20) = 10 ms, all inside
    # the tick spans, 9.6 ms inside the engine's spans, each share named
    # by the narrowest span open at the time
    assert s.idle_s == pytest.approx(0.010)
    assert s.idle_in_span_s == pytest.approx(0.0096)
    ms = {k: v * 1e3 for k, v in s.idle_by_span.items()}
    assert ms == pytest.approx({
        "bench.tick": 0.4, "speca.tick": 4.4, "speca.admit": 2.9,
        "speca.sync.prefill": 1.0, "speca.dispatch": 0.4,
        "speca.sync.flags": 0.8, "speca.harvest": 0.1}, abs=1e-5)
    # the longest gap is named by the narrowest engine span covering it
    assert s.idle_gaps[0] == ("speca.admit", pytest.approx(0.004))
    # the reduction of the existing readers is unchanged by the spans
    r = TR.reduce([e[:5] for e in _hand_made()],
                  {"predict": "taylor_predict_lanes"})
    assert r.busy_s == pytest.approx(0.009)


def test_readers_silent_without_spans_or_scopes():
    """A trace of a program with no spans and no scopes (the program
    before they existed) gives no reading; nor does a run whose trace
    was not reduced."""
    bare = [e[:5] + [""] for e in _hand_made()
            if not e[2].startswith("speca.")]
    assert _read(bare) == {n: None for n in READERS}
    none = types.SimpleNamespace(spans=None)
    assert all(getattr(SP, n)(none) is None for n in READERS)
    assert all(getattr(SP, n)(object()) is None for n in READERS)


def test_recorded_chip_trace():
    with gzip.open(DATA, "rt") as f:
        events = json.load(f)["events"]
    s = SP.reduce(events)
    got = _read(events)
    assert s.ticks == 1 and s.window_s == pytest.approx(0.102596698)
    assert got == pytest.approx({
        "admit_host_ms": 58.144738, "sync_wait_ms_per_tick": 47.764218,
        "draft_ms_per_tick": 32.857713, "full_ms_per_tick": 3.684878})
    assert s.phase_s["speca.rollback"] == pytest.approx(0.018684, abs=1e-6)
    # the phases lie inside the device's busy time, the sync spans inside
    # the tick
    busy = TR.reduce([e[:5] for e in events], {}).busy_s
    assert got["draft_ms_per_tick"] + got["full_ms_per_tick"] \
        <= 1e3 * busy / s.ticks
    assert got["sync_wait_ms_per_tick"] <= 1e3 * s.window_s / s.ticks
    # most of the device's idle time lies in the admission
    assert s.idle_by_span["speca.admit"] / s.idle_s > 0.8


def _traced_rows(monkeypatch, workload):
    """The rows ``spans.load`` reads from the trace of a tiny traced run
    of ``workload`` through the harness."""
    rows = {}
    real = TR.load

    def load(tdir):
        rows["rows"] = SP.load(tdir)
        return real(tdir)
    monkeypatch.setattr(TR, "load", load)
    res = run_tiny(workload, trace=1, seconds=1.0)
    assert res["correct"] is True, res["checks"]
    return rows["rows"]


def _inside(inner, outer):
    return outer[3] <= inner[3] and inner[3] + inner[4] <= outer[3] + outer[4]


@pytest.mark.parametrize("workload,absent", [
    ("dit_xl2.wave", {"speca.sync.prefill"}),
    ("mamba2_130m.chat", set()),
])
def test_tiny_engine_spans_nest_in_ticks(monkeypatch, workload, absent):
    from repro.obs import SPAN_NAMES
    rows = _traced_rows(monkeypatch, workload)
    host = [e for e in rows if e[2].startswith(("speca.", "bench."))]
    names = {e[2] for e in host}
    # every span the engine opens; diffusion has no prompt prefill
    assert names - {"bench.tick"} == set(SPAN_NAMES) - absent
    bench = [e for e in host if e[2] == "bench.tick"]
    w0 = min(b[3] for b in bench)
    ticks = [e for e in host if e[2] == "speca.tick"]
    # each engine tick of the traced window inside one harness tick (the
    # settling ticks before it run without harness spans); every other
    # span inside an engine tick (the harness submits outside ticks, and
    # the engine admits, dispatches, reads back and releases only inside)
    assert all(any(_inside(t, b) for b in bench) for t in ticks
               if t[3] >= w0)
    for e in host:
        if e[2] not in ("speca.tick", "bench.tick"):
            assert any(_inside(e, t) for t in ticks), e
    got = _read(rows)
    assert got["admit_host_ms"] > 0 and got["sync_wait_ms_per_tick"] > 0
    # the CPU trace has no TPU ops: the scope readers stay silent
    assert got["draft_ms_per_tick"] is None
    assert got["full_ms_per_tick"] is None

"""Observability subsystem pins (ISSUE 10 tentpole).

``repro.obs`` threads telemetry through the serving engine under two
hard promises, both pinned here to the standard of
``tests/test_forecaster_seam.py``:

  * **bitwise inert when disabled** — an ``obs=False`` engine serves
    the IDENTICAL trajectory (samples byte-for-byte, every counter) as
    an ``obs=True`` engine, across diffusion AND decode, depth 1 and
    K=3 chains, controller on and off. Observability never touches
    ``build_workload_step``, so this equality is also the PR-9
    equivalence pin: obs-off == obs-on == the pre-obs engine.
  * **zero extra host syncs when enabled** — observed traffic issues
    exactly the same number of device fetches (``_Session._fetch``)
    as unobserved traffic; the per-tick lane accumulator is one async
    jitted dispatch whose ONLY materialisation happens at flush.

Plus the seams the subsystem introduces: the ``Clock`` protocol (fake
clock → exactly reproducible ``Result.timings``), the flight-recorder
trace spans, the pre-admission queue-depth series (burst peaks the old
poll-boundary sampling missed), and unit pins for the registry /
exporters / device-side accumulator.
"""
import functools
import io
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SpeCaConfig, get_config, reduced
from repro.core.workload import DecodeWorkload
from repro.layers import model as M
from repro.obs import (Clock, FakeClock, FlightRecorder, MetricsRegistry,
                       MonotonicClock, Observability, build_trace,
                       chrome_trace, prometheus_text, resolve_clock,
                       to_jsonl)
from repro.obs.lane_metrics import LaneAccumulator
from repro.obs.trace import Timings, _tick_span_name
from repro.serving import Request, RequestPolicy, SpeCaEngine
from repro.serving import engine as ENG

import dataclasses

P, G = 8, 8          # decode prompt length / new tokens
STEPS = 6            # diffusion schedule length for engine tests


# ---------------------------------------------------------------------------
# Clock seam
# ---------------------------------------------------------------------------

def test_fake_clock_semantics():
    clk = FakeClock(10.0, auto_tick=0.5)
    assert clk.now() == 10.0          # read returns, THEN advances
    assert clk.now() == 10.5
    clk.advance(2.0)
    assert clk.now() == 13.0
    assert clk.reads == 3
    with pytest.raises(ValueError):
        clk.advance(-1.0)


def test_resolve_clock():
    assert isinstance(resolve_clock(None), MonotonicClock)
    fake = FakeClock()
    assert resolve_clock(fake) is fake
    assert isinstance(fake, Clock)
    m = MonotonicClock()
    assert m.now() <= m.now()
    with pytest.raises(TypeError):
        resolve_clock(object())


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_registry_counter_gauge_semantics():
    reg = MetricsRegistry()
    c = reg.counter("speca_x_total", workload="diffusion")
    c.inc()
    c.inc(2.0)
    assert c.value == 3.0
    with pytest.raises(ValueError):
        c.inc(-1.0)
    # same (name, labels) -> same instrument; different labels -> new one
    assert reg.counter("speca_x_total", workload="diffusion") is c
    assert reg.counter("speca_x_total", workload="decode") is not c
    g = reg.gauge("speca_depth")
    g.set(4.0)
    g.inc(-1.0)
    assert g.value == 3.0
    with pytest.raises(TypeError):
        # same (name, labels) identity, different instrument type
        reg.gauge("speca_x_total", workload="diffusion")


def test_registry_histogram_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("speca_lat", edges=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.6, 3.0, 100.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(106.6)
    assert h.mean == pytest.approx(106.6 / 5)
    # p50 lands in the (1, 2] bucket, interpolated
    assert 1.0 <= h.quantile(0.5) <= 2.0
    # q into the +Inf bucket clamps to the last finite edge
    assert h.quantile(0.99) == 8.0
    with pytest.raises(ValueError):
        reg.histogram("speca_lat", edges=(1.0, 2.0))   # edges mismatch
    with pytest.raises(ValueError):
        reg.histogram("speca_other")                   # edges required
    h2 = reg.histogram("speca_err", edges=(1.0, 2.0))
    h2.add_counts([2.0, 1.0, 1.0], total_sum=10.0, total_count=4.0)
    assert h2.count == 4.0 and h2.sum == 10.0


def test_registry_series_window():
    reg = MetricsRegistry()
    s = reg.series("speca_qd", capacity=4)
    for i in range(6):
        s.append(i, float(i))
    assert len(s) == 4
    assert s.values() == [2.0, 3.0, 4.0, 5.0]
    assert s.points()[0] == (2, 2.0)
    assert s.peak() == 5.0 and s.last() == 5.0
    assert s.dropped == 2


def test_registry_snapshot_shape():
    reg = MetricsRegistry()
    reg.counter("speca_done_total", workload="diffusion").inc(7.0)
    reg.histogram("speca_alpha", edges=(0.5, 1.0)).observe(0.75)
    reg.series("speca_qd").append(1, 3.0)
    snap = reg.snapshot()
    by_name = {(r["name"], tuple(sorted(r["labels"].items()))): r
               for r in snap}
    c = by_name[("speca_done_total", (("workload", "diffusion"),))]
    assert c["kind"] == "counter" and c["value"] == 7.0
    h = by_name[("speca_alpha", ())]
    assert h["kind"] == "histogram" and h["count"] == 1
    assert h["p50"] is not None
    s = by_name[("speca_qd", ())]
    assert s["kind"] == "series" and s["peak"] == 3.0


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("speca_done_total", tenant='we"ird\nname').inc(2.0)
    reg.histogram("speca_lat", edges=(1.0, 2.0)).observe(1.5)
    reg.series("speca_qd").append(1, 3.0)
    text = prometheus_text(reg.snapshot())
    assert "# TYPE speca_done_total counter" in text
    # label values escape quotes and newlines
    assert 'tenant="we\\"ird\\nname"' in text
    # cumulative buckets with a terminal +Inf, plus _sum/_count
    assert 'speca_lat_bucket{le="1.0"} 0' in text
    assert 'speca_lat_bucket{le="2.0"} 1' in text
    assert 'speca_lat_bucket{le="+Inf"} 1' in text
    assert "speca_lat_sum 1.5" in text
    assert "speca_lat_count 1" in text
    # a series surfaces as _last/_peak gauges
    assert "# TYPE speca_qd_last gauge" in text
    assert "speca_qd_peak 3" in text


def test_jsonl_roundtrip():
    rows = [{"kind": "submit", "ticket": 1}, {"kind": "finish", "s": 2.5}]
    buf = io.StringIO()
    text = to_jsonl(rows, buf)
    assert buf.getvalue() == text
    back = [json.loads(line) for line in text.splitlines()]
    assert back == rows


def test_chrome_trace_document():
    t = Timings(submit_s=1.0, admit_s=2.0, finish_s=5.0,
                first_token_s=2.5, submit_tick=0, admit_tick=3,
                finish_tick=6)
    tr = build_trace(ticket_id=9, request_id=4, workload="diffusion",
                     tenant="gold", completed=True, timings=t,
                     per_tick=[{"n_spec": 1, "n_drafted": 1, "full": 0,
                                "advanced": 1},
                               {"n_spec": 0, "n_drafted": 0, "full": 1,
                                "advanced": 1}],
                     tick_times=[None, None, None, 2.5, 3.5, None],
                     deep=False)
    doc = chrome_trace([tr])
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    spans = [e for e in evs if e["ph"] == "X"]
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "workload:diffusion"
               for e in metas)
    assert any(e["name"] == "thread_name" and e["tid"] == 9 for e in metas)
    names = [e["name"] for e in spans]
    assert names == ["queued", "running", "draft+verify", "refresh"]
    q = spans[0]
    assert q["ts"] == pytest.approx(1e6) and q["dur"] == pytest.approx(1e6)
    assert spans[2]["args"]["tick0"] == 3


# ---------------------------------------------------------------------------
# Trace construction + flight recorder
# ---------------------------------------------------------------------------

def test_tick_span_names():
    assert _tick_span_name(0, 0, 0, False) == "stall"
    assert _tick_span_name(0, 0, 1, False) == "refresh"
    assert _tick_span_name(1, 1, 0, False) == "draft+verify"
    assert _tick_span_name(1, 1, 1, False) == "draft+verify+refresh"
    # rollback only for deep lanes that accepted a strict prefix
    assert _tick_span_name(1, 3, 1, True) == "draft+verify+rollback+refresh"
    assert _tick_span_name(1, 3, 1, False) == "draft+verify+refresh"
    assert _tick_span_name(3, 3, 0, True) == "draft+verify"


def test_flight_recorder_bounds():
    rec = FlightRecorder(capacity=3, trace_capacity=2)
    for i in range(5):
        rec.record("submit", float(i), ticket=i)
    evs = rec.events()
    assert [e["ticket"] for e in evs] == [2, 3, 4]
    assert rec.dropped == 2
    assert [e["seq"] for e in evs] == [2, 3, 4]   # seq keeps counting

    def mk(tid):
        t = Timings(submit_s=0.0, admit_s=0.0, finish_s=1.0)
        return build_trace(ticket_id=tid, request_id=tid,
                           workload="diffusion", tenant="default",
                           completed=True, timings=t, per_tick=[],
                           tick_times=[], deep=False)

    for tid in range(3):
        rec.put_trace(mk(tid))
    assert rec.trace(0) is None        # LRU evicted the oldest
    assert rec.trace(2).ticket_id == 2
    assert len(rec.traces()) == 2


# ---------------------------------------------------------------------------
# Device-side lane accumulator
# ---------------------------------------------------------------------------

def test_lane_accumulator_flush():
    acc = LaneAccumulator(err_edges=(1e-3, 1e-1, 10.0))
    nan = float("nan")
    flags = {
        "attempted": jnp.asarray([1, 1, 0, 1], jnp.int32),
        "accepted": jnp.asarray([1, 0, 0, 1], jnp.int32),
        "n_spec": jnp.asarray([1, 0, 0, 1], jnp.int32),
        "n_drafted": jnp.asarray([1, 1, 0, 1], jnp.int32),
        "full": jnp.asarray([0, 1, 0, 0], jnp.int32),
        "advanced": jnp.asarray([1, 1, 0, 1], jnp.int32),
        # NaN = lane did not draft; must be parked outside every bucket
        "chain_err": jnp.asarray([1e-2, 5.0, nan, 2e-4], jnp.float32),
    }
    acc.update(flags)
    acc.update(flags)
    reg = MetricsRegistry()
    acc.flush_into(reg, workload="diffusion")
    lab = {"workload": "diffusion"}
    assert reg.counter("speca_n_spec_total", **lab).value == 4.0
    assert reg.counter("speca_n_drafted_total", **lab).value == 6.0
    assert reg.counter("speca_full_total", **lab).value == 2.0
    assert reg.counter("speca_obs_ticks_total", **lab).value == 2.0
    h = reg.histogram("speca_chain_err", **lab)
    # 3 finite errors x 2 ticks; the NaN lane contributes nothing
    assert h.count == 6.0
    assert h.sum == pytest.approx(2 * (1e-2 + 5.0 + 2e-4))
    assert reg.gauge("speca_draft_accept_rate", **lab).value \
        == pytest.approx(4.0 / 6.0)
    # flush swaps in a fresh accumulator: flushing again adds nothing
    acc.flush_into(reg, workload="diffusion")
    assert reg.counter("speca_obs_ticks_total", **lab).value == 2.0


def test_lane_accumulator_err_key_fallback():
    acc = LaneAccumulator(err_edges=(1.0, 2.0))
    acc.update({"attempted": jnp.ones((2,), jnp.int32),
                "accepted": jnp.ones((2,), jnp.int32),
                "n_spec": jnp.ones((2,), jnp.int32),
                "n_drafted": jnp.ones((2,), jnp.int32),
                "full": jnp.zeros((2,), jnp.int32),
                "advanced": jnp.ones((2,), jnp.int32),
                "err": jnp.asarray([0.5, 1.5], jnp.float32)})
    reg = MetricsRegistry()
    acc.flush_into(reg, workload="x")
    assert reg.histogram("speca_chain_err", workload="x").count == 2.0


# ---------------------------------------------------------------------------
# Engine integration
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _lm():
    cfg = reduced(get_config("llama3-8b"))
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _decode_workloads():
    cfg, params = _lm()
    return cfg, {"decode": DecodeWorkload(cfg, params, SpeCaConfig(tau0=5.0),
                                          max_new_tokens=G,
                                          max_seq_len=P + G)}


def _diffusion_requests(n, K):
    return [Request(request_id=i,
                    cond={"labels": jnp.asarray([i % 8])}, seed=i,
                    policy=RequestPolicy(tau0=0.5, draft_depth=K))
            for i in range(n)]


def _decode_requests(n, K, vocab):
    reqs = []
    for i in range(n):
        prompt = np.asarray(
            jax.random.randint(jax.random.PRNGKey(100 + i), (1, P),
                               0, vocab), np.int32)
        reqs.append(Request(
            request_id=i, cond={"tokens": prompt}, seed=i,
            policy=RequestPolicy(workload="decode", tau0=5.0,
                                 draft_depth=K)))
    return reqs


def _drive(eng, reqs):
    """submit()/tick()/release() to drain; results by request_id."""
    for r in reqs:
        eng.submit(r)
    out = {}
    for _ in range(10_000):
        if not (eng.pending() or eng.in_flight()):
            break
        for res in eng.tick():
            out[res.request_id] = res
            eng.release(res.ticket_id)
    assert len(out) == len(reqs)
    return [out[i] for i in sorted(out)]


def _make_engine(tiny, *, workload="diffusion", K=1, controller=False,
                 obs=False, clock=None, lanes=2):
    cfg, dcfg, params = tiny
    dcfg = dataclasses.replace(dcfg, num_inference_steps=STEPS)
    scfg = SpeCaConfig(taylor_order=2, max_draft=6, tau0=0.5, beta=0.9)
    kw = {}
    if workload == "decode":
        kw["workloads"] = _decode_workloads()[1]
    return SpeCaEngine(cfg, params, dcfg, scfg, lanes=lanes,
                       max_draft_depth=max(K, 1), controller=controller,
                       obs=obs, clock=clock, **kw)


@pytest.mark.parametrize("workload,K,controller", [
    ("diffusion", 1, False),
    ("diffusion", 3, True),
    ("decode", 1, False),
    ("decode", 3, False),
])
def test_obs_disabled_is_bitwise_inert(tiny_trained_dit, workload, K,
                                       controller):
    """The PR-9 equivalence pin: an obs=True engine and an obs=False
    engine serve IDENTICAL trajectories — samples byte-for-byte, every
    counter, every accept trajectory — across the workload × depth ×
    controller matrix. Observability is pure read-out."""
    if workload == "decode":
        vocab = _decode_workloads()[0].vocab_size
        reqs = _decode_requests(3, K, vocab)
    else:
        reqs = _diffusion_requests(4, K)
    res = {}
    for obs in (False, True):
        eng = _make_engine(tiny_trained_dit, workload=workload, K=K,
                           controller=controller, obs=obs)
        res[obs] = _drive(eng, reqs)
        eng.shutdown()
    spec = 0
    for off, on in zip(res[False], res[True]):
        a, b = np.asarray(off.sample), np.asarray(on.sample)
        assert a.dtype == b.dtype and a.shape == b.shape \
            and a.tobytes() == b.tobytes(), \
            f"sample diverged for request {off.request_id}"
        assert (off.num_full, off.num_spec, off.num_drafted) \
            == (on.num_full, on.num_spec, on.num_drafted)
        assert off.accepts == on.accepts
        assert off.completed and on.completed
        spec += on.num_spec
    assert spec > 0                    # non-vacuous: speculation happened
    # timings ride along in BOTH modes (clock reads are host-only)
    assert all(r.timings is not None and r.timings.service_s >= 0.0
               for r in res[False] + res[True])


def test_obs_zero_extra_host_syncs(tiny_trained_dit, monkeypatch):
    """Observed traffic fetches device flags exactly as often as
    unobserved traffic: the accumulator is an async dispatch, and every
    histogram/counter materialisation waits for flush."""
    counts = []
    orig = ENG._Session._fetch

    def run(obs):
        n = [0]

        def counted(self, t):
            n[0] += 1
            return orig(self, t)

        monkeypatch.setattr(ENG._Session, "_fetch", counted)
        eng = _make_engine(tiny_trained_dit, obs=obs)
        _drive(eng, _diffusion_requests(4, 1))
        eng.shutdown()
        monkeypatch.setattr(ENG._Session, "_fetch", orig)
        counts.append(n[0])

    run(False)
    run(True)
    assert counts[0] == counts[1] and counts[0] > 0


def test_fake_clock_timings_deterministic(tiny_trained_dit):
    """With a FakeClock the whole timing surface is exactly
    reproducible: two identical runs produce identical Timings, and the
    lifecycle ordering invariants hold."""
    def run():
        eng = _make_engine(tiny_trained_dit, obs=True,
                           clock=FakeClock(100.0, auto_tick=0.25))
        res = _drive(eng, _diffusion_requests(3, 1))
        eng.shutdown()
        return [r.timings for r in res]

    t1, t2 = run(), run()
    assert t1 == t2
    for t in t1:
        assert t.submit_s <= t.admit_s <= t.finish_s
        # depth-1 lanes fetch no per-tick flags: no first-token stamp
        assert t.first_token_s is None
        assert t.queue_wait_s == pytest.approx(t.admit_s - t.submit_s)
        assert t.service_s == pytest.approx(t.finish_s - t.admit_s)
        assert t.total_s == pytest.approx(t.finish_s - t.submit_s)
        assert t.service_ticks == t.finish_tick - t.admit_tick > 0


@pytest.mark.parametrize("obs", [False, True])
def test_first_token_stamp(tiny_trained_dit, obs):
    """A deep-drafting decode request's first_token_s is the end of the
    first per-tick flag fetch that shows its lane advanced: after
    admission, before finish, and with or without obs. The per-tick
    clock stamps exist only for the obs trace."""
    eng = _make_engine(tiny_trained_dit, workload="decode", K=3, obs=obs,
                       clock=FakeClock(0.0, auto_tick=0.5))
    vocab = _decode_workloads()[0].vocab_size
    res = _drive(eng, _decode_requests(3, 3, vocab))
    for r in res:
        t = r.timings
        assert t.first_token_s is not None
        assert t.admit_s < t.first_token_s < t.finish_s
    assert bool(eng._sessions["decode"]._tick_s) == obs
    eng.shutdown()


@pytest.mark.parametrize("workload,K", [("diffusion", 1), ("diffusion", 3),
                                        ("decode", 1), ("decode", 3)])
def test_lane_step_scopes_in_op_metadata(tiny_trained_dit, workload, K):
    """Every phase scope of the lane step (``step`` at depth 1,
    ``chain_step`` deeper) is in the op_name metadata of the compiled
    program, which is named ``jit_speca_lane_step``."""
    from repro.core import lane_step as LS
    from repro.obs import SCOPE_NAMES
    eng = _make_engine(tiny_trained_dit, workload=workload, K=K)
    eng.start(workload=workload)
    sess = eng._sessions[workload]
    wl = eng.workloads[workload]
    cond = {"labels": jnp.zeros((1,), jnp.int32)} if wl.cond_in_state \
        else {}
    state = LS.init_workload_state(
        wl, sess.W, cond, guidance="mixed" if sess.paired else False)
    step = sess.step_fn
    text = step.func.lower(*step.args, state).compile().as_text()
    assert text.startswith("HloModule jit_speca_lane_step")
    want = [s for s in SCOPE_NAMES if K > 1 or s != "speca.rollback"]
    for scope in want:
        assert f"/{scope}/" in text, f"no op under {scope!r}"
    if K == 1:
        assert "/speca.rollback/" not in text
    eng.shutdown()


def test_engine_trace_spans(tiny_trained_dit):
    """A served request's Trace: queued + running + one span per
    scheduler tick of its service window, named by the phases the tick
    executed, timestamped within the request's service interval."""
    eng = _make_engine(tiny_trained_dit, obs=True,
                       clock=FakeClock(0.0, auto_tick=0.5))
    tickets = [eng.submit(r) for r in _diffusion_requests(2, 1)]
    while eng.pending() or eng.in_flight():
        for res in eng.tick():
            eng.release(res.ticket_id)
    tr = eng.trace(tickets[0])
    assert tr.completed and tr.workload == "diffusion"
    assert [s.name for s in tr.spans[:2]] == ["queued", "running"]
    ticks = tr.tick_spans()
    assert len(ticks) == tr.timings.service_ticks
    allowed = {"stall", "refresh", "draft+verify", "draft+verify+refresh",
               "draft+verify+rollback", "draft+verify+rollback+refresh"}
    assert {s.name for s in ticks} <= allowed
    assert any(s.name != "stall" for s in ticks)
    running = tr.spans[1]
    for s in ticks:
        assert running.t0 <= s.t0 <= s.t1 <= running.t1
        assert s.tick1 == s.tick0 + 1
    # accounting attrs on the spans reconcile with the Result counters
    assert sum(dict(s.attrs).get("full", 0) for s in ticks) > 0
    eng.shutdown()


def test_burst_peak_queue_series(tiny_trained_dit):
    """The queue-depth series samples INSIDE tick() before admission, so
    a burst submitted between ticks lands in the series at its full
    height — the satellite fix for serve_load's old poll-boundary
    sampling, which could only ever see the post-admission queue."""
    eng = _make_engine(tiny_trained_dit, obs=True, lanes=2)
    burst = _diffusion_requests(6, 1)
    for r in burst:
        eng.submit(r)
    assert eng.pending() == 6
    while eng.pending() or eng.in_flight():
        for res in eng.tick():
            eng.release(res.ticket_id)
    qd = eng.obs.metrics.series("speca_queue_depth")
    assert qd.points()[0][1] == 6.0    # pre-admission: the full burst
    assert qd.peak() == 6.0            # post-admission would cap at 4
    fl = eng.obs.metrics.series("speca_in_flight")
    assert fl.peak() == 2.0            # lanes=2: both busy at the peak
    eng.shutdown()


def test_engine_metrics_and_exporters(tiny_trained_dit):
    """End-to-end read-out: lifecycle traffic populates the registry
    (request counters, accept-rate + latency histograms, accumulator
    flush), and every exporter renders it."""
    eng = _make_engine(tiny_trained_dit, obs=True)
    res = _drive(eng, _diffusion_requests(4, 1))
    eng.shutdown()
    snap = eng.metrics_snapshot()
    rows = {r["name"]: r for r in snap}
    done = [r for r in snap if r["name"] == "speca_requests_completed_total"]
    assert sum(r["value"] for r in done) == 4.0
    assert rows["speca_service_steps_total"]["value"] \
        == sum(r.num_full + r.num_spec for r in res)
    assert rows["speca_accept_rate"]["count"] == 4
    assert rows["speca_queue_wait_s"]["count"] == 4
    assert rows["speca_obs_ticks_total"]["value"] > 0
    assert rows["speca_n_spec_total"]["value"] \
        == sum(r.num_spec for r in res)
    assert rows["speca_chain_err"]["count"] > 0
    assert rows["speca_programs_built_total"]["value"] > 0
    text = eng.obs.prometheus()
    assert "# TYPE speca_requests_completed_total counter" in text
    events = eng.obs.recorder.events()
    kinds = [e["kind"] for e in events]
    for k in ("submit", "admit", "finish", "compile"):
        assert k in kinds, k
    lines = eng.obs.events_jsonl().splitlines()
    assert len(lines) == len(events)
    doc = eng.obs.chrome_trace()
    assert len([e for e in doc["traceEvents"] if e["ph"] == "X"]) > 0


def test_obs_disabled_surface_raises(tiny_trained_dit):
    eng = _make_engine(tiny_trained_dit, obs=False)
    assert eng.obs is None
    with pytest.raises(RuntimeError):
        eng.metrics_snapshot()
    tickets = [eng.submit(r) for r in _diffusion_requests(1, 1)]
    with pytest.raises(RuntimeError):
        eng.trace(tickets[0])
    eng.shutdown()


def test_observability_object_injection(tiny_trained_dit):
    """A caller-built Observability (shared registry, custom clock) can
    be handed to the engine directly."""
    obs = Observability(clock=FakeClock(5.0, auto_tick=0.1))
    eng = _make_engine(tiny_trained_dit, obs=obs)
    assert eng.obs is obs and eng.clock is obs.clock
    res = _drive(eng, _diffusion_requests(2, 1))
    eng.shutdown()
    assert all(r.timings.submit_s >= 5.0 for r in res)
    assert obs.metrics.series("speca_queue_depth").points()

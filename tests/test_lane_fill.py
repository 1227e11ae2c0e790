"""Admission and release through one donated, jitted program each.

``_Session._fill`` writes a request's lane (or guided pair) with one
dispatch of ``speca_lane_fill`` and ``_release`` with one of
``speca_lane_release``: the lane index is a traced scalar, the state is
donated, and nothing is read back to the host. These tests pin:

  * admission reads nothing back from the device and runs exactly one
    fill program (a decode admission also runs its prompt prefill);
  * the filled lane equals a row built by hand from the request, leaf by
    leaf — plain, guided-pair, negative-prompt, controller and decode
    lanes — and every other lane is bitwise untouched;
  * a release clears ``active`` (and a pair's ``paired``) and nothing
    else;
  * the in-program noise equals ``jax.random.normal(PRNGKey(seed))``
    bitwise for any integer seed;
  * on a four-device lane mesh the fill writes the same state as on one
    device and keeps every leaf's sharding.
"""
import dataclasses
import glob
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import DiffusionConfig, SpeCaConfig, get_config, reduced
from repro.core import controller as CT
from repro.core.workload import DecodeWorkload
from repro.diffusion.pipeline import latent_shape
from repro.layers import model as M
from repro.serving import Request, RequestPolicy, SpeCaEngine
from repro.serving.engine import _Entry, _Session
from repro.serving.scheduler import QueueItem
from repro.sharding.specs import LANE_STATE_AXES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W, STEPS, P, G = 4, 10, 6, 8


def _dit():
    cfg = dataclasses.replace(reduced(get_config("dit-xl2")), num_layers=2,
                              d_model=64, d_ff=128, num_heads=4,
                              num_kv_heads=4, num_classes=8)
    dcfg = DiffusionConfig(num_inference_steps=STEPS, latent_size=8)
    return cfg, dcfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _dit_engine(controller=False):
    cfg, dcfg, params = _dit()
    return SpeCaEngine(cfg, params, dcfg, SpeCaConfig(taylor_order=2),
                       lanes=W, max_draft_depth=3, controller=controller)


def _decode_engine(arch):
    cfg = reduced(get_config(arch))
    wl = DecodeWorkload(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                        SpeCaConfig(tau0=5.0), max_new_tokens=G,
                        max_seq_len=P + G)
    return SpeCaEngine(workloads={"decode": wl}, lanes=W,
                       max_draft_depth=3)


def _prompt(seed, vocab):
    return np.random.default_rng(seed).integers(0, vocab, (1, P)).astype(
        np.int32)


def _item(req, pol, steps, seq=0):
    return QueueItem(seq=seq, request=req, policy=pol, steps=steps,
                     ticket_id=seq)


def _garbage(state, seed=0):
    """A state of the same layout with every leaf filled at random, so a
    write of zeros or of the initial values shows."""
    rng = np.random.default_rng(seed)

    def fill(x):
        if x.dtype == jnp.bool_:
            v = rng.random(x.shape) < 0.5
        elif jnp.issubdtype(x.dtype, jnp.integer):
            v = rng.integers(-3, 50, x.shape)
        else:
            v = rng.normal(size=x.shape)
        return jnp.asarray(v, x.dtype)
    return jax.tree.map(fill, state)


def _block(key, leaf, lanes):
    """Index of ``lanes`` along ``key``'s lane axis of ``leaf``."""
    ax = 0 if key.startswith("cond/") else LANE_STATE_AXES[key]
    idx = [slice(None)] * leaf.ndim
    idx[ax] = lanes
    return tuple(idx), ax


def _flat(state):
    out = {k: np.asarray(v) for k, v in state.items() if k != "cond"}
    out.update({f"cond/{k}": np.asarray(v)
                for k, v in state.get("cond", {}).items()})
    return out


def _check_lanes(before, after, lanes, want):
    """Lanes ``lanes`` of every leaf named in ``want`` hold ``want``'s
    row; every other lane, and every leaf not in ``want``, is bitwise
    the state before."""
    b, a = _flat(before), _flat(after)
    assert b.keys() == a.keys()
    for key in a:
        assert a[key].dtype == b[key].dtype, key
        if key not in want:
            np.testing.assert_array_equal(a[key], b[key], err_msg=key)
            continue
        idx, ax = _block(key, a[key], list(lanes))
        rest = [l for l in range(a[key].shape[ax]) if l not in lanes]
        ridx, _ = _block(key, a[key], rest)
        np.testing.assert_array_equal(a[key][ridx], b[key][ridx],
                                      err_msg=f"{key}: other lanes")
        row = np.broadcast_to(np.asarray(want[key], a[key].dtype),
                              a[key][idx].shape)
        np.testing.assert_array_equal(a[key][idx], row, err_msg=key)


def _start(eng, tag, paired=None):
    if paired is None:
        eng.start(lanes=W, workload=tag)
        sess = eng._sessions[tag]
    else:
        sess = _Session(eng, W, paired=paired, workload=eng.workloads[tag])
    return sess


# --- admission: no read back, one program --------------------------------

def _programs_inside(trace_dir, span):
    """(fill dispatches, prefill dispatches, program executions) inside
    the host span ``span`` of the trace under ``trace_dir``."""
    pb = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                   recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(pb)
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in pd.planes if plane.name.startswith("/host")
              for line in plane.lines for ev in line.events]
    (a, b), = [(s, e) for n, s, e in events if n == span]

    def count(name):
        # a dispatch nests one event of its name in another: count the
        # outermost
        got = [(s, e) for n, s, e in events if n == name and a <= s <= b]
        return sum(1 for s, e in got
                   if not any(s0 <= s and e <= e0 and (s0, e0) != (s, e)
                              for s0, e0 in got))
    return (count("PjitFunction(speca_lane_fill)"),
            count("PjitFunction(_prefill_impl)"),
            count("PjRtCpuExecutable::Execute"))


def _requests(tag, eng):
    if tag == "decode":
        vocab = eng.workloads["decode"].cfg.vocab_size
        pol = RequestPolicy(workload="decode", draft_depth=3)
        return [(Request(request_id=i, cond={"tokens": _prompt(i, vocab)},
                         policy=pol), pol) for i in range(2)]
    pol = RequestPolicy(tau0=0.5)
    return [(Request(request_id=i, cond={"labels": np.asarray([i + 1],
                                                               np.int32)},
                     seed=100 + i, policy=pol), pol) for i in range(2)]


@pytest.mark.parametrize("tag", ["diffusion", "decode"])
def test_admission_one_program_no_readback(tag):
    """A warm admission dispatches ``speca_lane_fill`` once and runs no
    other program (a decode admission also runs its prompt prefill), and
    reads nothing back from the device."""
    eng = _dit_engine() if tag == "diffusion" else _decode_engine(
        "mamba2-130m")
    sess = _start(eng, tag)
    (r0, p0), (r1, p1) = _requests(tag, eng)
    with jax.transfer_guard_device_to_host("disallow"):
        sess._place(_item(r0, p0, STEPS if tag == "diffusion" else G))
    jax.block_until_ready(sess.state)
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            sess._place(_item(r1, p1, STEPS if tag == "diffusion" else G,
                              seq=1))
        jax.block_until_ready(sess.state)
    finally:
        jax.profiler.stop_trace()
    fills, prefills, runs = _programs_inside(d, "speca.admit")
    want_prefill = 1 if tag == "decode" else 0
    assert (fills, prefills) == (1, want_prefill)
    assert runs == 1 + want_prefill


# --- what the fill writes ------------------------------------------------

FILL_CASES = ["plain", "single_in_pair_slot", "guided", "negative",
              "controller"]


@pytest.mark.parametrize("case", FILL_CASES)
def test_fill_writes_the_lane_row(case):
    """The state after a diffusion fill equals the hand-built row of the
    request in its lane(s), and every other lane is bitwise untouched."""
    eng = _dit_engine(controller=case == "controller")
    cfg, dcfg = eng.cfg, eng.dcfg
    sess = _start(eng, "diffusion", paired=case != "plain")
    neg = {"labels": np.asarray([5], np.int32)}
    pol = {"plain": RequestPolicy(tau0=0.25, draft_depth=2),
           "single_in_pair_slot": RequestPolicy(tau0=0.25, draft_depth=2),
           "guided": RequestPolicy(tau0=0.25, guidance_scale=3.5),
           "negative": RequestPolicy(tau0=0.25, guidance_scale=3.5,
                                     negative_cond=neg),
           "controller": RequestPolicy(
               tau0=0.25, controller=CT.ControllerPolicy(k_max=3))}[case]
    req = Request(request_id=0, cond={"labels": np.asarray([3], np.int32)},
                  seed=2**33 + 17, policy=pol)
    pol = eng.resolve_policy(req)
    # the first admission lays the state out; the tested one goes to
    # lane 2 (lanes 2 and 3 for a pair) of a state filled at random
    sess._fill(_Entry(item=_item(req, pol, 7), lanes=(0,), start_tick=0,
                      t0=0.0))
    sess.state = _garbage(sess.state)
    before = jax.device_get(sess.state)
    lanes = (2, 3) if pol.guided else (3 if case == "single_in_pair_slot"
                                       else 2,)
    entry = _Entry(item=_item(req, pol, 7), lanes=lanes, start_tick=0,
                   t0=0.0, draft_k=int(pol.draft_depth or 1))
    sess._fill(entry)
    after = jax.device_get(sess.state)

    noise = np.asarray(jax.random.normal(
        jax.random.PRNGKey(req.seed), latent_shape(cfg, dcfg, 1),
        jnp.float32))
    want = {"draft_k": entry.draft_k, "max_step": 7, "tau0": 0.25,
            "diffs": 0, "n_anchors": 0, "anchor_step": -1, "gap": 1.0,
            "since": 0, "step": 0, "active": True, "x": noise,
            "cond/labels": 3}
    if case != "plain":
        want["paired"] = pol.guided
    if pol.guided:
        want["gscale"] = 3.5
        uncond = 5 if case == "negative" else cfg.num_classes
        want["cond/labels"] = np.asarray([3, uncond])
    if case == "controller":
        cv = CT.lane_values(pol.controller, tau0=0.25,
                            order=eng.scfg.taylor_order,
                            max_draft_depth=eng.max_draft_depth)
        want.update(cv)
        assert cv["ctl_on"]
    _check_lanes(before, after, lanes, want)


@pytest.mark.parametrize("arch", ["llama3-8b", "mamba2-130m", "hymba-1.5b"])
def test_decode_fill_writes_the_lane_row(arch):
    """A decode fill writes the prompt prefill's cache (K/V zero beyond
    the prompt), its first token, an empty token buffer and the prompt
    length into one lane, and nothing else."""
    eng = _decode_engine(arch)
    wl = eng.workloads["decode"]
    sess = _start(eng, "decode")
    vocab = wl.cfg.vocab_size
    pol = RequestPolicy(workload="decode", draft_depth=3, tau0=0.5)
    req = Request(request_id=0, cond={"tokens": _prompt(0, vocab)},
                  policy=pol)
    sess._fill(_Entry(item=_item(req, pol, G), lanes=(0,), start_tick=0,
                      t0=0.0))
    sess.state = _garbage(sess.state)
    before = jax.device_get(sess.state)
    req = Request(request_id=1, cond={"tokens": _prompt(1, vocab)},
                  policy=pol)
    sess._fill(_Entry(item=_item(req, pol, 5), lanes=(1,), start_tick=0,
                      t0=0.0, draft_k=3))
    after = jax.device_get(sess.state)

    logits, cache = wl._prefill(wl.params, jnp.asarray(_prompt(1, vocab)))
    want = {"draft_k": 3, "max_step": 5, "tau0": 0.5, "diffs": 0,
            "n_anchors": 0, "anchor_step": -1, "gap": 1.0, "since": 0,
            "step": 0, "active": True,
            "tok": int(np.argmax(np.asarray(logits)[0])), "tokens": 0,
            "pos0": P}
    for key, c in cache.items():
        c = np.asarray(c)
        if key in ("k", "v"):
            row = np.zeros(before[key][:, 1:2].shape, c.dtype)
            row[:, :, :P] = c
            c = row
        want[key] = c
    assert set(cache) == set(wl._cache_keys)
    _check_lanes(before, after, (1,), want)


@pytest.mark.parametrize("guided", [False, True])
def test_release_clears_only_its_lanes(guided):
    """A release writes ``active`` off (and ``paired`` off for a guided
    pair) in its own lanes and leaves everything else bitwise as it
    was; the state it was given is donated."""
    eng = _dit_engine()
    sess = _start(eng, "diffusion")
    pol = eng.resolve_policy(Request(
        request_id=0, cond={"labels": np.asarray([1], np.int32)},
        policy=RequestPolicy(guidance_scale=2.0 if guided else None)))
    req = Request(request_id=0, cond={"labels": np.asarray([1], np.int32)},
                  policy=pol)
    lanes = (2, 3) if guided else (1,)
    entry = _Entry(item=_item(req, pol, 7), lanes=lanes, start_tick=0,
                   t0=0.0)
    sess._fill(entry)
    for l in lanes:
        sess.lane_entry[l] = entry
    sess.state = _garbage(sess.state, seed=1)
    # read through copies: a host view of a CPU buffer keeps it from
    # being donated
    before = jax.device_get(jax.tree.map(jnp.copy, sess.state))
    held = sess.state
    sess._release(entry)
    assert held["active"].is_deleted()
    want = {"active": False}
    if guided:
        want["paired"] = False
    _check_lanes(before, jax.device_get(sess.state), lanes, want)
    assert all(sess.lane_entry[l] is None for l in lanes)


@pytest.mark.parametrize("seed", [0, 90_001, 2**31 + 5, 2**32 + 3, -7,
                                  2**40 + 11])
def test_fill_noise_equals_eager_prng(seed):
    """The noise drawn inside the fill program from the seed's low 32
    bits is bitwise ``jax.random.normal(PRNGKey(seed))``, as eager
    serving drew it."""
    eng = _dit_engine()
    wl = eng.workloads["diffusion"]
    req = Request(request_id=0, cond={"labels": np.asarray([0])}, seed=seed)
    rows = jax.jit(wl.fill_rows)({}, wl.fill_inputs(req, STEPS))
    want = jax.random.normal(jax.random.PRNGKey(seed),
                             latent_shape(eng.cfg, eng.dcfg, 1),
                             jnp.float32)
    np.testing.assert_array_equal(np.asarray(rows["x"]), np.asarray(want))


@pytest.mark.slow
def test_mesh_fill_matches_one_device_subprocess():
    """On a four-device lane mesh (forced host devices, subprocess) the
    fill and release write the same state as without a mesh, for a lane
    on every shard and a guided pair, and keep every leaf's sharding."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses, json
        import jax, numpy as np
        from repro.configs import (DiffusionConfig, SpeCaConfig,
                                   get_config, reduced)
        from repro.launch.mesh import make_lane_mesh
        from repro.layers import model as M
        from repro.serving import Request, RequestPolicy, SpeCaEngine
        from repro.serving.engine import _Entry, _Session
        from repro.serving.scheduler import QueueItem

        cfg = dataclasses.replace(reduced(get_config("dit-xl2")),
                                  num_layers=2, d_model=64, d_ff=128,
                                  num_heads=4, num_kv_heads=4,
                                  num_classes=8)
        dcfg = DiffusionConfig(num_inference_steps=10, latent_size=8)
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        W = 16

        def run(mesh):
            eng = SpeCaEngine(cfg, params, dcfg, SpeCaConfig(), mesh=mesh)
            sess = _Session(eng, W, paired=True)
            done = []
            for i, lanes in enumerate([(0,), (5,), (9,), (14, 15), (3,)]):
                pol = eng.resolve_policy(Request(
                    request_id=i, cond={"labels": np.asarray([i])},
                    policy=RequestPolicy(
                        tau0=0.1 * (i + 1),
                        guidance_scale=2.0 if len(lanes) == 2 else None)))
                req = Request(request_id=i,
                              cond={"labels": np.asarray([i])},
                              seed=1000 + i, policy=pol)
                e = _Entry(item=QueueItem(seq=i, request=req, policy=pol,
                                          steps=6, ticket_id=i),
                           lanes=lanes, start_tick=0, t0=0.0)
                sess._fill(e)
                done.append(e)
            sess._release(done[2])
            sess._release(done[3])
            specs = {k: str(getattr(v.sharding, "spec", None))
                     for k, v in sess.state.items() if k != "cond"}
            return jax.device_get(sess.state), specs

        one, _ = run(None)
        four, specs = run(make_lane_mesh(4))
        same = all(np.array_equal(a, b) for a, b in
                   zip(jax.tree.leaves(one), jax.tree.leaves(four)))
        print(json.dumps({"same": same, "specs": specs,
                          "devices": len(jax.devices())}))
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["same"]
    assert "'data'" in res["specs"]["diffs"]
    for key in ("x", "active", "paired", "tau0", "draft_k"):
        assert "'data'" in res["specs"][key], (key, res["specs"][key])

"""Compile the serving path's Pallas kernels for a described TPU v5e.

The interpret-mode tests check what the kernels compute; these check
that the chip's compiler accepts them at the widths the server runs:
block shapes Mosaic's tiling rule admits, SMEM scalars, scoped VMEM.
Nothing runs — each kernel is lowered and compiled for one device of a
described ``v5e:2x2`` topology, and its HLO must hold a
``tpu_custom_call`` (a Mosaic kernel, not the interpreter's jnp) under
the kernel's fixed name, the name a device profile shows. The engine's
lane step, compiled the same way at a tiny size, must keep its program
name, its kernels' names and its phase scopes.

Widths: DiT-XL/2 at 256² (28 layers, 2 branches, 4 lanes, 256 tokens,
d 1152, bf16 tables, Taylor order 2), mamba2-130m decode state
(24 layers, 24 heads × 64 × 128 f32 SSM state, 4 × 1792 bf16 conv state),
and the widest column tile the wrappers choose (scoped-VMEM headroom).

The topology is described inside a module fixture (never at import:
only one process at a time may load the TPU library), and the
persistent compilation cache is off around these compiles, since a
compile for a described device cannot be read back without one.
"""
import dataclasses
import functools
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

M1, LAYERS, LANES, TOKENS, D = 3, 28, 4, 256, 1152
DIT_FEAT = (LAYERS, 2, LANES, TOKENS, D)
DIT_LATENT = (LANES, 32, 32, 4)
DEPTH = 2                           # draft chain length K
SSM_STATE = (24, LANES, 24, 64, 128)
CONV_STATE = (24, LANES, 4, 1792)
# the widest tile the wrappers pick (block_c=8192 of the (16, C/16) fold):
# one layer of a FLUX-like 1024² table (4096 tokens × 3072), f32, order 3
WIDE_M1, WIDE_FEAT = 4, (1, 2, 2, 4096, 3072)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the wrappers to their compiled kernels: this process's
    backend is the CPU, for which they would pick the interpreter."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _compile(fn, sharding, *args, **static):
    """Lower a fresh jit of ``fn`` (the wrapper's undecorated body, so no
    CPU-traced program is reused) on shapes placed on ``sharding``."""
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
              for shape, dtype in args]
    body = getattr(fn, "__wrapped__", fn)
    compiled = jax.jit(functools.partial(body, **static)).lower(
        *shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


BF16, F32, I32, BOOL = jnp.bfloat16, jnp.float32, jnp.int32, jnp.bool_

CASES = {
    "predict_lanes": (ops.taylor_predict_lanes,
                      [((M1,) + DIT_FEAT, BF16), ((M1, LANES), F32)], {}),
    "predict_chain": (ops.taylor_predict_chain_lanes,
                      [((M1,) + DIT_FEAT, BF16),
                       ((M1, DEPTH, LANES), F32)], {}),
    "update_lanes": (ops.taylor_update_lanes,
                     [((M1,) + DIT_FEAT, BF16), (DIT_FEAT, BF16),
                      ((LANES,), BOOL)], {}),
    "spectral_update": (ops.spectral_update_lanes,
                        [((M1,) + DIT_FEAT, BF16), (DIT_FEAT, BF16),
                         ((LANES,), BOOL)], {}),
    "rollback_latent": (ops.lane_rollback,
                        [((DEPTH + 1,) + DIT_LATENT, F32), ((LANES,), I32)],
                        {"lane_axis": 0}),
    "rollback_ssm_state": (ops.lane_rollback,
                           [((DEPTH + 1,) + SSM_STATE, F32),
                            ((LANES,), I32)], {"lane_axis": 1}),
    "rollback_conv_state": (ops.lane_rollback,
                            [((DEPTH + 1,) + CONV_STATE, BF16),
                             ((LANES,), I32)], {"lane_axis": 1}),
    "update_lanes_widest_tile": (ops.taylor_update_lanes,
                                 [((WIDE_M1,) + WIDE_FEAT, F32),
                                  (WIDE_FEAT, F32), ((2,), BOOL)], {}),
    "predict_chain_widest_tile": (ops.taylor_predict_chain_lanes,
                                  [((WIDE_M1,) + WIDE_FEAT, F32),
                                   ((WIDE_M1, 4, 2), F32)], {}),
    "verify_accept": (ops.verify_accept,
                      [((LANES, TOKENS * D), BF16),
                       ((LANES, TOKENS * D), BF16), ((LANES,), F32)], {}),
    "verify_accept_mixed": (ops.verify_accept_mixed,
                            [((LANES, TOKENS * D), BF16),
                             ((LANES, TOKENS * D), BF16), ((LANES,), F32),
                             ((LANES,), F32), ((LANES,), BOOL)], {}),
}


# each case's kernel name in the compiled program (the pallas_call's
# ``name``), which the benchmark's trace readers match by prefix
KERNEL_NAMES = {
    "predict_lanes": "taylor_predict_lanes",
    "predict_chain": "taylor_predict_chain_lanes",
    "update_lanes": "taylor_update_lanes",
    "spectral_update": "spectral_update_lanes",
    "rollback_latent": "lane_rollback",
    "rollback_ssm_state": "lane_rollback",
    "rollback_conv_state": "lane_rollback",
    "update_lanes_widest_tile": "taylor_update_lanes",
    "predict_chain_widest_tile": "taylor_predict_chain_lanes",
    "verify_accept": "verify_accept",
    "verify_accept_mixed": "verify_accept_mixed",
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, mosaic):
    fn, args, static = CASES[name]
    compiled = _compile(fn, one_chip, *args, **static)
    text = compiled.as_text()
    assert f"%{KERNEL_NAMES[name]}." in text, \
        f"no kernel named {KERNEL_NAMES[name]!r} in the program"
    mem = compiled.memory_analysis()
    if mem is not None:
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < 16 * 2**30, used


@pytest.fixture
def fresh_traces():
    """The lane step calls the module-level jitted kernel wrappers, whose
    traces are cached by shape: clear them around a Mosaic compile so it
    neither reuses a CPU-interpreter trace nor leaves a Mosaic one for
    the CPU tests at the same tiny shapes."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("depth", [1, 2])
def test_lane_step_names_for_v5e(depth, one_chip, mosaic, fresh_traces):
    """A tiny DiT engine's lane step (depth 1: ``step``; depth 2:
    ``chain_step``) compiled for the chip: the program is
    ``jit_speca_lane_step``, its kernels keep their names, and every op
    of a phase carries the phase's scope in its ``op_name``."""
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import chip_smoke
    from repro.configs import (DiffusionConfig, SpeCaConfig, get_config,
                               reduced)
    from repro.core import lane_step as LS
    from repro.serving import SpeCaEngine
    cfg = dataclasses.replace(reduced(get_config("dit-xl2")), num_layers=2,
                              d_model=64, d_ff=128, num_heads=4,
                              num_kv_heads=4, num_classes=8)
    dcfg = DiffusionConfig(num_inference_steps=10, latent_size=8)
    eng = SpeCaEngine(cfg, chip_smoke.dit_params(cfg, 0), dcfg,
                      SpeCaConfig(), verify_backend="fused", lanes=4,
                      max_draft_depth=depth)
    eng.start()
    sess = eng._sessions["diffusion"]
    state = LS.init_workload_state(
        eng.workloads["diffusion"], sess.W,
        {"labels": jnp.zeros((1,), jnp.int32)}, guidance="mixed")
    step = sess.step_fn
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        (*step.args, state))
    text = step.func.lower(*shapes).compile().as_text()
    assert text.startswith("HloModule jit_speca_lane_step")
    predict = "taylor_predict_lanes" if depth == 1 \
        else "taylor_predict_chain_lanes"
    kernels = [predict, "taylor_update_lanes", "verify_accept_mixed"]
    scopes = ["speca.draft", "speca.verify", "speca.full", "speca.update"]
    if depth > 1:
        kernels.append("lane_rollback")
        scopes.append("speca.rollback")
    for k in kernels:
        assert f"%{k}." in text, f"no kernel named {k!r}"
    for sc in scopes:
        assert f"/{sc}/" in text, f"no op under the {sc!r} scope"


def _dit_xl2_fill(mesh=None, lanes=LANES):
    """The DiT-XL/2 engine's lane fill and release (the programs every
    admission and completion runs) with the shapes of their arguments:
    the 256² session state of ``lanes`` lanes and one unguided lane's
    values. The fill uses no weights, so the engine is built without."""
    from repro.configs import DiffusionConfig, SpeCaConfig, get_config
    from repro.core import lane_step as LS
    from repro.serving import SpeCaEngine
    dcfg = DiffusionConfig(latent_size=32, schedule="cosine")
    eng = SpeCaEngine(get_config("dit-xl2"), None, dcfg,
                      SpeCaConfig(taylor_order=2), lanes=lanes, mesh=mesh)
    wl = eng.workloads["diffusion"]
    state = jax.eval_shape(lambda: LS.init_workload_state(
        wl, lanes, {"labels": jnp.zeros((1,), jnp.int32)},
        guidance="mixed"))
    rows = {"draft_k": jax.ShapeDtypeStruct((1,), I32),
            "max_step": jax.ShapeDtypeStruct((1,), I32),
            "tau0": jax.ShapeDtypeStruct((1,), F32),
            "paired": jax.ShapeDtypeStruct((1,), BOOL)}
    args = (jax.ShapeDtypeStruct((), I32), rows,
            ({"labels": jax.ShapeDtypeStruct((1,), I32)},),
            {"seed": jax.ShapeDtypeStruct((), jnp.uint32)})
    return eng, state, args


def _placed(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding(x) if callable(sharding)
        else sharding), tree)


def test_lane_fill_in_place_for_v5e(one_chip):
    """The one-chip DiT-XL/2 lane fill compiles for the chip as
    ``jit_speca_lane_fill`` and writes the donated state in place: every
    leaf is aliased from input to output, and the program needs no
    scratch anywhere near the size of the difference table."""
    eng, state, args = _dit_xl2_fill()
    fill = eng._fill_fns["diffusion"]
    compiled = fill.lower(_placed(state, one_chip),
                          *_placed(args, one_chip)).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit_speca_lane_fill")
    assert "input_output_alias" in text
    n_leaves = len(jax.tree.leaves(state))
    assert text.count("may-alias") + text.count("must-alias") == n_leaves
    table = state["diffs"]
    assert table.shape == (M1,) + DIT_FEAT
    mem = compiled.memory_analysis()
    if mem is not None:
        table_bytes = table.size * table.dtype.itemsize
        assert mem.temp_size_in_bytes < table_bytes // 16, \
            mem.temp_size_in_bytes


def test_lane_fill_on_lane_mesh_no_collectives_for_v5e(topo):
    """On a lane mesh over the four chips of a described v5e:2x2 (8
    DiT-XL/2 lanes per chip), the lane fill and release write each
    shard's own lanes: no all-gather, all-reduce, collective-permute or
    all-to-all in either program, and the table stays aliased."""
    import numpy as np
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from repro.serving.engine import speca_lane_release
    from repro.sharding import specs as SH
    mesh = Mesh(np.array(topo.devices), ("data",))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        eng, state, args = _dit_xl2_fill(mesh=mesh, lanes=8 * 4)
        shardings = SH.lane_state_shardings(mesh, state)
        state = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            state, shardings)
        rep = NamedSharding(mesh, PartitionSpec())
        fill = eng._fill_fns["diffusion"].lower(
            state, *_placed(args, rep)).compile().as_text()
        release = speca_lane_release.lower(
            state, jax.ShapeDtypeStruct((), I32, sharding=rep),
            {"active": jax.ShapeDtypeStruct((2,), BOOL, sharding=rep),
             "paired": jax.ShapeDtypeStruct((2,), BOOL, sharding=rep)},
            mesh=mesh).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert fill.startswith("HloModule jit_speca_lane_fill")
    assert release.startswith("HloModule jit_speca_lane_release")
    for text in (fill, release):
        for op in ("all-gather", "all-reduce", "collective-permute",
                   "all-to-all"):
            assert op not in text, op
        assert "input_output_alias" in text

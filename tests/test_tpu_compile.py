"""Compile-only checks against a described TPU v5e 2x2 topology.

Nothing runs: each case lowers and compiles for a chip that is
described, not attached, so the TPU compiler refuses here what the
chip would refuse (tiling, VMEM, memory, partitioning) at real widths.
The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from conftest import step_passes, unscoped_matmuls
from repro.configs import (DeviceInfo, MeshConfig, OSDPConfig, RunConfig,
                           get_arch, get_shape)
from repro.core.plan import make_plan
from repro.kernels.flash_attention import flash_attention
from repro.kernels.split_matmul import split_matmul
from repro.kernels.ssd_scan import ssd_scan
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model, train_inputs
from repro.optim import AdamWConfig, init_state, state_shardings
from repro.roofline.analysis import analyze_lowered
from repro.serving.engine import make_prefill_step, make_serve_step
from repro.train.loop import make_train_step

V5E = DeviceInfo.preset("tpu-v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _with(tree, sharding):
    """Abstract copy of `tree` placed by `sharding` (one sharding for
    every leaf, or a matching tree of them)."""
    if not isinstance(sharding, (dict, tuple)):
        return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), tree)
    return jax.tree.map(lambda s, sh: _sds(s.shape, s.dtype, sh),
                        tree, sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    used = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E.hbm_bytes, (used / 2**30, mem)
    return used


# --- Pallas kernels at model widths -----------------------------------------

@pytest.mark.parametrize("kv,g,hd", [
    (16, 1, 64),     # qwen1.5-0.5b: 16 heads of 64, no grouping
    (8, 3, 128),     # grouped-query attention
])
def test_flash_attention_compiles(one_chip, kv, g, hd):
    B, S = 1, 4096
    q = _sds((B, kv, g, S, hd), jnp.bfloat16, one_chip)
    k = _sds((B, kv, S, hd), jnp.bfloat16, one_chip)
    fn = functools.partial(flash_attention, causal=True)
    compiled = jax.jit(fn).lower(q, k, k).compile()
    assert _has_kernel(compiled)


def test_split_matmul_compiles(one_chip):
    # qwen1.5-0.5b's ffn up-projection: d_ff 2816 is not a multiple of
    # the 512 default block
    x = _sds((4096, 1024), jnp.bfloat16, one_chip)
    w = _sds((1024, 2816), jnp.bfloat16, one_chip)
    compiled = jax.jit(split_matmul).lower(x, w).compile()
    assert _has_kernel(compiled)


def test_ssd_scan_compiles(one_chip):
    # mamba2-2.7b: 80 heads of 64, state 128, chunk 256
    B, S, nh, hd, ns = 1, 4096, 80, 64, 128
    x = _sds((B, S, nh, hd), jnp.bfloat16, one_chip)
    dt = _sds((B, S, nh), jnp.float32, one_chip)
    a_log = _sds((nh,), jnp.float32, one_chip)
    bc = _sds((B, S, ns), jnp.bfloat16, one_chip)
    fn = functools.partial(ssd_scan, chunk=256)
    compiled = jax.jit(fn).lower(x, dt, a_log, bc, bc).compile()
    assert _has_kernel(compiled)


# --- the qwen1.5-0.5b steps chip_smoke.py runs -------------------------------

def _qwen_run(mesh_cfg, *, batch, seq, force_mode=None):
    shape = dataclasses.replace(get_shape("train_4k"), seq_len=seq,
                                global_batch=batch)
    return RunConfig(model=get_arch("qwen1.5-0.5b"), shape=shape,
                     mesh=mesh_cfg,
                     osdp=OSDPConfig(force_mode=force_mode,
                                     memory_limit_bytes=V5E.hbm_bytes))


@pytest.fixture(scope="module")
def train_step_one_chip(one_chip):
    """Full-width qwen1.5-0.5b, its searched plan, AdamW, batch 2 x
    4096: the one-chip train step, compiled once for the tests below."""
    run = _qwen_run(MeshConfig((1, 1), ("data", "model")), batch=2,
                    seq=4096)
    built = build_model(run, make_plan(run, V5E), None)
    step_fn, _ = make_train_step(built, AdamWConfig())
    params = _with(built.abstract_params(), one_chip)
    opt = _with(jax.eval_shape(init_state, params), one_chip)
    batch = _with(train_inputs(run.model, 2, 4096), one_chip)
    return step_fn.lower(params, opt, batch).compile()


def test_train_step_fits_one_chip(train_step_one_chip):
    """The one-chip train step compiles and fits in HBM."""
    assert _fits(train_step_one_chip) > 2**30


def test_train_step_attention_saves_no_blocks(train_step_one_chip):
    """Attention's backward recomputes each block's probabilities: the
    step compiles to 8.23 GiB, where the float32 probability blocks
    that autodiff of the scans saved took it to 14.29 GiB."""
    assert _fits(train_step_one_chip) < 10 * 2**30


def test_train_step_layer_scopes(train_step_one_chip):
    """Every matmul of the chip's optimized step names its layer, and
    the forward, recomputed, backward and optimizer instructions are
    all there to find."""
    text = train_step_one_chip.as_text()
    assert unscoped_matmuls(text) == []
    assert step_passes(text) == {"forward", "recompute", "backward",
                                 "optimizer"}


def test_serve_steps_compile_one_chip(one_chip):
    """Prefill of a 512-token prompt and an 8-slot decode step."""
    cfg = get_arch("qwen1.5-0.5b")
    run = RunConfig(model=cfg, shape=get_shape("decode_32k"),
                    mesh=MeshConfig((1, 1), ("data", "model")),
                    osdp=OSDPConfig(enabled=False))
    built = build_model(run)
    params = _with(built.abstract_params(), one_chip)
    cache_len = 512 + 64
    prompt = _sds((1, 512), jnp.int32, one_chip)
    _fits(make_prefill_step(built, cache_len).lower(
        params, {"tokens": prompt}).compile())
    caches = _with(jax.eval_shape(
        lambda: built.model.init_caches(8, cache_len)), one_chip)
    toks = _sds((8, 1), jnp.int32, one_chip)
    t = _sds((8,), jnp.int32, one_chip)
    _fits(make_serve_step(built).lower(params, caches, toks, t).compile())


def test_zdp_step_all_gathers_on_four_chips(topo):
    """The --four-chips ZDP step on a (4, 1) data mesh: fits each chip
    and all-gathers its parameters."""
    mesh_cfg = MeshConfig((4, 1), ("data", "model"))
    mesh = make_mesh(mesh_cfg.shape, mesh_cfg.axes, devices=topo.devices)
    run = _qwen_run(mesh_cfg, batch=4, seq=4096, force_mode="ZDP")
    built = build_model(run, make_plan(run, V5E), mesh)
    with jax.set_mesh(mesh):
        step_fn, _ = make_train_step(built, AdamWConfig())
        params = _with(built.abstract_params(), built.shardings)
        opt = _with(jax.eval_shape(init_state, params),
                    state_shardings(built.shardings,
                                    NamedSharding(mesh, P())))
        batch = _with(train_inputs(run.model, 4, 4096),
                      NamedSharding(mesh, P("data", None)))
        compiled = step_fn.lower(params, opt, batch).compile()
    _fits(compiled)
    assert "all-gather" in analyze_lowered(compiled.as_text())


def test_phi4_cell_step_fits_four_chips(topo):
    """The `phi4mini-L8-osdp4-train-4k` step: Phi-4-mini's widths at 8
    of 32 layers, 1 x 4096 a chip on a (4, 1) data mesh, under the plan
    searched for the cell's budget. The plan mixes DP and ZDP, and the
    step fits the 15.75 GiB the chip gives a program; forced DP needs
    22.42 GiB on a described v5e 2x2 and is no candidate."""
    import json
    import pathlib
    cfg_file = (pathlib.Path(__file__).resolve().parents[1] / "chipbench"
                / "configs" / "phi-4-mini.json")
    budget = json.loads(cfg_file.read_text())["deployment"][
        "memory_limit_gib"]
    model = dataclasses.replace(get_arch("phi4-mini-3.8b"), n_layers=8)
    mesh_cfg = MeshConfig((4, 1), ("data", "model"))
    mesh = make_mesh(mesh_cfg.shape, mesh_cfg.axes, devices=topo.devices)
    run = RunConfig(model=model, shape=dataclasses.replace(
        get_shape("train_4k"), seq_len=4096, global_batch=4),
        mesh=mesh_cfg, osdp=OSDPConfig(memory_limit_bytes=budget * 2**30))
    plan = make_plan(run, V5E)
    modes = {m for d in plan.decisions.values() for m in d.modes}
    assert modes == {"DP", "ZDP"}, modes
    built = build_model(run, plan, mesh)
    with jax.set_mesh(mesh):
        step_fn, _ = make_train_step(built, AdamWConfig())
        params = _with(built.abstract_params(), built.shardings)
        opt = _with(jax.eval_shape(init_state, params),
                    state_shardings(built.shardings,
                                    NamedSharding(mesh, P())))
        batch = _with(train_inputs(model, 4, 4096),
                      NamedSharding(mesh, P("data", None)))
        compiled = step_fn.lower(params, opt, batch).compile()
    assert _fits(compiled) < 15.75 * 2**30
    text = compiled.as_text()
    assert "all-gather" in analyze_lowered(text)
    assert "zdp/gather" in text

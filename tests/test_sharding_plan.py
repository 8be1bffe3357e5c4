"""Plan -> PartitionSpec compilation + operator-splitting semantics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, tiny_run
from repro.configs import OSDPConfig, get_arch, get_shape, reduced
from repro.core.cost_model import DP, ZDP, Decision
from repro.core.operator_split import chunked_ffn, chunked_matmul
from repro.models.registry import build_model
from repro.sharding.specs import (WeightSpec, _merge_modes, build_param_set,
                                  layout_for, seg_matmul)


# --- segment layout ----------------------------------------------------------

def test_merge_modes_uniform_collapses():
    # merged runs also carry the contributing plan-slice indices
    assert _merge_modes([ZDP] * 4, 1024) == [(ZDP, 0, 1024, (0, 1, 2, 3))]
    assert _merge_modes([DP] * 8, 512) == [(DP, 0, 512,
                                            tuple(range(8)))]


def test_merge_modes_mixed():
    segs = _merge_modes([ZDP, ZDP, DP, DP], 1024)
    assert segs == [(ZDP, 0, 512, (0, 1)), (DP, 512, 512, (2, 3))]
    # boundaries snap to 128 where possible (MXU alignment)
    segs = _merge_modes([ZDP, DP, DP], 1152)
    assert all(s % 128 == 0 for _, s, _, _ in segs)


def test_layout_single_segment_when_no_zdp_axis():
    spec = WeightSpec("w", (64,), "op", zdp_axis=None)
    lay = layout_for(spec, Decision("op", (ZDP, ZDP)))
    assert len(lay.segments) == 1 and lay.segments[0].mode == DP


# --- seg_matmul semantics -----------------------------------------------------

def _pset_for(shape, zdp_axis, decision, stacked=False, tp_axis=None):
    spec = WeightSpec("w", shape, "op", tp_axis=tp_axis, zdp_axis=zdp_axis,
                      stacked=stacked)
    return build_param_set([spec], {"op": decision}, None,
                           jax.random.PRNGKey(0))


def test_seg_matmul_sum_variant_matches_plain():
    """Input-dim split (Figure 4): sum of slice products == full matmul."""
    pset = _pset_for((256, 64), 0, Decision("op", (ZDP, DP, ZDP, DP)))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256))
    w_full = jnp.concatenate([pset.params[k] for k, _ in pset.segments("w")],
                             axis=0)
    y = seg_matmul(x, pset.params, pset, "w", 0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w_full),
                               atol=1e-4, rtol=1e-4)


def test_seg_matmul_concat_variant_matches_plain():
    """Output-dim split: concat of slice outputs == full matmul."""
    pset = _pset_for((64, 256), 1, Decision("op", (DP, ZDP)))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    w_full = jnp.concatenate([pset.params[k] for k, _ in pset.segments("w")],
                             axis=1)
    y = seg_matmul(x, pset.params, pset, "w", 0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w_full),
                               atol=1e-4, rtol=1e-4)


# --- chunked (uniform-mode) splitting ------------------------------------------

@pytest.mark.parametrize("g", [1, 2, 4, 8])
def test_chunked_matmul_equivalence(g):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 17, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (256, 64))
    y = chunked_matmul(x, w, g)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w), atol=1e-4,
                               rtol=1e-4)


@pytest.mark.parametrize("act", ["swiglu", "gelu"])
@pytest.mark.parametrize("g", [1, 2, 4])
def test_chunked_ffn_equivalence(act, g):
    two = 2 if act == "swiglu" else 1
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 64))
    w13 = jax.random.normal(jax.random.PRNGKey(1), (64, two * 128)) * 0.1
    w2 = jax.random.normal(jax.random.PRNGKey(2), (128, 64)) * 0.1
    y = chunked_ffn(x, w13, w2, g, act)
    y1 = chunked_ffn(x, w13, w2, 1, act)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y1), atol=1e-4,
                               rtol=1e-3)


# --- plans change params layout, not math --------------------------------------

@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "hymba-1.5b"])
def test_forward_invariant_under_plan(arch):
    """The same seed + different OSDP plans must give identical loss on
    one device (plans change sharding/layout, never semantics)."""
    run_dp = tiny_run(arch, osdp=OSDPConfig(enabled=True, force_mode="DP",
                                            operator_splitting=False))
    run_zs = tiny_run(arch, osdp=OSDPConfig(enabled=True, force_mode="ZDP",
                                            default_slice_granularity=4))
    from repro.core.plan import make_plan
    losses = []
    for run in (run_dp, run_zs):
        plan = make_plan(run)
        built = build_model(run, plan)
        params = built.init(jax.random.PRNGKey(0))
        batch = make_batch(run.model, 2, 64)
        loss, _ = jax.jit(built.model.loss_fn)(params, batch)
        losses.append(float(loss))
    # segment init differs per-leaf RNG; compare magnitudes only loosely
    assert abs(losses[0] - losses[1]) < 0.5, losses


def test_zdp_plan_shards_over_data_axis():
    """On a fake 4-device mesh the ZDP weights' shardings use `data`."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        from repro.configs import (OSDPConfig, RunConfig, MeshConfig,
                                   get_arch, get_shape, reduced)
        from repro.core.plan import make_plan
        from repro.models.registry import build_model
        import dataclasses
        cfg = reduced(get_arch("phi4-mini-3.8b"))
        mesh_cfg = MeshConfig((2, 2), ("data", "model"))
        shape = dataclasses.replace(get_shape("train_4k"), seq_len=64,
                                    global_batch=4)
        run = RunConfig(model=cfg, shape=shape, mesh=mesh_cfg,
                        osdp=OSDPConfig(force_mode="ZDP",
                                        operator_splitting=False))
        plan = make_plan(run)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2), ("data", "model"))
        built = build_model(run, plan, mesh)
        sh = built.shardings["layers/ffn/w13"]
        assert "data" in str(sh.spec), sh.spec
        assert "model" in str(sh.spec), sh.spec
        sh_dp = built.shardings["layers/ffn/norm_scale"]
        assert "data" not in str(sh_dp.spec), sh_dp.spec
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_env())
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def _env():
    import os
    e = dict(os.environ)
    e["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    return e

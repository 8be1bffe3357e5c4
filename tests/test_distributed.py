"""Distributed semantics on forced multi-device CPU (subprocess — jax
locks the device count at first init, so these run out-of-process).

The ZeRO invariant the whole paper rests on: DP, ZDP, and any mixed
OSDP plan compute IDENTICAL training trajectories — sharding changes
where bytes live, never the math. We train the same tiny model for 3
steps under three plans on a 4-device (2 data x 2 model) mesh and
compare losses bitwise-ish (fp32 tolerance).
"""
import os
import subprocess
import sys
import textwrap


def _run(code: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, env=env, timeout=560)


COMMON = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import (OSDPConfig, RunConfig, MeshConfig, get_arch,
                           get_shape, reduced)
from repro.core.plan import make_plan, data_sharding
from repro.launch.mesh import make_mesh
from repro.models.registry import build_model, input_shardings
from repro.train.loop import make_train_step
from repro.optim import AdamWConfig

def make_batch(cfg, B, S, key=0):
    k = jax.random.PRNGKey(key)
    return {
        "tokens": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(k, (B, S), 0, cfg.vocab_size),
    }

def losses_for(force_mode, split, arch="qwen1.5-0.5b", steps=3):
    cfg = reduced(get_arch(arch))
    mesh_cfg = MeshConfig((2, 2), ("data", "model"))
    shape = dataclasses.replace(get_shape("train_4k"), seq_len=64,
                                global_batch=4)
    osdp = OSDPConfig(force_mode=force_mode, operator_splitting=split > 1,
                      default_slice_granularity=max(split, 1))
    run = RunConfig(model=cfg, shape=shape, mesh=mesh_cfg, osdp=osdp)
    plan = make_plan(run)
    mesh = make_mesh((2, 2), ("data", "model"))
    built = build_model(run, plan, mesh)
    with jax.set_mesh(mesh):
        step_fn, init_fn = make_train_step(built, AdamWConfig(lr=1e-3),
                                           donate=False)
        params, opt = init_fn(jax.random.PRNGKey(0))
        out = []
        for s in range(steps):
            batch = make_batch(cfg, 4, 64, key=s)
            dsh = data_sharding(mesh)
            batch = {k: jax.device_put(v, NamedSharding(
                mesh, P(("data",), *([None] * (v.ndim - 1)))))
                for k, v in batch.items()}
            params, opt, metrics = step_fn(params, opt, batch)
            out.append(float(metrics["loss"]))
        return out
"""



def test_dp_zdp_mixed_same_trajectory():
    code = COMMON + textwrap.dedent("""
        l_dp = losses_for("DP", 1)
        l_zdp = losses_for("ZDP", 1)
        l_split = losses_for("ZDP", 2)
        print("DP  ", l_dp)
        print("ZDP ", l_zdp)
        print("SPLT", l_split)
        np.testing.assert_allclose(l_dp, l_zdp, rtol=2e-2, atol=2e-2)
        print("EQUIV_OK")
    """)
    r = _run(code)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "EQUIV_OK" in r.stdout, r.stdout


def test_train_step_lowers_with_collectives():
    """On the 2x2 mesh the ZDP plan's HLO must contain all-gathers of
    parameters and reduce-scatters of gradients."""
    code = COMMON + textwrap.dedent("""
        import dataclasses
        cfg = reduced(get_arch("qwen1.5-0.5b"))
        mesh_cfg = MeshConfig((2, 2), ("data", "model"))
        shape = dataclasses.replace(get_shape("train_4k"), seq_len=64,
                                    global_batch=4)
        run = RunConfig(model=cfg, shape=shape, mesh=mesh_cfg,
                        osdp=OSDPConfig(force_mode="ZDP",
                                        operator_splitting=False))
        plan = make_plan(run)
        mesh = make_mesh((2, 2), ("data", "model"))
        built = build_model(run, plan, mesh)
        with jax.set_mesh(mesh):
            step_fn, init_fn = make_train_step(built, donate=False)
            params, opt = init_fn(jax.random.PRNGKey(0))
            batch = make_batch(cfg, 4, 64)
            lowered = step_fn.lower(params, opt, batch)
            compiled = lowered.compile()
            txt = compiled.as_text()
        from repro.roofline.analysis import analyze_lowered
        coll = analyze_lowered(txt)
        assert "all-gather" in coll, list(coll)
        assert ("reduce-scatter" in coll) or ("all-reduce" in coll), \\
            list(coll)
        print("COLL_OK", {k: v for k, v in coll.items() if k != "total_bytes"})
    """)
    r = _run(code)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "COLL_OK" in r.stdout, r.stdout


def test_dp_vs_zdp_collective_bytes():
    """ZDP must move MORE collective bytes than DP (the paper's 1.5x) —
    measured on real compiled HLO, not the cost model."""
    code = COMMON + textwrap.dedent("""
        import dataclasses
        from repro.roofline.analysis import analyze_lowered

        def coll_bytes(force_mode):
            cfg = reduced(get_arch("qwen1.5-0.5b"))
            mesh_cfg = MeshConfig((4, 1), ("data", "model"))
            shape = dataclasses.replace(get_shape("train_4k"), seq_len=64,
                                        global_batch=4)
            run = RunConfig(model=cfg, shape=shape, mesh=mesh_cfg,
                            osdp=OSDPConfig(force_mode=force_mode,
                                            operator_splitting=False,
                                            checkpointing=False))
            plan = make_plan(run)
            mesh = make_mesh((4, 1), ("data", "model"))
            built = build_model(run, plan, mesh)
            with jax.set_mesh(mesh):
                step_fn, init_fn = make_train_step(built, donate=False)
                params, opt = init_fn(jax.random.PRNGKey(0))
                batch = make_batch(cfg, 4, 64)
                batch = {k: jax.device_put(v, NamedSharding(
                    mesh, P(("data",), *([None] * (v.ndim - 1)))))
                    for k, v in batch.items()}
                txt = step_fn.lower(params, opt, batch).compile().as_text()
            return analyze_lowered(txt)["total_bytes"]

        b_dp = coll_bytes("DP")
        b_zdp = coll_bytes("ZDP")
        print("bytes DP", b_dp, "ZDP", b_zdp)
        assert b_zdp > b_dp * 1.2, (b_dp, b_zdp)
        print("RATIO_OK", b_zdp / b_dp)
    """)
    r = _run(code)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    assert "RATIO_OK" in r.stdout, r.stdout

"""End-to-end driver: train a ~100M-param model for a few hundred steps
under three parallel plans (DP / FSDP / OSDP) on a forced 4-device CPU
mesh, verifying the ZeRO invariant (identical loss trajectories) and
reporting wall-clock per plan.

Run:  PYTHONPATH=src python examples/train_osdp_vs_fsdp.py [--steps 200]

(The 4-device mesh is forced via XLA_FLAGS before jax import, so run
this as a script, not inside another jax process.)
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import argparse        # noqa: E402
import dataclasses     # noqa: E402
import time            # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np     # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (DENSE, MeshConfig, ModelConfig, OSDPConfig,  # noqa: E402
                           RunConfig, get_shape)
from repro.core.plan import make_plan  # noqa: E402
from repro.launch.mesh import make_mesh_from_config  # noqa: E402
from repro.data.synthetic import Dataset  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.train.loop import make_train_step  # noqa: E402

# ~100M params: 12 x 768 GPT-ish (the deliverable config; needs an
# accelerator or patience for "a few hundred steps")
MODEL_100M = ModelConfig(
    name="demo-100m", family=DENSE, n_layers=12, d_model=768, n_heads=12,
    n_kv_heads=12, d_ff=3072, vocab_size=32768, act="swiglu", rope="rope",
)
# ~8M: CPU-sized default so the demo finishes in minutes
MODEL_SMALL = ModelConfig(
    name="demo-8m", family=DENSE, n_layers=6, d_model=256, n_heads=4,
    n_kv_heads=4, d_ff=1024, vocab_size=8192, act="swiglu", rope="rope",
)
MODEL = MODEL_SMALL


def run_plan(label: str, force_mode, steps: int, seq: int, batch: int,
             model=None):
    global MODEL
    MODEL = model or MODEL
    mesh_cfg = MeshConfig((2, 2), ("data", "model"))
    shape = dataclasses.replace(get_shape("train_4k"), seq_len=seq,
                                global_batch=batch)
    osdp = OSDPConfig(force_mode=force_mode,
                      memory_limit_bytes=2 * 2**30,
                      operator_splitting=force_mode is None)
    run = RunConfig(model=MODEL, shape=shape, mesh=mesh_cfg, osdp=osdp)
    plan = make_plan(run)
    mesh = make_mesh_from_config(mesh_cfg)
    built = build_model(run, plan, mesh)
    ds = Dataset(MODEL, shape, seed=0)
    with jax.set_mesh(mesh):
        step_fn, init_fn = make_train_step(
            built, AdamWConfig(lr=3e-4), warmup=20, donate=False)
        params, opt = init_fn(jax.random.PRNGKey(0))
        losses = []
        t0 = time.perf_counter()
        for s in range(steps):
            b = ds.global_batch(s)
            b = {k: jax.device_put(jnp.asarray(v), NamedSharding(
                mesh, P(("data",), *([None] * (v.ndim - 1)))))
                for k, v in b.items()}
            params, opt, m = step_fn(params, opt, b)
            losses.append(float(m["loss"]))
        dt = time.perf_counter() - t0
    n_zdp = sum(1 for d in plan.decisions.values()
                if d.uniform() not in ("DP", None))
    print(f"{label:6s} loss {losses[0]:.4f} -> {losses[-1]:.4f} | "
          f"{steps / dt:.2f} steps/s | zdp_ops={n_zdp}")
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--full", action="store_true",
                    help="use the ~100M deliverable config")
    args = ap.parse_args()
    global MODEL
    MODEL = MODEL_100M if args.full else MODEL_SMALL
    print(f"model: {MODEL.name} = {MODEL.param_count() / 1e6:.1f}M params, "
          f"mesh 2x2 (data x model), {args.steps} steps")
    l_dp = run_plan("DP", "DP", args.steps, args.seq, args.batch)
    l_fsdp = run_plan("FSDP", "ZDP", args.steps, args.seq, args.batch)
    l_osdp = run_plan("OSDP", None, args.steps, args.seq, args.batch)
    d = max(abs(a - b) for a, b in zip(l_dp, l_fsdp))
    d2 = max(abs(a - b) for a, b in zip(l_dp, l_osdp))
    print(f"max |loss_DP - loss_FSDP| = {d:.4f}; "
          f"max |loss_DP - loss_OSDP| = {d2:.4f} "
          f"(ZeRO invariant: sharding never changes the math)")


if __name__ == "__main__":
    main()

"""Training launcher.

    python -m repro.launch.train --arch qwen1.5-0.5b --steps 100 \
        [--reduced] [--seq 256 --batch 8] [--force-mode ZDP] \
        [--memory-gib 16] [--ckpt-dir /tmp/ckpt]

Runs the OSDP pipeline (describe -> search -> plan), builds the model
with the planned shardings on the local mesh, and trains on the
synthetic pipeline. The planner prices the attached device (its
`device_kind`) unless --device names a preset. On a real TPU slice the
same RunConfig lowers against make_production_mesh() instead (see
launch/dryrun.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

import jax

from repro.configs import (DeviceInfo, MeshConfig, OSDPConfig, RunConfig,
                           get_arch, get_shape, preset_for_device, reduced)
from repro.core.plan import make_plan
from repro.launch.cache import enable_compilation_cache
from repro.launch.mesh import make_mesh_from_config
from repro.models.registry import build_model
from repro.optim import AdamWConfig
from repro.sharding.specs import OverlapConfig
from repro.train.loop import train


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant (CPU-sized)")
    ap.add_argument("--seq", type=int, default=0)
    ap.add_argument("--batch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=100)
    ap.add_argument("--memory-gib", type=float, default=16.0)
    ap.add_argument("--device", default=None, metavar="PRESET",
                    help="DeviceInfo preset the planner prices against "
                         "(tpu-v5e, tpu-v4, a100-80g, h100-sxm; "
                         "default: the attached device's)")
    ap.add_argument("--overlap", default=None, metavar="FACTOR",
                    help="comm/compute overlap: a factor in [0, 1] for "
                         "the planner's timeline model, or 'auto' for "
                         "the device preset's catalog value; also "
                         "turns on the runtime prefetch + gradient-"
                         "bucketing transforms (default: off, serial "
                         "model, legacy program)")
    ap.add_argument("--overlap-prefetch", type=int, default=1,
                    help="segment-weight gather prefetch depth "
                         "(slices ahead, with --overlap)")
    ap.add_argument("--overlap-bucket-mib", type=float, default=4.0,
                    help="gradient all-reduce bucket size in MiB "
                         "(with --overlap)")
    ap.add_argument("--force-mode", default=None, choices=["DP", "ZDP"])
    ap.add_argument("--no-osdp", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--keep-last", type=int, default=0,
                    help="checkpoint retention: keep the newest N "
                         "completed steps (0 = keep everything)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest valid checkpoint under "
                         "--ckpt-dir and treat --steps as the TOTAL "
                         "step target (completed steps are skipped)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.resume and not args.ckpt_dir:
        ap.error("--resume requires --ckpt-dir")

    model_cfg = get_arch(args.arch)
    if args.reduced:
        model_cfg = reduced(model_cfg)
    shape = get_shape(args.shape)
    if args.seq or args.batch:
        shape = dataclasses.replace(
            shape, seq_len=args.seq or shape.seq_len,
            global_batch=args.batch or shape.global_batch)

    enable_compilation_cache()
    n_dev = len(jax.devices())
    mesh_cfg = MeshConfig((n_dev, 1), ("data", "model"))
    osdp = OSDPConfig(enabled=not args.no_osdp,
                      memory_limit_bytes=args.memory_gib * 2**30,
                      force_mode=args.force_mode)
    run = RunConfig(model=model_cfg, shape=shape, mesh=mesh_cfg, osdp=osdp)
    ov, overlap_cfg = None, None
    if args.overlap is not None:
        ov = args.overlap if args.overlap == "auto" else float(args.overlap)
        overlap_cfg = OverlapConfig(
            prefetch=args.overlap_prefetch,
            bucket_bytes=int(args.overlap_bucket_mib * 2**20))
    device = DeviceInfo.preset(
        args.device or preset_for_device(jax.devices()[0]), overlap=ov)
    plan = make_plan(run, device)
    print(plan.summary())
    mesh = make_mesh_from_config(mesh_cfg) if n_dev > 1 else None
    built = build_model(run, plan, mesh, overlap=overlap_cfg)
    res = train(built, args.steps, seed=args.seed,
                opt_cfg=AdamWConfig(lr=args.lr), warmup=args.warmup,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                keep_last=args.keep_last, resume=args.resume)
    if not res.steps:
        print(f"nothing to train: checkpoint already at step "
              f"{res.start_step} >= target {args.steps}")
        return 0
    print(f"done: {res.steps} steps, loss {res.losses[0]:.4f} -> "
          f"{res.losses[-1]:.4f}, {res.tokens_per_s:.0f} tok/s")
    return 0


if __name__ == "__main__":
    sys.exit(main())

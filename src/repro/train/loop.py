"""Training loop: jit-compiled step with OSDP shardings + microbatching.

`make_train_step(built, ...)` returns (step_fn, init_fn) where step_fn
is `jit(step, in_shardings=..., out_shardings=..., donate...)` — the
same callable the dry-run lowers for the production meshes and the
smoke tests execute on CPU.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import io as ckpt_io
from repro.configs.base import RunConfig
from repro.data.synthetic import Dataset
from repro.models.registry import Built, input_shardings
from repro.optim import (AdamWConfig, AdamWState, apply_update, init_state,
                         state_shardings, warmup_cosine)


def loss_and_grads(model, params, batch, microbatch: int = 0):
    """Optionally microbatched (gradient-accumulated) value+grad."""
    if microbatch <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    n = microbatch
    split = lambda x: x.reshape(n, x.shape[0] // n, *x.shape[1:])
    mb = jax.tree.map(split, batch)

    def body(carry, b):
        acc_loss, acc_grads = carry
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, b)
        acc_grads = jax.tree.map(jnp.add, acc_grads, grads)
        return (acc_loss + loss, acc_grads), metrics

    zero_grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
    (loss, grads), metrics = jax.lax.scan(body, (jnp.zeros(()), zero_grads),
                                          mb)
    grads = jax.tree.map(lambda g: g / n, grads)
    last = jax.tree.map(lambda m: m[-1], metrics)
    return loss / n, last, grads


def _bucket_grads(grads, bucket_bytes: int):
    """Greedily pack gradient leaves (tree order) into ~`bucket_bytes`
    buckets and pass each bucket through one `optimization_barrier`.

    Identity on values; the barrier makes each bucket an independently
    schedulable unit, so XLA can launch a bucket's gradient all-reduce
    as soon as the backward walk has produced its leaves instead of
    batching every reduction behind the full backward pass — the async
    all-reduce half of the overlap the timeline cost model prices.
    """
    leaves, treedef = jax.tree.flatten(grads)
    out, bucket, size = [], [], 0
    for g in leaves:
        bucket.append(g)
        size += g.size * jnp.dtype(g.dtype).itemsize
        if size >= bucket_bytes:
            out.extend(jax.lax.optimization_barrier(tuple(bucket)))
            bucket, size = [], 0
    if bucket:
        out.extend(jax.lax.optimization_barrier(tuple(bucket)))
    return jax.tree.unflatten(treedef, out)


def make_train_step(built: Built, opt_cfg: Optional[AdamWConfig] = None,
                    total_steps: int = 10_000, warmup: int = 100,
                    donate: bool = True) -> Tuple[Callable, Callable]:
    opt_cfg = opt_cfg or AdamWConfig()
    model = built.model
    run = built.run
    micro = run.microbatch
    overlap = built.pset_abstract.overlap

    def step(params, opt_state: AdamWState, batch):
        loss, metrics, grads = loss_and_grads(model, params, batch, micro)
        with jax.named_scope("optimizer"):
            if overlap is not None and overlap.bucket_bytes > 0:
                grads = _bucket_grads(grads, overlap.bucket_bytes)
            lr_scale = warmup_cosine(opt_state.step + 1, warmup,
                                     total_steps)
            params, opt_state, opt_metrics = apply_update(
                opt_cfg, params, grads, opt_state, lr_scale)
        metrics = dict(metrics, loss=loss, **opt_metrics)
        return params, opt_state, metrics

    if built.mesh is None:
        def init(key):
            params = built.init(key)
            return params, init_state(params)
        return jax.jit(step, donate_argnums=(0, 1) if donate else ()), init

    mesh = built.mesh
    psh = built.shardings
    repl = NamedSharding(mesh, P())
    osh = state_shardings(psh, repl)

    def init(key):
        params = built.init(key)
        params = {k: jax.device_put(v, psh[k]) for k, v in params.items()}
        opt = init_state(params)
        opt = jax.tree.map(jax.device_put, opt, osh)
        return params, opt
    # batch shardings ride on the input ShapeDtypeStructs / arrays
    step_jit = jax.jit(
        step,
        in_shardings=(psh, osh, None),
        out_shardings=(psh, osh, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return step_jit, init


@dataclass
class TrainResult:
    steps: int
    losses: list
    tokens_per_s: float
    final_metrics: Dict[str, float] = field(default_factory=dict)
    start_step: int = 0


def restore_or_init(built: Built, ckpt_dir: Optional[str], *,
                    seed: int = 0,
                    opt_cfg: Optional[AdamWConfig] = None,
                    warmup: int = 100, total_steps: int = 10_000,
                    print_fn=print):
    """(step_fn, params, opt_state, start_step): resume from the
    latest *valid* checkpoint under `ckpt_dir` when one exists, else
    a fresh init — what `launch/train.py --resume` and the resilience
    supervisor call after a crash or a replan.  Checkpoint validation
    (CRC + sizes) happens inside `checkpoint.io.restore`; a corrupt
    latest step raises `CheckpointCorruptError` rather than silently
    restoring garbage."""
    step_fn, init_fn = make_train_step(built, opt_cfg, warmup=warmup,
                                       total_steps=total_steps)
    params, opt_state = init_fn(jax.random.PRNGKey(seed))
    start_step = 0
    if ckpt_dir and ckpt_io.latest_step(ckpt_dir) is not None:
        (params, opt_state), start_step = ckpt_io.restore(
            ckpt_dir, (params, opt_state))
        print_fn(f"restored checkpoint at step {start_step}")
    return step_fn, params, opt_state, start_step


def train(built: Built, n_steps: int, *, seed: int = 0,
          opt_cfg: Optional[AdamWConfig] = None,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 0,
          keep_last: int = 0, resume: bool = False,
          log_every: int = 10, batch_override: Optional[int] = None,
          seq_override: Optional[int] = None, warmup: int = 100,
          total_steps: int = 10_000, faults=None,
          print_fn=print) -> TrainResult:
    """Single-host training driver (CPU smoke / example scale).

    `resume=True` makes `n_steps` the TOTAL step target: a restored
    run skips its already-completed steps (restoring at step >=
    `n_steps` trains nothing).  The default (False) keeps the legacy
    semantics — train `n_steps` more from wherever the restore landed.

    `keep_last > 0` prunes checkpoint retention to the newest N
    completed steps.  `faults` (a `resilience.faults.FaultSchedule`)
    injects device losses (raising `DeviceLost` at the scheduled
    step — progress since the last checkpoint is lost, exactly like
    the real failure) and checkpoint-write crashes
    (`CheckpointCrashError` mid-save).
    """
    step_fn, params, opt_state, start_step = restore_or_init(
        built, ckpt_dir, seed=seed, opt_cfg=opt_cfg, warmup=warmup,
        total_steps=total_steps, print_fn=print_fn)
    ds = Dataset(built.run.model, built.run.shape, seed=seed)
    target = n_steps if resume else start_step + n_steps
    if resume and start_step >= target:
        print_fn(f"nothing to do: restored step {start_step} >= "
                 f"target {target}")
        return TrainResult(0, [], 0.0, {}, start_step)

    def save(step: int) -> None:
        crash = (faults.checkpoint_crash_at(step)
                 if faults is not None else None)
        ckpt_io.save(ckpt_dir, step, (params, opt_state),
                     keep_last=keep_last,
                     crash_after_leaves=(crash.after_leaves
                                         if crash else None))

    losses = []
    t0 = time.perf_counter()
    tokens = 0
    metrics = {}
    for s in range(start_step, target):
        if faults is not None:
            ev = faults.device_loss_at(s)
            if ev is not None:
                from repro.resilience.faults import DeviceLost
                raise DeviceLost(ev, s)
        batch = {k: jnp.asarray(v) for k, v in ds.global_batch(
            s, batch=batch_override, seq=seq_override).items()}
        tokens += int(np.prod(batch["labels"].shape))
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if log_every and (s % log_every == 0 or s == target - 1):
            print_fn(f"step {s:5d} loss {loss:.4f} "
                     f"gnorm {float(metrics['grad_norm']):.3f}")
        if ckpt_dir and ckpt_every and (s + 1) % ckpt_every == 0:
            save(s + 1)
    dt = time.perf_counter() - t0
    if ckpt_dir:
        save(target)
    return TrainResult(target - start_step, losses, tokens / dt,
                       {k: float(v) for k, v in metrics.items()},
                       start_step)

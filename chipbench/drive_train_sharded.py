"""Train cells on several chips: the program's planned, compiled training
step on a (chips, 1) data mesh under the plan OSDP searches for the
configuration's memory limit, fed one sequence per chip, timed for the
window as `drive_train.py` times a one-chip cell, and checked against
the float32 reference with its state laid over the same chips.

The weights from the seed are placed with the plan's shardings (DP
tensors whole on every chip, ZDP tensors split over the data axis), and
each batch is split over the data axis by rows. The reference's
equations are `drive_train.reference_steps`'s; only where its tensors
lie differs: each is split over the data axis along its largest
dimension that divides (never the layer axis it scans), and the batch
by rows.
"""
from __future__ import annotations

import collections
import gc
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import compare, gen, weights
from chipbench.drive_train import (GiB, IN_FLIGHT, _square_sums,
                                   compiled_bytes, merge_square_sums,
                                   merged_norms, to_program, train_numbers)
from chipbench.reference.dense import adamw, warmup_cosine
from chipbench.trace import span


class Program:
    """The system under test for one sharded train cell: mesh, plan,
    model, and the shardings of its state and batches."""

    def __init__(self, cfg: dict, m: dict, mix: dict, devices,
                 force_mode=None):
        from repro.configs import (DeviceInfo, MeshConfig, ModelConfig,
                                   OSDPConfig, RunConfig, ShapeConfig,
                                   preset_for_device)
        from repro.core.plan import make_plan
        from repro.launch.mesh import make_mesh
        from repro.models.registry import build_model
        from repro.optim import state_shardings

        self.model_cfg = ModelConfig(name=cfg["name"], **m)
        shape = ShapeConfig("chipbench", mix["seq"], mix["batch"], "train")
        mesh_cfg = MeshConfig((len(devices), 1), ("data", "model"))
        self.mesh = make_mesh(mesh_cfg.shape, mesh_cfg.axes, devices)
        osdp = OSDPConfig(memory_limit_bytes=cfg["deployment"]
                          ["memory_limit_gib"] * GiB, force_mode=force_mode)
        self.run = RunConfig(model=self.model_cfg, shape=shape,
                             mesh=mesh_cfg, osdp=osdp)
        with span("plan"):
            device = DeviceInfo.preset(preset_for_device(devices[0]))
            self.plan = make_plan(self.run, device)
        self.built = build_model(self.run, self.plan, self.mesh)
        self.psh = self.built.shardings
        self.osh = state_shardings(self.psh, NamedSharding(self.mesh, P()))

    def make_state(self, family, m: dict, seed: int):
        """Weights from the seed in one jitted call, laid out as the plan
        wants them, and the optimizer's state from them."""
        from repro.optim import init_state
        params = jax.jit(lambda key: to_program(family.make(m, key),
                                                self.built),
                         out_shardings=self.psh)(weights.seed_key(seed))
        return params, jax.jit(init_state, out_shardings=self.osh)(params)

    def put(self, batch: dict) -> dict:
        """A host batch split over the data axis by rows."""
        return {k: jax.device_put(v, NamedSharding(self.mesh, P("data")))
                for k, v in batch.items()}

    def compile(self, opt_cfg: dict, params, opt, batch):
        from repro.optim import AdamWConfig
        from repro.train.loop import make_train_step
        with jax.set_mesh(self.mesh):
            step_fn, _ = make_train_step(
                self.built, AdamWConfig(**{k: opt_cfg[k] for k in (
                    "lr", "b1", "b2", "eps", "weight_decay", "grad_clip")}),
                total_steps=opt_cfg["total_steps"], warmup=opt_cfg["warmup"])
            return step_fn.lower(params, opt, batch).compile()

    def modes(self) -> Dict[str, List[str]]:
        """Each operator's mode per slice, as the plan chose them."""
        return {op: list(d.modes) for op, d in
                sorted(self.plan.decisions.items())}


def first_steps(prog: Program, step, params, opt, batches, opt_cfg: dict,
                family, m: dict, seed: int):
    """The checked steps, through the window's own call: losses, pre-clip
    gradient norms, the first gradient as the optimizer got it, and each
    tensor's change from the seed's weights."""
    losses, gnorms = [], []
    for i, b in enumerate(batches):
        params, opt, met = step(params, opt, b)
        jax.block_until_ready((params, opt, met))
        losses.append(float(met["loss"]))
        gnorms.append(float(met["grad_norm"]))
        if i == 0:
            g1 = {k: v / (1 - opt_cfg["b1"])
                  for k, v in merged_norms(opt.m).items()}
    # the weights are made again inside the program that measures the
    # change, so only scalars leave it
    change = merge_square_sums(jax.jit(lambda master, key: _square_sums(
        master, to_program(family.make(m, key), prog.built)))(
            opt.master, weights.seed_key(seed)))
    return params, opt, {"losses": losses, "gnorms": gnorms, "grad": g1,
                         "change": change}


def census(params) -> dict:
    """How the parameters lie on the chips: how many (and how many bytes)
    are split evenly over every chip, and each chip's parameter bytes."""
    devs = sorted({s.device for a in params.values()
                   for s in a.addressable_shards}, key=lambda d: d.id)
    per_device = {d: 0 for d in devs}
    split = split_bytes = total = 0
    for arr in params.values():
        nbytes = arr.size * arr.dtype.itemsize
        total += nbytes
        shards = arr.addressable_shards
        for s in shards:
            per_device[s.device] += s.data.size * arr.dtype.itemsize
        if (len({s.device for s in shards}) == len(devs)
                and all(s.data.size * len(devs) == arr.size
                        for s in shards)):
            split += 1
            split_bytes += nbytes
    return {"n": len(params), "split": split, "split_bytes": split_bytes,
            "total_bytes": total,
            "per_device": [per_device[d] for d in devs]}


def run(ctx) -> dict:
    cfg, mix, m = ctx.cfg, ctx.mix, ctx.model
    opt_cfg = mix["optimizer"]
    prog = Program(cfg, m, mix, ctx.devices[:ctx.chips])
    params, opt = prog.make_state(ctx.family, m, ctx.seed)
    n_check = mix["check_steps"]
    host = [gen.train_batch(mix, m["vocab_size"], ctx.seed, i)
            for i in range(n_check + mix["pool"])]
    batches = [prog.put(b) for b in host]
    compiled = prog.compile(opt_cfg, params, opt, batches[0])
    mem = compiled.memory_analysis()
    ctx.program = prog
    step = ctx.planted(compiled)
    params, opt, first = first_steps(prog, step, params, opt,
                                     batches[:n_check], opt_cfg, ctx.family,
                                     m, ctx.seed)
    where = census(params)
    ctx.setup_done()

    # the window, as drive_train.py's: steps dispatched up to IN_FLIGHT
    # ahead of the one the host waits for
    pool = batches[n_check:]
    failed = 0
    ends, flight = [], collections.deque()
    with ctx.window() as clock:
        while clock.elapsed() < ctx.seconds or flight:
            if clock.elapsed() < ctx.seconds:
                with span("dispatch"):
                    params, opt, met = step(
                        params, opt, pool[(len(ends) + len(flight))
                                          % len(pool)])
                flight.append(met)
                if len(flight) <= IN_FLIGHT:
                    continue
            with span("sync"):
                loss = float(flight.popleft()["loss"])
            ends.append(clock.elapsed())
            failed += not math.isfinite(loss)
        jax.block_until_ready((params, opt))
        window_s = clock.elapsed()
    n_steps = len(ends)
    memory = ctx.memory_peak(mem)
    del params, opt, met, step, compiled, batches, pool
    gc.collect()

    ref = reference_steps(m, opt_cfg, ctx.seed, host[:n_check], ctx,
                          prog.mesh)
    numbers = train_numbers(first, ref)
    g1, change = first["grad"], first["change"]
    leaves = {k: [g1.get(k), ref["grad"][k], change.get(k),
                  ref["change"][k]] for k in ref["grad"]}
    return {
        "attempted": n_steps, "failed": failed, "numbers": numbers,
        "memory": memory, "window_s": window_s,
        "end_to_end": {"train_tokens_per_s":
                       n_steps * mix["batch"] * mix["seq"] / window_s},
        "facts": {
            "kind": "train", "steps": n_steps, "window_s": window_s,
            "flops_per_step": ctx.family.train_step(m, mix["batch"],
                                                    mix["seq"]),
            "plan_step_s": prog.plan.cost.time,
            "plan_peak_bytes": prog.plan.cost.peak_memory,
            "compiled_bytes": compiled_bytes(mem),
        },
        "detail": {"step_ends_s": ends,
                   "losses": first["losses"], "ref_losses": ref["losses"],
                   "gnorms": first["gnorms"], "ref_gnorms": ref["gnorms"],
                   "leaves_grad_ref_change_ref": leaves,
                   "left_out": sorted(set(ref["grad"]) - set(
                       compare.moved_leaves(ref["grad"]))),
                   "plan": prog.plan.summary(),
                   "plan_modes": prog.modes(),
                   "census": where},
    }


def spread(mesh, name: str, shape) -> NamedSharding:
    """A reference tensor split over the data axis along its largest
    dimension that divides (a stacked tensor's layer axis excluded: the
    layer scan walks it); whole on every chip where none divides."""
    n = mesh.shape["data"]
    dims = range(1 if name.startswith("layers/") else 0, len(shape))
    fit = [a for a in dims if shape[a] % n == 0]
    parts = [None] * len(shape)
    if fit:
        parts[max(fit, key=lambda a: shape[a])] = "data"
    return NamedSharding(mesh, P(*parts))


def reference_steps(m: dict, opt_cfg: dict, seed: int, host: List[dict],
                    ctx, mesh, lowp=None, rows=None) -> dict:
    """`drive_train.reference_steps` with the reference's state and
    batches laid over the mesh's chips."""
    ref = ctx.family.Reference(m, lowp=lowp)
    sh = {name: spread(mesh, name, shape)
          for name, shape, _ in ctx.family.canonical_specs(m)}
    rows_sh = NamedSharding(mesh, P("data"))

    def init(key):
        return {k: v.astype(jnp.float32)
                for k, v in ctx.family.make(m, key).items()}

    master = jax.jit(init, out_shardings=sh)(weights.seed_key(seed))
    init_master = jax.jit(lambda t: jax.tree.map(jnp.copy, t),
                          out_shardings=sh)(master)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t),
                    out_shardings=sh)
    mom, var = zeros(master), zeros(master)

    def one(master, mom, var, tokens, labels, step, lr):
        loss, grads = jax.value_and_grad(ref.loss)(master, tokens, labels)
        master, mom, var, gnorm = adamw(opt_cfg, master, mom, var, grads,
                                        step, lr)
        return master, mom, var, loss, gnorm

    one = jax.jit(one, out_shardings=(sh, sh, sh, None, None),
                  donate_argnums=(0, 1, 2))
    losses, gnorms = [], []
    for i, b in enumerate(host):
        if rows is not None:
            keep = np.zeros(len(b["labels"]), bool)
            keep[rows] = True
            b = dict(b, labels=np.where(keep[:, None], b["labels"], -1))
        b = {k: jax.device_put(v, rows_sh) for k, v in b.items()}
        lr = opt_cfg["lr"] * warmup_cosine(i + 1, opt_cfg["warmup"],
                                           opt_cfg["total_steps"])
        master, mom, var, loss, gnorm = one(
            master, mom, var, b["tokens"], b["labels"],
            jnp.float32(i + 1), jnp.float32(lr))
        losses.append(float(loss))
        gnorms.append(float(gnorm))
        if i == 0:
            grad = {k: v / (1 - opt_cfg["b1"])
                    for k, v in merged_norms(mom).items()}
    change = merged_norms(master, init_master)
    return {"losses": losses, "gnorms": gnorms, "grad": grad,
            "change": change}

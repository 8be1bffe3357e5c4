"""Train cells: the planned, compiled training step of the program, fed
token batches made from the seed, timed for the window, and checked
against the float32 reference over its first steps.

One object, the compiled step with its state, is built in set-up,
driven through `check_steps` steps on batches that all differ, and
handed on to the window as it is. The reference then repeats those
steps from the same weights and batches, once the program's state is
freed.
"""
from __future__ import annotations

import collections
import gc
import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare, gen, weights
from chipbench.reference.dense import adamw, warmup_cosine
from chipbench.trace import span

GiB = 2**30
IN_FLIGHT = 3


def _square_sums(tree, minus):
    return {k: jnp.sum(jnp.square(v.astype(jnp.float32) - (
        0.0 if minus is None else minus[k].astype(jnp.float32))))
        for k, v in tree.items()}


def merged_norms(tree: Dict[str, jax.Array],
                 minus: Dict[str, jax.Array] = None) -> Dict[str, float]:
    """Norm of each whole tensor (of `tree - minus`, where given): a
    tensor that the plan split into segments (`name@i`) is measured as
    one."""
    return merge_square_sums(jax.jit(_square_sums)(tree, minus))


def merge_square_sums(sq: Dict[str, jax.Array]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for k, v in sq.items():
        base = k.split("@")[0]
        out[base] = out.get(base, 0.0) + float(v)
    return {k: math.sqrt(v) for k, v in out.items()}


def to_program(canon: Dict[str, jax.Array], built) -> Dict[str, jax.Array]:
    """Whole tensors cut into the leaves the program's plan laid out."""
    out = {}
    for base, lay in built.pset_abstract.layouts.items():
        for seg in lay.segments:
            a = canon[base]
            if seg.key:
                a = jax.lax.slice_in_dim(a, seg.start, seg.start + seg.size,
                                         axis=lay.spec.zdp_axis)
            out[base + seg.key] = a
    return out


class Program:
    """The system under test for one train cell: plan, model, compiled
    step, and its state."""

    def __init__(self, cfg: dict, m: dict, mix: dict, devices):
        from repro.configs import (DeviceInfo, MeshConfig, ModelConfig,
                                   OSDPConfig, RunConfig, ShapeConfig,
                                   preset_for_device)
        from repro.core.plan import make_plan
        from repro.models.registry import build_model

        self.model_cfg = ModelConfig(name=cfg["name"], **m)
        shape = ShapeConfig("chipbench", mix["seq"], mix["batch"], "train")
        mesh_cfg = MeshConfig((1, 1), ("data", "model"))
        osdp = OSDPConfig(memory_limit_bytes=cfg["deployment"]
                          ["memory_limit_gib"] * GiB)
        self.run = RunConfig(model=self.model_cfg, shape=shape,
                             mesh=mesh_cfg, osdp=osdp)
        with span("plan"):
            device = DeviceInfo.preset(preset_for_device(devices[0]))
            self.plan = make_plan(self.run, device)
        self.built = build_model(self.run, self.plan, None)

    def make_state(self, family, m: dict, seed: int):
        """Weights from the seed in one jitted call, laid out as the plan
        wants them, and the optimizer's state from them."""
        from repro.optim import init_state
        params = jax.jit(lambda key: to_program(family.make(m, key),
                                                self.built))(
            weights.seed_key(seed))
        return params, jax.jit(init_state)(params)


def put(batch: dict) -> dict:
    return {k: jax.device_put(v) for k, v in batch.items()}


def run(ctx) -> dict:
    cfg, mix, m = ctx.cfg, ctx.mix, ctx.model
    opt_cfg = mix["optimizer"]
    from repro.optim import AdamWConfig
    from repro.train.loop import make_train_step

    if ctx.chips != 1:
        raise ValueError("train cells run on one chip")
    prog = Program(cfg, m, mix, ctx.devices)
    params, opt = prog.make_state(ctx.family, m, ctx.seed)
    n_check = mix["check_steps"]
    host = [gen.train_batch(mix, m["vocab_size"], ctx.seed, i)
            for i in range(n_check + mix["pool"])]
    batches = [put(b) for b in host]
    step_fn, _ = make_train_step(
        prog.built, AdamWConfig(**{k: opt_cfg[k] for k in (
            "lr", "b1", "b2", "eps", "weight_decay", "grad_clip")}),
        total_steps=opt_cfg["total_steps"], warmup=opt_cfg["warmup"])
    compiled = step_fn.lower(params, opt, batches[0]).compile()
    mem = compiled.memory_analysis()
    ctx.program = prog
    step = ctx.planted(compiled)

    # the first steps, through the window's own call and feed
    losses, gnorms = [], []
    for i in range(n_check):
        params, opt, met = step(params, opt, batches[i])
        jax.block_until_ready((params, opt, met))
        losses.append(float(met["loss"]))
        gnorms.append(float(met["grad_norm"]))
        if i == 0:
            g1 = {k: v / (1 - opt_cfg["b1"])
                  for k, v in merged_norms(opt.m).items()}
    # the weights are made again inside the program that measures the
    # change, so only scalars leave it
    change = merge_square_sums(jax.jit(lambda master, key: _square_sums(
        master, to_program(ctx.family.make(m, key), prog.built)))(
            opt.master, weights.seed_key(ctx.seed)))
    ctx.setup_done()

    # the window: steps are dispatched up to IN_FLIGHT ahead of the one
    # the host waits for, as a training loop runs them, so that a host
    # that wakes late costs the chip nothing while work is queued
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

    # the reference over the same first steps
    ref = reference_steps(m, opt_cfg, ctx.seed, host[:n_check], ctx)
    numbers = train_numbers({"losses": losses, "gnorms": gnorms,
                             "grad": g1, "change": change}, ref)
    leaves = {k: [g1.get(k), ref["grad"][k], change.get(k),
                  ref["change"][k]] for k in ref["grad"]}
    tokens = mix["batch"] * mix["seq"]
    return {
        "attempted": n_steps, "failed": failed, "numbers": numbers,
        "memory": memory, "window_s": window_s,
        "end_to_end": {"train_tokens_per_s": n_steps * tokens / window_s},
        "facts": {
            "kind": "train", "steps": n_steps, "window_s": window_s,
            "flops_per_step": ctx.family.train_step(m, mix["batch"],
                                                    mix["seq"]),
            "plan_step_s": prog.plan.cost.time,
            "plan_peak_bytes": prog.plan.cost.peak_memory,
            "compiled_bytes": compiled_bytes(mem),
        },
        "detail": {"step_ends_s": ends,
                   "losses": losses, "ref_losses": ref["losses"],
                   "gnorms": gnorms, "ref_gnorms": ref["gnorms"],
                   "leaves_grad_ref_change_ref": leaves,
                   "left_out": sorted(set(ref["grad"]) - set(
                       compare.moved_leaves(ref["grad"]))),
                   "plan": prog.plan.summary()},
    }


def train_numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers of a train cell: the losses and the pre-clip
    gradient norms of the first steps (worst step), the first gradient
    as the optimizer got it (worst tensor), and each tensor's change
    over the steps (worst of the tensors the reference moves)."""
    keep = compare.moved_leaves(ref["grad"])
    return {
        "loss": compare.rel_gap(prog["losses"], ref["losses"]),
        "grad_norm": compare.rel_gap(prog["gnorms"], ref["gnorms"]),
        "leaf_grad": compare.leaf_gap(prog["grad"], ref["grad"])[0],
        "leaf_change": compare.leaf_gap(prog["change"], ref["change"],
                                        keep)[0],
    }


def compiled_bytes(mem) -> float:
    """Per-device bytes of the compiled step: temporaries, arguments and
    outputs, less what the outputs reuse of donated arguments."""
    if mem is None:
        return math.nan
    return float(mem.temp_size_in_bytes + mem.argument_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def reference_steps(m: dict, opt_cfg: dict, seed: int, host: List[dict],
                    ctx, lowp=None, rows=None) -> dict:
    """The reference's first steps from the seed's weights: losses,
    pre-clip gradient norms, the first gradient as the optimizer gets it
    (after the clip), and each tensor's change over the steps. `rows`
    (a slice) keeps only the labels of those rows of every batch."""
    ref = ctx.family.Reference(m, lowp=lowp, eps=ctx.cfg["rms_norm_eps"])

    def init(key):
        return {k: v.astype(jnp.float32)
                for k, v in ctx.family.make(m, key).items()}

    master = jax.jit(init)(weights.seed_key(seed))
    init_master = jax.tree.map(jnp.copy, master)
    zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
    mom, var = zeros(master), zeros(master)

    @jax.jit
    def one(master, mom, var, tokens, labels, step, lr):
        loss, grads = jax.value_and_grad(ref.loss)(master, tokens, labels)
        master, mom, var, gnorm = adamw(opt_cfg, master, mom, var, grads,
                                        step, lr)
        return master, mom, var, loss, gnorm

    losses, gnorms = [], []
    for i, b in enumerate(host):
        if rows is not None:
            keep = np.zeros(len(b["labels"]), bool)
            keep[rows] = True
            b = dict(b, labels=np.where(keep[:, None], b["labels"], -1))
        b = put(b)
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

"""Bring-up smoke run of the OSDP train and serve path on TPU.

    python chip_smoke.py               # one chip: train phase, serve phase
    python chip_smoke.py --four-chips  # four chips: searched/DP/ZDP only

Runs full-width qwen1.5-0.5b (random weights from a seed) through the
entry points the launchers use:

* train: make_plan -> build_model -> make_train_step, a few steps at
  batch 2 x seq 4096; compile time and steady step time, the plan's
  predicted peak memory beside the device's measured peak, and finite
  losses starting near ln(vocab);
* serve: search_serve -> ContinuousEngine, 8 requests of 512 prompt
  tokens and 64 new tokens each; every request must finish with its
  tokens, and one request's prefill-then-decode logits must match a
  full forward;
* four chips: the same model trained for 3 steps on a (4, 1) data mesh
  under the searched plan, forced DP and forced ZDP; losses must
  agree, ZDP must split every large parameter across all 4 devices,
  and the compiled ZDP step must all-gather.

Everything runs in this one process. Without a TPU, or when any check
fails, it exits non-zero and does not print its last line, which is
otherwise one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (DeviceInfo, MeshConfig, OSDPConfig,  # noqa: E402
                           RunConfig, get_arch, get_shape,
                           preset_for_device)
from repro.core.api import search_serve  # noqa: E402
from repro.core.plan import make_plan  # noqa: E402
from repro.data.synthetic import Dataset  # noqa: E402
from repro.launch.cache import enable_compilation_cache  # noqa: E402
from repro.launch.mesh import make_mesh_from_config  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.roofline.analysis import analyze_lowered  # noqa: E402
from repro.serving.engine import (ContinuousEngine, Request,  # noqa: E402
                                  make_prefill_step, make_serve_step)
from repro.train.loop import make_train_step  # noqa: E402

ARCH = "qwen1.5-0.5b"
GiB = 2**30
# step-0 loss of a random init sits at ln(vocab); this bounds the gap
LOSS0_TOL = 0.5
# tolerances shared with the test suite: decode vs full forward
# (tests/test_arch_smoke.py) and DP/ZDP loss agreement
# (tests/test_distributed.py)
DECODE_TOL = dict(atol=0.15, rtol=0.1)
LOSS_TOL = dict(rtol=2e-2, atol=2e-2)


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_preset(dev) -> DeviceInfo:
    return DeviceInfo.preset(preset_for_device(dev))


def _memory_stats(dev) -> str:
    stats = dev.memory_stats() or {}
    keys = ("bytes_in_use", "peak_bytes_in_use", "largest_alloc_size",
            "bytes_limit", "bytes_reserved", "peak_bytes_reserved")
    return ", ".join(f"{k} {_gib(stats[k])}" for k in keys if k in stats) \
        or "not reported"


def _gib(n) -> str:
    return "not reported" if n is None else f"{n / GiB:.3f} GiB"


def train_phase(cfg, *, batch: int, seq: int, steps: int) -> dict:
    """Plan, build and train `cfg` on the first device for `steps`."""
    dev = jax.devices()[0]
    device = device_preset(dev)
    shape = dataclasses.replace(get_shape("train_4k"), seq_len=seq,
                                global_batch=batch)
    run = RunConfig(model=cfg, shape=shape,
                    mesh=MeshConfig((1, 1), ("data", "model")),
                    osdp=OSDPConfig(memory_limit_bytes=device.hbm_bytes))
    plan = make_plan(run, device)
    log(plan.summary())
    built = build_model(run, plan, None)
    step_fn, init_fn = make_train_step(built, AdamWConfig(lr=3e-4),
                                       warmup=10)
    params, opt = init_fn(jax.random.PRNGKey(0))
    ds = Dataset(cfg, shape, seed=0)
    batches = [{k: jnp.asarray(v) for k, v in ds.global_batch(s).items()}
               for s in range(steps)]
    jax.block_until_ready((params, opt, batches))

    t0 = time.perf_counter()
    compiled = step_fn.lower(params, opt, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()

    losses, step_s = [], []
    for b in batches:
        t0 = time.perf_counter()
        params, opt, metrics = compiled(params, opt, b)
        jax.block_until_ready((params, opt, metrics))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    steady = float(np.median(step_s[1:])) if steps > 1 else step_s[0]
    tok_s = batch * seq / steady

    log(f"train: {cfg.name} d_model={cfg.d_model} layers={cfg.n_layers} "
        f"vocab={cfg.vocab_size} batch={batch} seq={seq} steps={steps}")
    log(f"train: compile {compile_s:.3f} s, first step {step_s[0]:.4f} s, "
        f"steady step {steady:.4f} s (median of {max(steps - 1, 1)}), "
        f"{tok_s:.0f} tok/s")
    log(f"train: losses {['%.4f' % x for x in losses]}, "
        f"ln(vocab) {math.log(cfg.vocab_size):.4f}")
    if mem is not None:
        log(f"train: compiled temp {_gib(mem.temp_size_in_bytes)}, "
            f"arguments {_gib(mem.argument_size_in_bytes)}")
    log(f"train: plan peak memory {_gib(plan.cost.peak_memory)}; "
        f"device memory_stats {_memory_stats(dev)}")

    check(all(math.isfinite(x) for x in losses),
          f"non-finite training loss: {losses}")
    check(abs(losses[0] - math.log(cfg.vocab_size)) < LOSS0_TOL,
          f"step-0 loss {losses[0]:.4f} is not near "
          f"ln({cfg.vocab_size}) = {math.log(cfg.vocab_size):.4f}")
    return dict(losses=losses, compile_s=compile_s, step_s=steady)


def serve_phase(cfg, *, n_requests: int, prompt_len: int,
                new_tokens: int) -> dict:
    """search_serve -> ContinuousEngine over `n_requests` requests, then
    one request's decode checked against a full forward."""
    dev = jax.devices()[0]
    device = device_preset(dev)
    plan = search_serve(cfg, prompt_len=prompt_len, decode_len=new_tokens,
                        n_devices=1, memory_limit_gib=device.hbm_bytes / GiB,
                        device=device)
    log(plan.summary())
    check(plan.feasible, "serving plan infeasible")
    slots = max(1, min(plan.max_slots_per_device, n_requests))
    cache_len = prompt_len + new_tokens
    run = RunConfig(model=cfg, shape=get_shape("decode_32k"),
                    mesh=MeshConfig((1, 1), ("data", "model")),
                    osdp=OSDPConfig(enabled=True, checkpointing=False,
                                    memory_limit_bytes=device.hbm_bytes))
    built = build_model(run, plan)
    params = built.init(jax.random.PRNGKey(1))
    eng = ContinuousEngine(built, params, max_slots=slots,
                           cache_len=cache_len)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n_requests, prompt_len)).astype(np.int32)

    # warm-up: one short request compiles prefill, insert and decode
    t0 = time.perf_counter()
    eng.run([Request(-1, prompts[0], 2)])
    warm_s = time.perf_counter() - t0
    results, stats = eng.run(
        [Request(i, prompts[i], new_tokens) for i in range(n_requests)])

    ttft = sorted(r.ttft_s for r in results)
    log(f"serve: {n_requests} requests x ({prompt_len} prompt + "
        f"{new_tokens} new) on {slots} slots; warm-up (compile) "
        f"{warm_s:.3f} s")
    log(f"serve: {stats.completed} completed, {stats.useful_tokens} "
        f"tokens in {stats.wall_s:.3f} s = {stats.tokens_per_s:.1f} tok/s; "
        f"TTFT p50 {ttft[len(ttft) // 2] * 1e3:.1f} ms, "
        f"max {ttft[-1] * 1e3:.1f} ms")
    check(len(results) == n_requests and stats.completed == n_requests,
          f"{stats.completed}/{n_requests} requests completed")
    for r in results:
        check(r.ok and r.n_generated == new_tokens,
              f"request {r.rid}: status {r.status}, "
              f"{r.n_generated}/{new_tokens} tokens {r.error}")

    # prefill(prompt) + decode(first generated token) == full forward
    first = next(r for r in results if r.rid == 0)
    toks = np.concatenate([prompts[0], first.tokens[:1]])[None]
    prefill = make_prefill_step(built, cache_len)
    decode = make_serve_step(built)
    full, _ = prefill(params, {"tokens": jnp.asarray(toks)})
    _, caches = prefill(params, {"tokens": jnp.asarray(toks[:, :-1])})
    step_logits, _ = decode(params, caches, jnp.asarray(toks[:, -1:]),
                            jnp.int32(prompt_len))
    a = np.asarray(step_logits[:, 0, :cfg.vocab_size], np.float32)
    b = np.asarray(full[:, 0, :cfg.vocab_size], np.float32)
    err = float(np.max(np.abs(a - b)))
    log(f"serve: decode vs full forward max |diff| {err:.4f} "
        f"(atol {DECODE_TOL['atol']}, rtol {DECODE_TOL['rtol']})")
    try:
        np.testing.assert_allclose(a, b, **DECODE_TOL)
    except AssertionError as e:
        raise SmokeFailure(f"decode disagrees with full forward: {e}")
    return dict(completed=stats.completed, tokens_per_s=stats.tokens_per_s,
                decode_err=err)


def four_chip_phase(cfg, *, batch: int, seq: int, steps: int) -> dict:
    """Searched, DP and ZDP plans on a (4, 1) data mesh: same losses,
    ZDP parameters split 4 ways, all-gathers in the ZDP step."""
    devs = jax.devices()
    check(len(devs) == 4, f"need 4 devices, found {len(devs)}")
    device = device_preset(devs[0])
    mesh_cfg = MeshConfig((4, 1), ("data", "model"))
    mesh = make_mesh_from_config(mesh_cfg)
    shape = dataclasses.replace(get_shape("train_4k"), seq_len=seq,
                                global_batch=batch)
    ds = Dataset(cfg, shape, seed=0)
    host_batches = [ds.global_batch(s) for s in range(steps)]
    losses, zdp = {}, {}
    for label, mode in (("searched", None), ("DP", "DP"), ("ZDP", "ZDP")):
        run = RunConfig(model=cfg, shape=shape, mesh=mesh_cfg,
                        osdp=OSDPConfig(force_mode=mode,
                                        memory_limit_bytes=device.hbm_bytes))
        plan = make_plan(run, device)
        log(f"[{label}] " + plan.summary())
        built = build_model(run, plan, mesh)
        with jax.set_mesh(mesh):
            step_fn, init_fn = make_train_step(built, AdamWConfig(lr=3e-4),
                                               warmup=10)
            params, opt = init_fn(jax.random.PRNGKey(0))
            batches = [{k: jax.device_put(v, NamedSharding(
                mesh, P("data", *([None] * (v.ndim - 1)))))
                for k, v in b.items()} for b in host_batches]
            t0 = time.perf_counter()
            compiled = step_fn.lower(params, opt, batches[0]).compile()
            compile_s = time.perf_counter() - t0
            mem = compiled.memory_analysis()
            if mem is not None:
                log(f"[{label}] compiled per device: temp "
                    f"{_gib(mem.temp_size_in_bytes)}, arguments "
                    f"{_gib(mem.argument_size_in_bytes)}")
            if mode == "ZDP":
                zdp["collectives"] = analyze_lowered(compiled.as_text())
                zdp["params"] = _shard_census(params)
            out, step_s = [], []
            for b in batches:
                t0 = time.perf_counter()
                params, opt, metrics = compiled(params, opt, b)
                jax.block_until_ready((params, opt, metrics))
                step_s.append(time.perf_counter() - t0)
                out.append(float(metrics["loss"]))
        losses[label] = out
        log(f"[{label}] compile {compile_s:.3f} s, step times "
            f"{['%.4f' % s for s in step_s]} s, losses "
            f"{['%.4f' % x for x in out]}")
        del params, opt, batches, compiled

    for d in devs:
        log(f"four-chip: {d} memory_stats {_memory_stats(d)}")
    for label in ("searched", "ZDP"):
        check(all(math.isfinite(x) for x in losses[label]),
              f"non-finite {label} loss: {losses[label]}")
        try:
            np.testing.assert_allclose(losses[label], losses["DP"],
                                       **LOSS_TOL)
        except AssertionError as e:
            raise SmokeFailure(f"{label} losses differ from DP: {e}")
    census = zdp["params"]
    log(f"four-chip: ZDP params {census['split']}/{census['n']} split 4 "
        f"ways ({_gib(census['split_bytes'])} of "
        f"{_gib(census['total_bytes'])}); per-device parameter bytes "
        f"{[_gib(b) for b in census['per_device']]}")
    check(census["unsplit_large"] == [],
          f"large ZDP params not split 4 ways: {census['unsplit_large']}")
    check(census["split_bytes"] >= 0.99 * census["total_bytes"],
          f"ZDP splits only {census['split_bytes']} of "
          f"{census['total_bytes']} parameter bytes")
    coll = {k: v for k, v in zdp["collectives"].items()
            if k != "total_bytes"}
    log(f"four-chip: ZDP step collectives {coll}")
    check("all-gather" in coll, f"no all-gather in the ZDP step: {coll}")
    return dict(losses=losses, params=census, collectives=coll)


def _shard_census(params, large_bytes: int = 2**20) -> dict:
    """How each parameter lies on the devices: split when every device
    holds exactly a quarter of it."""
    devs = jax.devices()
    per_device = {d: 0 for d in devs}
    split = split_bytes = total = 0
    unsplit_large = []
    for name, arr in params.items():
        nbytes = arr.size * arr.dtype.itemsize
        total += nbytes
        shards = arr.addressable_shards
        quarter = (len({s.device for s in shards}) == len(devs)
                   and all(s.data.size * len(devs) == arr.size
                           for s in shards))
        for s in shards:
            per_device[s.device] += s.data.size * arr.dtype.itemsize
        if quarter:
            split += 1
            split_bytes += nbytes
        elif nbytes >= large_bytes:
            unsplit_large.append(name)
    return dict(n=len(params), split=split, split_bytes=split_bytes,
                total_bytes=total, unsplit_large=unsplit_large,
                per_device=[per_device[d] for d in devs])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip searched/DP/ZDP phase")
    args = ap.parse_args(argv)

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    cache_dir = enable_compilation_cache()
    log(f"device: platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devs)} preset={preset_for_device(dev)} "
        f"jax={jax.__version__} cache={cache_dir}")
    cfg = get_arch(ARCH)
    if args.four_chips:
        four_chip_phase(cfg, batch=4, seq=4096, steps=3)
    else:
        train_phase(cfg, batch=2, seq=4096, steps=5)
        serve_phase(cfg, n_requests=8, prompt_len=512, new_tokens=64)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

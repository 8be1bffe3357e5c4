"""Serving launcher: OSDP-planned continuous batching.

Default path: run the serving search (`repro.core.api.search_serve`)
for the target device / fleet, print the plan (sharding decisions +
KV-budget admission limit), build the model with the plan's decisions,
and serve a synthetic request stream through the continuous-batching
engine.  `--no-plan` restores the legacy path — a hardcoded (1,1)
mesh with OSDP disabled and the static batch engine.

    python -m repro.launch.serve --arch mamba2-2.7b --reduced \
        --prompt-len 64 --new-tokens 32 --requests 8
    python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
        --no-plan --batch 4 --prompt-len 64 --new-tokens 32

`--fleet` switches to multi-replica planning (`search_fleet`): the
cluster is partitioned into replica groups for a request-class mix
(`--classes name:prompt:decode:rate[:ttft_slo[:tpot_slo]],...`), and
with `--reduced` the plan is exercised by the deterministic traffic
simulator — one reduced-model engine per group, seeded `--arrival`
poisson traffic (or a "tick,class" CSV trace), per-class latency
percentiles in ticks:

    python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
        --fleet --n-devices 8 --memory-limit-gib 4 \
        --classes interactive:16:8:4:0.05:0.02,batch:64:32:0.5 \
        --arrival poisson --horizon 48
"""
from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from repro.configs import (DeviceInfo, MeshConfig, OSDPConfig, RunConfig,
                           get_arch, get_shape, preset_for_device, reduced)
from repro.core.api import search_serve
from repro.launch.cache import enable_compilation_cache
from repro.models.registry import build_model
from repro.serving.engine import ContinuousEngine, Engine, Request


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="static batch size (legacy / --engine static)")
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    # --- planning ----------------------------------------------------------
    ap.add_argument("--no-plan", action="store_true",
                    help="legacy path: (1,1) mesh, OSDP disabled, "
                         "static batching")
    ap.add_argument("--device", default=None, metavar="PRESET",
                    help="DeviceInfo preset to plan for "
                         "(tpu-v5e, tpu-v4, a100-80g, h100-sxm; "
                         "default: the attached device's)")
    ap.add_argument("--n-devices", type=int, default=1,
                    help="data extent the plan targets")
    ap.add_argument("--memory-limit-gib", type=float, default=16.0)
    ap.add_argument("--max-slots", type=int, default=0,
                    help="cap the admission limit (0 = searched)")
    # --- workload ----------------------------------------------------------
    ap.add_argument("--engine", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--requests", type=int, default=0,
                    help="synthetic requests to serve (0 = 2x batch)")
    ap.add_argument("--mixed", action="store_true",
                    help="mixed decode lengths (every 4th request "
                         "decodes the full --new-tokens, the rest 1/4)")
    # --- fleet -------------------------------------------------------------
    ap.add_argument("--fleet", action="store_true",
                    help="multi-replica planning (search_fleet) + "
                         "traffic simulation instead of one engine")
    ap.add_argument("--classes", default=None, metavar="SPEC",
                    help="request-class mix, comma-separated "
                         "name:prompt:decode:rate[:ttft_slo[:tpot_slo]] "
                         "(rates in requests/s at plan scale)")
    ap.add_argument("--arrival", default="poisson", metavar="KIND",
                    help="'poisson' (seeded, default) or a CSV trace "
                         "file of 'tick,class' lines")
    ap.add_argument("--horizon", type=int, default=64,
                    help="simulated traffic horizon in ticks")
    # --- hardening ---------------------------------------------------------
    ap.add_argument("--max-queue", type=int, default=-1,
                    help="queue-depth backpressure: REJECT requests "
                         "beyond max_slots + this many waiting "
                         "(-1 = unbounded)")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="retry budget for transiently-failed attempts")
    ap.add_argument("--backoff-steps", type=int, default=2,
                    help="base engine-step backoff between retries "
                         "(doubles per attempt)")
    ap.add_argument("--deadline-steps", type=int, default=0,
                    help="per-request engine-step deadline "
                         "(0 = none); expired requests end TIMED_OUT")
    args = ap.parse_args(argv)
    enable_compilation_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if not cfg.is_decoder:
        print(f"{cfg.name} is encoder-only; nothing to decode")
        return 1

    device = DeviceInfo.preset(
        args.device or preset_for_device(jax.devices()[0]))
    if args.fleet:
        return _serve_fleet(cfg, args, device)

    rng = np.random.default_rng(args.seed)
    if args.no_plan:
        return _serve_static(cfg, args, rng, plan=None)

    plan = search_serve(
        cfg, prompt_len=args.prompt_len, decode_len=args.new_tokens,
        n_devices=args.n_devices,
        memory_limit_gib=args.memory_limit_gib, device=device)
    print(plan.summary())
    if not plan.feasible:
        print("plan infeasible: no concurrency fits the memory limit "
              "(shrink the workload or add devices)")
        return 2
    if args.engine == "static":
        return _serve_static(cfg, args, rng, plan=plan)

    n_req = args.requests or 2 * args.batch
    slots = plan.max_slots_per_device
    if args.max_slots:
        slots = min(slots, args.max_slots)
    slots = max(1, min(slots, n_req))
    run = RunConfig(model=cfg, shape=get_shape("decode_32k"),
                    mesh=MeshConfig((1, 1), ("data", "model")),
                    osdp=OSDPConfig(
                        enabled=True, checkpointing=False,
                        memory_limit_bytes=args.memory_limit_gib * 2**30))
    built = build_model(run, plan)
    params = built.init(jax.random.PRNGKey(args.seed))
    eng = ContinuousEngine(built, params, max_slots=slots,
                           cache_len=args.prompt_len + args.new_tokens,
                           temperature=args.temperature,
                           max_queue=(None if args.max_queue < 0
                                      else args.max_queue),
                           max_retries=args.max_retries,
                           backoff_steps=args.backoff_steps)
    reqs = []
    for i in range(n_req):
        n_new = args.new_tokens
        if args.mixed and i % 4 != 0:
            n_new = max(1, args.new_tokens // 4)
        prompt = rng.integers(0, cfg.vocab_size,
                              args.prompt_len).astype(np.int32)
        reqs.append(Request(i, prompt, n_new,
                            deadline_steps=args.deadline_steps or None))
    results, stats = eng.run(reqs, seed=args.seed)
    print(f"served {stats.completed} requests "
          f"({stats.useful_tokens} tokens) in {stats.wall_s:.2f}s: "
          f"{stats.tokens_per_s:.1f} tok/s, {stats.prefill_steps} "
          f"prefills + {stats.decode_steps} decode steps on {slots} "
          f"slots (utilization {stats.slot_utilization:.0%})")
    if stats.terminal > stats.completed:
        print(f"  non-OK terminals: {stats.rejected} rejected, "
              f"{stats.invalid} invalid, {stats.timed_out} timed out, "
              f"{stats.failed} failed ({stats.retries} retries, "
              f"{stats.wasted_tokens} wasted tokens)")
    for r in results[:3]:
        print(f"  req {r.rid}: {r.n_generated} tokens, queue "
              f"{r.queue_wait_s * 1e3:.0f} ms, ttft "
              f"{r.ttft_s * 1e3:.0f} ms, latency "
              f"{r.latency_s * 1e3:.0f} ms")
    return 0


DEFAULT_CLASSES = "interactive:16:8:4:0.05:0.02,batch:64:32:0.5"


def _parse_classes(spec: str):
    from repro.core.cost_model import RequestClass, RequestClassMix
    classes = []
    for part in spec.split(","):
        f = part.split(":")
        if len(f) < 4:
            raise SystemExit(
                f"bad class spec {part!r} (want "
                f"name:prompt:decode:rate[:ttft_slo[:tpot_slo]])")
        kw = {}
        if len(f) > 4:
            kw["ttft_slo"] = float(f[4])
        if len(f) > 5:
            kw["tpot_slo"] = float(f[5])
        classes.append(RequestClass(f[0], int(f[1]), int(f[2]),
                                    float(f[3]), **kw))
    return RequestClassMix(tuple(classes))


def _serve_fleet(cfg, args, device: DeviceInfo) -> int:
    """Fleet path: search_fleet over the class mix, then (with
    --reduced) drive the plan with the deterministic traffic
    simulator — one reduced engine per replica group."""
    import math

    from repro.core.api import search_fleet

    mix = _parse_classes(args.classes or DEFAULT_CLASSES)
    plan = search_fleet(cfg, mix=mix, n_devices=args.n_devices,
                        memory_limit_gib=args.memory_limit_gib,
                        device=device)
    print(plan.summary())
    if not plan.feasible:
        print("fleet plan infeasible: no replica split fits the "
              "memory limit (shrink the workload or add devices)")
        return 2
    if not args.reduced:
        print("(pass --reduced to exercise the plan with simulated "
              "traffic through real engines)")
        return 0

    from repro.serving.simulator import (TrafficSimulator,
                                         fleet_replicas,
                                         poisson_arrivals,
                                         trace_arrivals)
    run = RunConfig(model=cfg, shape=get_shape("decode_32k"),
                    mesh=MeshConfig((1, 1), ("data", "model")),
                    osdp=OSDPConfig(enabled=False))
    built = build_model(run)
    params = built.init(jax.random.PRNGKey(args.seed))
    slots = args.max_slots or 4
    cache_len = mix.max_cache_len

    def make(_group):
        return ContinuousEngine(built, params, max_slots=slots,
                                cache_len=cache_len, max_queue=64,
                                temperature=args.temperature)

    replicas = fleet_replicas(plan, make, max_replicas_per_group=1)
    # the planner's 2x-occupancy admission rule at sim scale
    admission: dict = {}
    for g in plan.groups:
        sub = mix.subset(g.classes)
        for name in g.classes:
            admission[name] = admission.get(name, 0.0) \
                + 2.0 * slots * sub.slot_share(name)
    admission = {k: max(1, math.ceil(v)) for k, v in admission.items()}

    if args.arrival == "poisson":
        # normalize the plan-scale rates to ~0.5 requests/tick offered
        scale = 0.5 / mix.total_rate
        arrivals = poisson_arrivals(
            mix, horizon=args.horizon, seed=args.seed,
            rate_scale=scale, cap_scale=max(16.0, scale))
    else:
        pairs = []
        with open(args.arrival) as fh:
            for line in fh:
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                t, name = line.split(",")
                pairs.append((int(t), name.strip()))
        arrivals = trace_arrivals(pairs)

    sim = TrafficSimulator(replicas, mix, routing=plan.routing,
                           admission=admission, seed=args.seed)
    rep = sim.run(arrivals)
    print(f"simulated {len(arrivals)} arrivals over {rep.ticks} ticks "
          f"on {len(replicas)} replicas ({slots} slots each): "
          f"{rep.completed} completed, "
          f"{rep.goodput_tokens_per_tick:.2f} tok/tick")
    for name, cr in sorted(rep.per_class.items()):
        print(f"  {name}: {cr.completed}/{cr.arrived} ok "
              f"({cr.rejected} rejected), ttft p50/p99 "
              f"{cr.ttft_p50:.1f}/{cr.ttft_p99:.1f} ticks, tpot "
              f"p50/p99 {cr.tpot_p50:.2f}/{cr.tpot_p99:.2f}")
    print(f"  fingerprint {rep.fingerprint()}")
    return 0


def _serve_static(cfg, args, rng, plan=None) -> int:
    """The pre-plan engine: one batch, lockstep decode."""
    run = RunConfig(model=cfg, shape=get_shape("decode_32k"),
                    mesh=MeshConfig((1, 1), ("data", "model")),
                    osdp=(OSDPConfig(enabled=True, checkpointing=False)
                          if plan is not None
                          else OSDPConfig(enabled=False)))
    built = build_model(run, plan)
    params = built.init(jax.random.PRNGKey(args.seed))
    cache_len = (args.prompt_len + args.new_tokens
                 if plan is not None else None)
    eng = Engine(built, params, temperature=args.temperature,
                 cache_len=cache_len)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    res = eng.generate(prompts, args.new_tokens, seed=args.seed)
    print(f"prefill {args.batch}x{args.prompt_len} in {res.prefill_s:.2f}s; "
          f"decoded {args.new_tokens} tokens/seq in {res.decode_s:.2f}s "
          f"({res.tokens_per_s:.1f} tok/s)")
    print("first sequence:", res.tokens[0][:16], "...")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Plan-search explorer: run the OSDP search across all 10 assigned
architectures x memory limits and print the decision matrix — which
operators the search shards, which slices it remats
(checkpointing="selective"), and where the memory/time frontier
sits.

Run:  PYTHONPATH=src python examples/search_plans.py
"""
from repro.configs import (ARCHS, SINGLE_POD_MESH, MULTI_POD_MESH,
                           OSDPConfig, get_shape)
from repro.core import osdp, fsdp_baseline
from repro.core.cost_model import DP, count_remat_slices

shape = get_shape("train_4k")

print(f"{'arch':24s} {'limit':>6s} {'feas':>4s} {'zdp/total':>9s} "
      f"{'remat':>9s} {'mem GiB':>8s} {'t_OSDP ms':>9s} "
      f"{'t_FSDP ms':>9s} {'gain':>6s}")
for name, cfg in sorted(ARCHS.items()):
    for gib in (8, 16, 32):
        # remat searched per slice, jointly with the sharding mode
        plan = osdp(cfg, shape, SINGLE_POD_MESH, memory_limit_gib=gib,
                    checkpointing="selective")
        fsdp = fsdp_baseline(cfg, shape, SINGLE_POD_MESH)
        n_zdp = sum(1 for d in plan.decisions.values()
                    if d.uniform() != DP)
        n_remat = count_remat_slices(plan.decisions)
        n_slices = sum(len(d.remat) for d in plan.decisions.values()
                       if d.remat is not None)
        feas = plan.search.feasible if plan.search else False
        gain = (fsdp.cost.time / plan.cost.time - 1) * 100
        print(f"{name:24s} {gib:4d}G {str(feas):>4s} "
              f"{n_zdp:4d}/{len(plan.decisions):<4d} "
              f"{n_remat:4d}/{n_slices:<4d} "
              f"{plan.cost.memory / 2**30:8.1f} "
              f"{plan.cost.time * 1e3:9.1f} {fsdp.cost.time * 1e3:9.1f} "
              f"{gain:5.1f}%")

print("\nmulti-pod (2x16x16) with hierarchical ZDP_POD (beyond-paper):")
for name in ("llama3-405b", "arctic-480b"):
    cfg = ARCHS[name]
    p_flat = osdp(cfg, shape, MULTI_POD_MESH, memory_limit_gib=16)
    from repro.configs import RunConfig
    print(f"  {name}: flat-ZDP-capable plan time "
          f"{p_flat.cost.time * 1e3:.0f} ms, "
          f"mem {p_flat.cost.memory / 2**30:.1f} GiB "
          f"(modes: {sorted(set(d.uniform() or 'MIXED' for d in p_flat.decisions.values()))})")

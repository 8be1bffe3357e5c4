"""Search-engine timing (paper §3.2: 9–307 s for 98–194 operators).

Times the planner's dfs search at paper-scale per-layer granularity —
including the two largest assigned architectures, llama3-405b (885
per-layer operators) and arctic-480b (353) — plus a full n_devices=64 `search_hybrid` factorization sweep.

Results are written to ``BENCH_search.json`` at the repo root so the
planner-latency trajectory is tracked across PRs:

    {"schema": 1,
     "baseline": {case: {"seconds": ..., "solvers": {...}}},  # pre-PR2
     "current":  {case: {...}},                               # this tree
     "speedup":  {case: baseline_seconds / current_seconds}}

The ``baseline`` section is measured once against the pre-optimization
engine and committed; ``--record current`` (the default) refreshes only
the ``current`` section, so speedups always compare against the same
committed reference.  A case's ``seconds`` is the total over the
solvers it timed: the committed baseline and current totals also count
two solvers since removed, so a refreshed case total times dfs alone.  ``--quick`` runs a small case set for CI smoke
(``--check`` then fails the run if any case exceeds its generous
wall-clock ceiling).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, List, Optional

from benchmarks.paper_models import MESH_8GPU, RTX_TITAN_8
from repro.configs import SINGLE_POD_MESH, DeviceInfo, OSDPConfig, get_arch, \
    get_shape
from repro.configs.base import DENSE, ModelConfig, ShapeConfig
from repro.core.cost_model import CostEnv
from repro.core.descriptions import describe
from repro.core.search import search_hybrid, search_plan

JSON_PATH = Path(__file__).resolve().parent.parent / "BENCH_search.json"

# generous wall-clock ceilings (seconds) for --check; ~20x headroom over
# the optimized engine so CI only trips on a real regression
CEILINGS = {
    "nd-96-perlayer": 15.0,
    "selective-remat": 60.0,
    "llama3-405b": 30.0,
    "arctic-480b": 30.0,
    "hybrid-16dev": 60.0,
    "hybrid-64dev": 120.0,
}


def _gpt(name: str, layers: int, hidden: int) -> ModelConfig:
    heads = max(8, hidden // 64)
    return ModelConfig(
        name=name, family=DENSE, n_layers=layers, d_model=hidden,
        n_heads=heads, n_kv_heads=heads, d_ff=4 * hidden,
        vocab_size=50257, act="gelu", norm="layernorm", rope="none",
        tie_embeddings=True)


def _search_plan_cases(quick: bool, device: Optional[DeviceInfo] = None):
    """(name, desc, env, memory_limit_bytes, global_batch, checkpointing)
    tuples.

    The llama3-405b / arctic-480b limits sit between the all-DP and
    all-ZDP+split memory of the per-layer description, so the search
    does real work (cover search + repair) instead of short-circuiting.
    The selective-remat case times the 4-mode axis (DP/ZDP x
    remat/no-remat per slice) at per-layer granularity — the widest
    decision space the engine searches.
    """
    cases = [
        ("nd-96-perlayer", describe(get_arch("phi4-mini-3.8b"),
                                    get_shape("train_4k"), per_layer=True),
         CostEnv(RTX_TITAN_8, MESH_8GPU, checkpointing=False), 8 * 2**30,
         8, False),
        ("selective-remat", describe(get_arch("phi4-mini-3.8b"),
                                     get_shape("train_4k"),
                                     per_layer=True),
         CostEnv(RTX_TITAN_8, MESH_8GPU, checkpointing=False), 16 * 2**30,
         8, "selective"),
    ]
    dev = device or DeviceInfo()
    if not quick:
        cases += [
            ("llama3-405b", describe(get_arch("llama3-405b"),
                                     get_shape("train_4k"), per_layer=True),
             CostEnv(dev, SINGLE_POD_MESH), 240 * 2**30, 256,
             True),
            ("arctic-480b", describe(get_arch("arctic-480b"),
                                     get_shape("train_4k"), per_layer=True),
             CostEnv(dev, SINGLE_POD_MESH), 80 * 2**30, 256,
             True),
        ]
    return cases


def _run_search_plan_case(name, desc, env, lim, batch, ckpt, out) -> dict:
    osdp = OSDPConfig(search="dfs", memory_limit_bytes=lim,
                      operator_splitting=True,
                      default_slice_granularity=4,
                      checkpointing=ckpt)
    t0 = time.perf_counter()
    res = search_plan(desc, batch, env, osdp)
    dt = time.perf_counter() - t0
    out(f"{name},{desc.n_operators},dfs,{dt:.3f},"
        f"{res.cost.time * 1e3:.2f},{res.feasible},{res.nodes_visited}")
    return {"seconds": round(dt, 6), "n_operators": desc.n_operators,
            "solvers": {"dfs": {"seconds": round(dt, 6),
                                "step_time_ms": round(res.cost.time * 1e3,
                                                      3),
                                "feasible": res.feasible,
                                "nodes_visited": res.nodes_visited}}}


def _run_hybrid_case(name, desc, device, n_devices, lim, batch, out,
                     checkpointing=True) -> dict:
    osdp = OSDPConfig(search="dfs", memory_limit_bytes=lim,
                      operator_splitting=True,
                      default_slice_granularity=4,
                      allow_pod_hierarchical=False,
                      checkpointing=checkpointing)
    t0 = time.perf_counter()
    plan = search_hybrid(desc, device, n_devices, osdp,
                         batch_candidates=[batch])
    dt = time.perf_counter() - t0
    f = plan.factorization
    out(f"{name},{desc.n_operators},hybrid,{dt:.3f},"
        f"{plan.cost.time * 1e3:.2f},{plan.feasible},"
        f"dp={f.dp}/tp={f.tp}/pp={f.pp}")
    return {"seconds": round(dt, 6), "n_operators": desc.n_operators,
            "n_devices": n_devices, "feasible": plan.feasible,
            "factorization": [f.dp, f.tp, f.pp],
            "throughput_tok_s": round(plan.cost.throughput, 1),
            "swept": len(plan.swept)}


def _measure(quick: bool, out,
             device: Optional[DeviceInfo] = None) -> Dict[str, dict]:
    out("case,n_ops,solver,seconds,step_time_ms,feasible,work")
    results: Dict[str, dict] = {}
    for name, desc, env, lim, batch, ckpt in _search_plan_cases(quick,
                                                                device):
        results[name] = _run_search_plan_case(name, desc, env, lim, batch,
                                              ckpt, out)
    if quick:
        desc = describe(_gpt("nd-48x1024", 48, 1024),
                        ShapeConfig("paper_b64", 1024, 64, "train"),
                        per_layer=True)
        results["hybrid-16dev"] = _run_hybrid_case(
            "hybrid-16dev", desc, RTX_TITAN_8, 16, 16 * 2**30, 64, out,
            checkpointing=False)
    else:
        # 480B over 64 chips has a ~120 GiB/device state floor even fully
        # sharded, so the limit is set where most factorizations are live
        # (24 feasible sweep points) and the inner searches do real work.
        desc = describe(get_arch("arctic-480b"), get_shape("train_4k"),
                        per_layer=True)
        results["hybrid-64dev"] = _run_hybrid_case(
            "hybrid-64dev", desc, device or DeviceInfo(), 64,
            192 * 2**30, 64, out)
    return results


def _merge(path: Path, record: str, results: Dict[str, dict],
           quick: bool) -> dict:
    doc = {"schema": 1}
    if path.exists():
        doc = json.loads(path.read_text())
    section = doc.setdefault(record, {})
    section.update(results)
    base, cur = doc.get("baseline", {}), doc.get("current", {})
    doc["speedup"] = {
        case: round(base[case]["seconds"] / max(cur[case]["seconds"], 1e-9),
                    2)
        for case in base if case in cur}
    doc["quick"] = quick
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    return doc


def main(out=print, quick: bool = False, record: str = "current",
         check: bool = False, json_path: Optional[Path] = None,
         device: Optional[str] = None) -> dict:
    path = Path(json_path) if json_path else JSON_PATH
    results = _measure(quick, out,
                       DeviceInfo.preset(device) if device else None)
    doc = _merge(path, record, results, quick)
    out(f"# wrote {path}")
    if doc.get("speedup"):
        for case, x in sorted(doc["speedup"].items()):
            out(f"# speedup[{case}] = {x:.2f}x")
    out("# paper DFS: 9-307 s; ours is branch-and-bound exact + pruned")
    if check:
        slow = [(c, r["seconds"], CEILINGS[c]) for c, r in results.items()
                if c in CEILINGS and r["seconds"] > CEILINGS[c]]
        if slow:
            raise SystemExit(
                "perf-smoke regression: " + ", ".join(
                    f"{c} took {s:.1f}s (ceiling {lim:.0f}s)"
                    for c, s, lim in slow))
        out("# perf-smoke: all cases within ceilings")
    return doc


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small case set for CI smoke")
    ap.add_argument("--record", choices=("baseline", "current"),
                    default="current",
                    help="which BENCH_search.json section to update")
    ap.add_argument("--check", action="store_true",
                    help="fail if any case exceeds its wall-clock ceiling")
    ap.add_argument("--json", type=Path, default=None,
                    help=f"output path (default {JSON_PATH})")
    ap.add_argument("--device", default=None, metavar="PRESET",
                    help="DeviceInfo preset for the large-model cases "
                         "(tpu-v5e, tpu-v4, a100-80g, h100-sxm)")
    a = ap.parse_args()
    main(quick=a.quick, record=a.record, check=a.check, json_path=a.json,
         device=a.device)

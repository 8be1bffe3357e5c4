"""Solver optimality audit: the planner's dfs vs the exact ILP.

OSDP's claim is *optimality* of the searched plan, but dfs is an
engineered search whose bounds were asserted nowhere against ground
truth (ROADMAP item 4).  This benchmark re-solves a model-zoo x
memory-limit x batch grid with dfs and scores it against the
``search="ilp"`` oracle (``repro.core.ilp``):

    gap(dfs) = step_time(dfs) / step_time(ilp) - 1

recording per-row gaps, decision identity, and effort (nodes, seconds)
into the ``"solver_audit"`` section of ``BENCH_search.json``.

``--check`` (CI gate) asserts the audit table:

  * dfs and the ilp agree on feasibility, row by row;
  * the ilp proves optimality on every row (no time budget given);
  * dfs is *exact*: gap == 0 and decisions byte-identical to the ilp
    on every row where its node budget does not truncate — i.e. all
    legacy (2/3-mode) rows.  On selective-remat rows the 4-mode dfs is
    budget-capped by design (PR 3, 10k nodes: the unbudgeted search
    does not terminate in minutes on problems the MILP closes in
    milliseconds) and carries a real, bounded gap — the audit records
    it instead of leaving it folklore, under its ceiling;
  * dfs never beats the proven optimum (gap >= 0 up to evaluator
    repair noise).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, Optional

from benchmarks.paper_models import MESH_8GPU, RTX_TITAN_8
from repro.configs import (SINGLE_POD_MESH, DeviceInfo, OSDPConfig,
                           SOLVERS, get_arch, get_shape)
from repro.core.cost_model import CostEnv
from repro.core.descriptions import describe
from repro.core.search import search_plan

JSON_PATH = Path(__file__).resolve().parent.parent / "BENCH_search.json"

# --check ceilings on the relative step-time gap vs the ilp optimum.
# dfs: exact on legacy rows (asserted == 0 there); the selective rows
# run it budget-truncated, where the measured gap is ~2.3% — ceiling 5%.
GAP_CEILINGS = {"dfs": 0.05}
# the ilp is exact w.r.t. the search's per-slice item model, which is
# itself a (slightly optimistic) approximation of the PlanEvaluator —
# a truncated dfs's different cover can evaluate up to ~0.1% cheaper
# through the evaluator, so "nobody beats the optimum" is asserted to
# this model-vs-evaluator tolerance, not to float epsilon
EVAL_TOL = 2e-3


def _grid(quick: bool, device: Optional[DeviceInfo] = None):
    """(row_name, desc, env, limit_bytes, batch, checkpointing) rows.

    Limits sit between the all-DP and all-ZDP+split memory of each
    description so the cover solves do real work; the 8 GiB phi4 row
    and the 16 GiB selective row are the committed BENCH quick cases.
    """
    dev = device or DeviceInfo()
    phi4 = describe(get_arch("phi4-mini-3.8b"), get_shape("train_4k"),
                    per_layer=True)
    env8 = CostEnv(RTX_TITAN_8, MESH_8GPU, checkpointing=False)
    qwen = describe(get_arch("qwen1.5-0.5b"), get_shape("train_4k"))
    envq = CostEnv(dev, SINGLE_POD_MESH, checkpointing=False)
    rows = [
        # the committed BENCH quick case (8 GiB is below the fully-
        # sharded floor: an infeasible-agreement + fallback-identity row)
        ("phi4-perlayer@8g-b8", phi4, env8, 8 * 2**30, 8, False),
        # inside the [20.3, 24] GiB feasibility window: real cover work
        ("phi4-perlayer@21g-b8", phi4, env8, 21 * 2**30, 8, False),
        ("phi4-perlayer@22.5g-b8", phi4, env8,
         int(22.5 * 2**30), 8, False),
        # the committed selective quick case: the 4-mode axis, where
        # the budget-truncated dfs carries a real gap
        ("phi4-perlayer@16g-b8-sel", phi4, env8, 16 * 2**30, 8,
         "selective"),
        # qwen's window is narrow ([2.22, 2.60] GiB at b256) — two
        # frontier rows where the cover is tight
        ("qwen0.5@2.3g-b256", qwen, envq, int(2.3 * 2**30), 256, False),
        ("qwen0.5@2.45g-b256", qwen, envq, int(2.45 * 2**30), 256,
         False),
    ]
    if not quick:
        mamba = describe(get_arch("mamba2-2.7b"), get_shape("train_4k"))
        dbrx = describe(get_arch("dbrx-132b"), get_shape("train_4k"))
        env_on = CostEnv(dev, SINGLE_POD_MESH, checkpointing=True)
        rows += [
            (f"mamba2@{g}g-b256", mamba, envq, g * 2**30, 256, False)
            for g in (11, 12)
        ] + [
            ("dbrx@16g-b256", dbrx, env_on, 16 * 2**30, 256, True),
            ("phi4-perlayer@21g-b16", phi4, env8, 21 * 2**30, 16, False),
            ("phi4-perlayer@12g-b8-sel", phi4, env8, 12 * 2**30, 8,
             "selective"),
            ("llama3-perlayer@240g-b256",
             describe(get_arch("llama3-405b"), get_shape("train_4k"),
                      per_layer=True), env_on, 240 * 2**30, 256, True),
            ("arctic-perlayer@80g-b256",
             describe(get_arch("arctic-480b"), get_shape("train_4k"),
                      per_layer=True), env_on, 80 * 2**30, 256, True),
        ]
    return rows


def _run_row(name, desc, env, lim, batch, ckpt, out) -> dict:
    per: Dict[str, dict] = {}
    results = {}
    for solver in SOLVERS:
        cfg = OSDPConfig(search=solver, memory_limit_bytes=lim,
                         operator_splitting=True,
                         default_slice_granularity=4,
                         checkpointing=ckpt)
        t0 = time.perf_counter()
        res = search_plan(desc, batch, env, cfg)
        dt = time.perf_counter() - t0
        results[solver] = res
        per[solver] = {"seconds": round(dt, 6),
                       "step_time_ms": round(res.cost.time * 1e3, 3),
                       "feasible": res.feasible,
                       "nodes_visited": res.nodes_visited}
    ref = results["ilp"]
    per["ilp"]["proven_optimal"] = bool(ref.proven_optimal)
    per["ilp"]["backend"] = ref.solver_backend
    if ref.lower_bound is not None:
        per["ilp"]["cover_lower_bound"] = round(float(ref.lower_bound), 9)
    for solver in SOLVERS:
        res = results[solver]
        gap = (res.cost.time / ref.cost.time - 1.0
               if ref.feasible and res.feasible else None)
        per[solver]["gap"] = (round(gap, 9) if gap is not None else None)
        per[solver]["decisions_identical"] = \
            res.decisions == ref.decisions
        out(f"{name},{solver},{per[solver]['seconds']:.3f},"
            f"{per[solver]['step_time_ms']:.2f},{res.feasible},"
            f"{per[solver]['gap']},{per[solver]['decisions_identical']}")
    return {"selective": ckpt == "selective", "n_operators":
            desc.n_operators, "solvers": per}


def _merge(path: Path, rows: Dict[str, dict], quick: bool,
           seconds: float) -> dict:
    doc = {"schema": 1}
    if path.exists():
        doc = json.loads(path.read_text())
    worst = {
        s: max((r["solvers"][s]["gap"] or 0.0) for r in rows.values())
        for s in SOLVERS}
    doc["solver_audit"] = {
        "quick": quick,
        "seconds": round(seconds, 3),
        "rows": rows,
        "worst_gap": {s: round(g, 9) for s, g in worst.items()},
    }
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    return doc


def _check(rows: Dict[str, dict], out) -> None:
    errors = []
    for name, row in rows.items():
        per = row["solvers"]
        ref = per["ilp"]
        if not ref["proven_optimal"]:
            errors.append(f"{name}: ilp did not prove optimality")
        feas = {s: per[s]["feasible"] for s in SOLVERS}
        if len(set(feas.values())) != 1:
            errors.append(f"{name}: feasibility disagreement {feas}")
            continue
        for s in SOLVERS:
            gap = per[s]["gap"]
            if gap is None:
                continue
            if gap < -EVAL_TOL:
                errors.append(
                    f"{name}: {s} beats the proven optimum by "
                    f"{-gap:.2e} — ilp reference is broken")
            if gap > GAP_CEILINGS.get(s, 0.0):
                errors.append(
                    f"{name}: {s} gap {gap:.4%} exceeds ceiling "
                    f"{GAP_CEILINGS.get(s, 0.0):.0%}")
        # exactness: dfs (and its decisions) wherever its budget does
        # not truncate — every non-selective row
        if not row["selective"]:
            if per["dfs"]["gap"] not in (None, 0.0):
                errors.append(
                    f"{name}: dfs gap {per['dfs']['gap']} != 0 on a "
                    f"legacy row — dfs is supposed to be exact here")
            if not per["dfs"]["decisions_identical"]:
                errors.append(
                    f"{name}: ilp decisions differ from dfs on a row "
                    f"where both are exact (canonical decode broke)")
    if errors:
        raise SystemExit("solver audit failed:\n  " + "\n  ".join(errors))
    out("# solver audit: all gap/identity assertions hold")


def main(out=print, quick: bool = False, check: bool = False,
         json_path: Optional[Path] = None,
         device: Optional[str] = None) -> dict:
    path = Path(json_path) if json_path else JSON_PATH
    out("row,solver,seconds,step_time_ms,feasible,gap,decisions==ilp")
    t0 = time.perf_counter()
    rows = {}
    for name, desc, env, lim, batch, ckpt in _grid(
            quick, DeviceInfo.preset(device) if device else None):
        rows[name] = _run_row(name, desc, env, lim, batch, ckpt, out)
    doc = _merge(path, rows, quick, time.perf_counter() - t0)
    out(f"# wrote {path}")
    for s, g in sorted(doc["solver_audit"]["worst_gap"].items()):
        out(f"# worst_gap[{s}] = {g:.4%}")
    if check:
        _check(rows, out)
    return doc


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small grid for CI smoke")
    ap.add_argument("--check", action="store_true",
                    help="fail on any gap/identity assertion")
    ap.add_argument("--json", type=Path, default=None,
                    help=f"output path (default {JSON_PATH})")
    ap.add_argument("--device", default=None, metavar="PRESET",
                    help="DeviceInfo preset for the zoo rows")
    a = ap.parse_args()
    main(quick=a.quick, check=a.check, json_path=a.json, device=a.device)

"""Search engine (Algorithm 1) correctness: DFS == brute force on small
instances and == the ILP oracle past brute force's reach; pruned DFS
scales; Scheduler picks the throughput argmax; the benchmark cells'
plans are pinned."""
import importlib
import itertools
import json
import math
import pathlib
import random
import sys

import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.configs import (DeviceInfo, MeshConfig, ModelConfig,
                           OSDPConfig, RunConfig, SINGLE_POD_MESH,
                           ShapeConfig, get_arch, get_shape)
from repro.core.cost_model import CostEnv, DP, ZDP
from repro.core.descriptions import describe
from repro.core.ilp import solve_ilp
from repro.core.plan import make_plan
from repro.core.search import (SliceItem, _solve_dfs, _solve_greedy,
                               schedule, search_plan)

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _mk_items(rng, n):
    items = []
    for i in range(n):
        sav = rng.uniform(1, 100)
        t = rng.uniform(0.01, 10.0)
        items.append(SliceItem(f"op{i}", 0, 1, {ZDP: sav}, {ZDP: t}))
    return items


def _brute_force(items, need):
    best_t, best = math.inf, None
    n = len(items)
    for mask in range(1 << n):
        sav = sum(items[i].savings[ZDP] for i in range(n) if mask >> i & 1)
        if sav < need:
            continue
        t = sum(items[i].extra_time[ZDP] for i in range(n) if mask >> i & 1)
        if t < best_t:
            best_t, best = t, mask
    return best_t


@pytest.mark.parametrize("seed", range(8))
def test_dfs_matches_brute_force(seed):
    rng = random.Random(seed)
    items = _mk_items(rng, 10)
    total = sum(it.savings[ZDP] for it in items)
    need = rng.uniform(0.2, 0.9) * total
    choice, _ = _solve_dfs(items, need)
    t_dfs = sum(items[i].extra_time[c] for i, c in enumerate(choice) if c)
    sav = sum(items[i].savings[c] for i, c in enumerate(choice) if c)
    assert sav >= need - 1e-9
    t_bf = _brute_force(items, need)
    assert t_dfs == pytest.approx(t_bf, rel=1e-9)


def _mk_remat_items(rng, n):
    """Sharding x remat items as the selective search builds them: ZDP
    alone, remat alone (DP+R), and both (ZDP+R, which also pays the
    remat'd 4th gather), drawn from a few per-layer signatures so the
    dfs's group collapsing has groups to collapse."""
    sigs = []
    for _ in range(max(2, n // 8)):
        s_z, t_z = rng.uniform(1, 100), rng.uniform(0.01, 10.0)
        s_r, t_r = rng.uniform(1, 100), rng.uniform(0.01, 10.0)
        sigs.append(({ZDP: s_z, "DP+R": s_r, "ZDP+R": s_z + s_r},
                     {ZDP: t_z, "DP+R": t_r,
                      "ZDP+R": t_z + t_r + rng.uniform(0.0, t_z / 3)}))
    return [SliceItem(f"op{i}", 0, 1, *rng.choice(sigs)) for i in range(n)]


def _cover(items, choice):
    sav = sum(items[i].savings[c] for i, c in enumerate(choice) if c)
    t = sum(items[i].extra_time[c] for i, c in enumerate(choice) if c)
    return sav, t


@pytest.mark.parametrize("kind", ("2mode", "4mode"))
@pytest.mark.parametrize("seed", range(8))
def test_dfs_matches_ilp_beyond_brute_force(seed, kind):
    """Brute force stops at 10 items; the ILP oracle reaches 30-60. A
    dfs that finishes under its node budget is exact; one cut off is
    never below the optimum and still covers the need."""
    rng = random.Random(300 + seed)
    n = rng.randrange(30, 61)
    items = (_mk_items(rng, n) if kind == "2mode"
             else _mk_remat_items(rng, n))
    need = rng.uniform(0.2, 0.8) * sum(max(it.savings.values())
                                       for it in items)
    budget = 20_000
    choice, nodes = _solve_dfs(items, need, budget)
    ref = solve_ilp(items, need)
    assert ref.optimal
    sav, t = _cover(items, choice)
    ref_sav, ref_t = _cover(items, ref.choice)
    assert sav >= need - 1e-9 and ref_sav >= need - 1e-9
    if nodes <= budget:
        assert t == pytest.approx(ref_t, rel=1e-9)
    else:
        assert t >= ref_t * (1 - 1e-9)


@pytest.mark.parametrize("seed", range(4))
def test_greedy_feasible(seed):
    rng = random.Random(200 + seed)
    items = _mk_items(rng, 20)
    total = sum(it.savings[ZDP] for it in items)
    need = 0.7 * total
    choice, t = _solve_greedy(items, need)
    sav = sum(items[i].savings[c] for i, c in enumerate(choice) if c)
    assert sav >= need
    assert t < math.inf


def test_dfs_scales_to_paper_operator_counts():
    """Paper: 98-194 operators, search in 9-307 s. Our branch-and-bound
    DFS must handle 200 items fast."""
    import time
    rng = random.Random(42)
    items = _mk_items(rng, 200)
    total = sum(it.savings[ZDP] for it in items)
    t0 = time.perf_counter()
    choice, nodes = _solve_dfs(items, 0.6 * total)
    dt = time.perf_counter() - t0
    sav = sum(items[i].savings[c] for i, c in enumerate(choice) if c)
    assert sav >= 0.6 * total - 1e-6
    assert dt < 30.0, f"search took {dt:.1f}s"


def test_infeasible_falls_back_to_max_sharding():
    env = CostEnv(DeviceInfo(), SINGLE_POD_MESH)
    desc = describe(get_arch("llama3-405b"), get_shape("train_4k"))
    res = search_plan(desc, 256, env,
                      OSDPConfig(memory_limit_bytes=1 * 2**30))
    assert not res.feasible
    # every decidable op must be sharded in the fallback plan
    from repro.core.cost_model import DP as DPM
    for op in desc.decidable():
        assert res.decisions[op.name].uniform() != DPM, op.name


def test_memory_limit_binds():
    """Looser limit -> no slower plan; tighter -> no smaller memory."""
    env = CostEnv(DeviceInfo(), SINGLE_POD_MESH)
    desc = describe(get_arch("phi4-mini-3.8b"), get_shape("train_4k"))
    prev_time = None
    for gib in (64, 32, 16, 8):
        res = search_plan(desc, 256, env,
                          OSDPConfig(memory_limit_bytes=gib * 2**30))
        if res.feasible:
            assert res.cost.memory <= gib * 2**30 * 1.001
            if prev_time is not None:
                assert res.cost.time >= prev_time - 1e-9
            prev_time = res.cost.time


def test_scheduler_returns_throughput_argmax():
    env = CostEnv(DeviceInfo(), SINGLE_POD_MESH)
    desc = describe(get_arch("qwen1.5-0.5b"), get_shape("train_4k"))
    res = schedule(desc, env, OSDPConfig(), max_batch=512)
    assert res.candidates, "no feasible candidates"
    best_b, best_tp = max(res.candidates, key=lambda c: c[1])
    assert res.batch_size == best_b
    assert res.cost.throughput == pytest.approx(best_tp)


def test_osdp_between_dp_and_fsdp():
    """OSDP plan: memory <= limit, and time <= all-ZDP time (never worse
    than FSDP when feasible) — the paper's core claim."""
    from repro.core import dp_baseline, fsdp_baseline, osdp
    m = get_arch("phi4-mini-3.8b")
    s = get_shape("train_4k")
    p = osdp(m, s, SINGLE_POD_MESH, memory_limit_gib=16)
    pf = fsdp_baseline(m, s, SINGLE_POD_MESH)
    pd = dp_baseline(m, s, SINGLE_POD_MESH)
    assert p.cost.memory <= 16 * 2**30 * 1.001
    assert p.cost.time <= pf.cost.time * 1.001
    assert p.cost.memory <= pd.cost.memory * 1.001


# --- the benchmark cells' own planning problems -----------------------------

# each operator's modes per slice in the plan the benchmark cells have
# compiled and measured on the chip; a change here changes what they run
_ALL_DP = {"embed.tok": ("DP",), "final_norm": ("DP",),
           "layers.attn_qkv": ("DP",) * 4, "layers.attn_out": ("DP",) * 4,
           "layers.attn_scores": ("DP",), "layers.attn_norm": ("DP",),
           "layers.ffn_w13": ("DP",) * 4, "layers.ffn_w2": ("DP",) * 4,
           "layers.ffn_norm": ("DP",)}
CELL_PLANS = {
    "qwen1.5-0.5b": _ALL_DP,
    "phi-4-mini": dict(_ALL_DP, **{
        "layers.attn_qkv": ("ZDP", "ZDP", "DP", "DP"),
        "layers.attn_out": ("ZDP", "DP", "DP", "DP"),
        "layers.ffn_w13": ("ZDP",) * 4,
        "layers.ffn_w2": ("ZDP",) * 4}),
}


def _cell_plan(config: str, search: str):
    """The plan `chipbench` makes for a train cell: its model from the
    configuration file, its traffic's batch and sequence, a (chips, 1)
    data mesh, the deployment's memory limit, priced for the v5e."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg_entry, = (c for c in bench["configs"] if c["name"] == config)
    cell, = (w for w in bench["workloads"] if w["config"] == config)
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    mix = json.loads((ROOT / "chipbench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    sys.path.insert(0, str(ROOT))
    try:
        family = importlib.import_module(
            f"chipbench.families.{cfg['family']}")
    finally:
        sys.path.pop(0)
    model = ModelConfig(name=cfg["name"], **family.program_kwargs(cfg))
    run = RunConfig(
        model=model,
        shape=ShapeConfig("chipbench", mix["seq"], mix["batch"], "train"),
        mesh=MeshConfig((cell["chips"], 1), ("data", "model")),
        osdp=OSDPConfig(memory_limit_bytes=cfg["deployment"]
                        ["memory_limit_gib"] * 2**30, search=search))
    return make_plan(run, DeviceInfo.preset("tpu-v5e"))


@pytest.mark.parametrize("config", sorted(CELL_PLANS))
def test_cell_plan_pinned(config):
    """The chip cells plan with the default search, dfs; their plans stay
    decision for decision what the chip has compiled and measured."""
    plan = _cell_plan(config, "dfs")
    assert plan.search.solver == "dfs" and plan.search.feasible
    got = {name: d.modes for name, d in plan.decisions.items()}
    assert got == CELL_PLANS[config]
    assert all(d.remat is None for d in plan.decisions.values())


@pytest.mark.parametrize("config", sorted(CELL_PLANS))
def test_cell_plan_is_optimal(config):
    """The ILP oracle, on the cell's own problem, reaches the dfs plan's
    step cost: the cell runs the optimum of the cost model."""
    dfs = _cell_plan(config, "dfs")
    ilp = _cell_plan(config, "ilp")
    assert ilp.search.proven_optimal and ilp.search.feasible
    assert dfs.cost.time == pytest.approx(ilp.cost.time, rel=1e-12)

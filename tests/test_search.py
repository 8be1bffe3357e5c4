"""Search engine (Algorithm 1) correctness: DFS == brute force == knapsack
on small instances; pruned DFS scales; Scheduler picks the throughput
argmax."""
import itertools
import math
import random

import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.configs import (DeviceInfo, SINGLE_POD_MESH, OSDPConfig,
                           get_arch, get_shape)
from repro.core.cost_model import CostEnv, DP, ZDP
from repro.core.descriptions import describe
from repro.core.search import (SliceItem, _solve_dfs, _solve_greedy,
                               _solve_knapsack, schedule, search_plan)


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


@pytest.mark.parametrize("seed", range(4))
def test_knapsack_near_optimal(seed):
    rng = random.Random(100 + seed)
    items = _mk_items(rng, 12)
    total = sum(it.savings[ZDP] for it in items)
    need = 0.5 * total
    t_bf = _brute_force(items, need)
    choice, _ = _solve_knapsack(items, need, quantum=total / 4096)
    sav = sum(items[i].savings[c] for i, c in enumerate(choice) if c)
    t = sum(items[i].extra_time[c] for i, c in enumerate(choice) if c)
    assert sav >= need * (1 - 2e-3)
    assert t <= t_bf * 1.05 + 1e-9


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

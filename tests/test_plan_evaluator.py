"""PlanEvaluator equivalence: the vectorized/incremental fast path must
return the same PlanCost (1e-9 rel) and the same argmin decisions as
the direct `plan_cost` path — for arbitrary mixed plans, for O(1) flip
sequences, and end-to-end through dfs and its ilp oracle (the
pre-optimization search_plan semantics are replicated here as the
golden reference)."""
import random

import numpy as np
import pytest

from repro.configs import (DeviceInfo, MULTI_POD_MESH, SINGLE_POD_MESH,
                           OSDPConfig, get_arch, get_shape)
from repro.core.cost_model import (DP, MODES, ZDP, CostEnv,
                                   Decision, PlanEvaluator, plan_cost,
                                   uniform_plan)
from repro.core.descriptions import describe
from repro.core.ilp import solve_ilp
from repro.core.search import (_build_items, _items_to_decisions,
                               _solve_dfs, search_plan)

MODELS = ("phi4-mini-3.8b", "dbrx-132b", "mamba2-2.7b")
ENVS = {
    "single_pod": CostEnv(DeviceInfo(), SINGLE_POD_MESH),
    "multi_pod": CostEnv(DeviceInfo(), MULTI_POD_MESH),
    "serve": CostEnv(DeviceInfo(), SINGLE_POD_MESH, train=False),
    "no_ckpt": CostEnv(DeviceInfo(), SINGLE_POD_MESH, checkpointing=False),
}


def _random_plan(desc, rng, modes):
    """Mixed split/unsplit decisions over random modes."""
    decs = {}
    for op in desc.operators:
        if not op.decidable:
            decs[op.name] = Decision(op.name, (DP,))
            continue
        g = rng.choice([1, 2, 4]) if op.splittable else 1
        decs[op.name] = Decision(
            op.name, tuple(rng.choice(modes) for _ in range(g)))
    return decs


def _assert_cost_equal(got, want, where=""):
    for f in ("memory", "peak_memory", "time", "comm_time",
              "compute_time", "throughput"):
        g, w = getattr(got, f), getattr(want, f)
        assert g == pytest.approx(w, rel=1e-9, abs=1e-12), (where, f, g, w)


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("env_name", sorted(ENVS))
def test_evaluator_matches_plan_cost_on_mixed_plans(model, env_name):
    desc = describe(get_arch(model), get_shape("train_4k"))
    env = ENVS[env_name]
    modes = ("DP", "ZDP", "ZDP_POD") if env.mesh.multi_pod \
        else ("DP", "ZDP")
    rng = random.Random(hash((model, env_name)) & 0xFFFF)
    for trial in range(5):
        decs = _random_plan(desc, rng, modes)
        for batch in (16, 256, 1024):
            want = plan_cost(desc, decs, batch, env)
            ev = PlanEvaluator.for_decisions(desc, env, decs)
            got = ev.plan_cost(ev.modes_from_decisions(decs), batch)
            _assert_cost_equal(got, want, f"{model}/{env_name}/b{batch}")


@pytest.mark.parametrize("model", MODELS)
def test_incremental_flips_match_full_evaluation(model):
    """begin() + a random flip sequence must track plan_cost exactly —
    the repair loop's O(1) delta updates cannot drift."""
    desc = describe(get_arch(model), get_shape("train_4k"))
    env = ENVS["multi_pod"]
    rng = random.Random(7)
    gran = {op.name: (4 if op.splittable else 1)
            for op in desc.decidable()}
    ev = PlanEvaluator(desc, env, gran)
    ev.begin(np.zeros(ev.n_slices, dtype=np.int8), 256)
    for step in range(200):
        j = rng.randrange(ev.n_slices)
        k = int(ev.slice_op[j])
        if not desc.operators[k].decidable:
            continue
        ev.flip(j, rng.randrange(len(MODES)))
        if step % 20 == 0:
            want = plan_cost(desc, ev.decisions(ev.current_modes), 256, env)
            _assert_cost_equal(ev.result(), want, f"{model}/step{step}")
    want = plan_cost(desc, ev.decisions(ev.current_modes), 256, env)
    _assert_cost_equal(ev.result(), want, f"{model}/final")


def test_evaluate_plan_accepts_any_plan_cost_plan():
    """The public one-call wrap must score every plan plan_cost scores —
    including split decisions on non-decidable operators."""
    from repro.core.api import evaluate_plan
    model = get_arch("phi4-mini-3.8b")
    desc = describe(model, get_shape("train_4k"))
    decs = {"final_norm": Decision("final_norm", (ZDP, ZDP))}
    want = plan_cost(desc, decs, 256, ENVS["single_pod"])
    got = evaluate_plan(model, decs, get_shape("train_4k"),
                        SINGLE_POD_MESH, global_batch=256)
    _assert_cost_equal(got, want, "evaluate_plan")


def test_all_dp_memory_matches_base_cost():
    for model in MODELS:
        desc = describe(get_arch(model), get_shape("train_4k"))
        env = ENVS["single_pod"]
        ev = PlanEvaluator(desc, env)
        for batch in (16, 256):
            want = plan_cost(desc, uniform_plan(desc, DP), batch, env)
            assert ev.all_dp_memory(batch) == pytest.approx(
                want.memory, rel=1e-9)


# --- end-to-end golden reference: the pre-optimization search_plan ----------

def _reference_search_plan(desc, global_batch, env, osdp):
    """The pre-PR2 search_plan: direct plan_cost evaluation everywhere,
    full O(slices * ops) re-evaluation per repair flip."""
    items = _build_items(desc, env, osdp)
    base = plan_cost(desc, uniform_plan(desc, DP), global_batch, env)
    need = base.memory - osdp.memory_limit_bytes
    if osdp.search == "dfs":
        choice, _ = _solve_dfs(items, need)
    else:
        choice = solve_ilp(items, need).choice
    choice = list(choice)
    decisions = _items_to_decisions(desc, items, choice)
    cost = plan_cost(desc, decisions, global_batch, env)
    if cost.memory > osdp.memory_limit_bytes:
        remaining = sorted(
            (i for i, c in enumerate(choice) if c is None),
            key=lambda i: min(items[i].extra_time[m]
                              / max(items[i].savings[m], 1e-9)
                              for m in items[i].savings))
        for i in remaining:
            it = items[i]
            choice[i] = min(it.savings,
                            key=lambda m: it.extra_time[m]
                            / max(it.savings[m], 1e-9))
            decisions = _items_to_decisions(desc, items, choice)
            cost = plan_cost(desc, decisions, global_batch, env)
            if cost.memory <= osdp.memory_limit_bytes:
                break
        if cost.memory > osdp.memory_limit_bytes:
            choice = [max(it.savings, key=it.savings.get) for it in items]
            decisions = _items_to_decisions(desc, items, choice)
            cost = plan_cost(desc, decisions, global_batch, env)
    return decisions, cost


# memory limits chosen so each (model, limit) lands in a different
# regime: comfortable, repair-triggering tight, and infeasible-fallback
CASES = [
    ("phi4-mini-3.8b", 64), ("phi4-mini-3.8b", 16), ("phi4-mini-3.8b", 1),
    ("dbrx-132b", 32), ("dbrx-132b", 12),
    ("mamba2-2.7b", 8), ("mamba2-2.7b", 2),
]


@pytest.mark.parametrize("solver", ("dfs", "ilp"))
@pytest.mark.parametrize("model,limit_gib", CASES)
def test_solvers_match_reference_path(solver, model, limit_gib):
    desc = describe(get_arch(model), get_shape("train_4k"))
    env = ENVS["single_pod"]
    osdp = OSDPConfig(search=solver,
                      memory_limit_bytes=limit_gib * 2**30,
                      operator_splitting=True,
                      default_slice_granularity=4)
    want_dec, want_cost = _reference_search_plan(desc, 256, env, osdp)
    got = search_plan(desc, 256, env, osdp)
    assert got.decisions == want_dec, (model, limit_gib, solver)
    _assert_cost_equal(got.cost, want_cost, f"{model}/{limit_gib}/{solver}")
    assert got.feasible == (want_cost.memory <= osdp.memory_limit_bytes)


@pytest.mark.parametrize("solver", ("dfs", "ilp"))
def test_solvers_match_reference_multi_pod(solver):
    """ZDP_POD adds a second mode per item — the grouped DFS and the
    ILP oracle must still mirror the reference exactly."""
    desc = describe(get_arch("dbrx-132b"), get_shape("train_4k"))
    env = ENVS["multi_pod"]
    osdp = OSDPConfig(search=solver, memory_limit_bytes=24 * 2**30,
                      operator_splitting=True,
                      default_slice_granularity=4)
    want_dec, want_cost = _reference_search_plan(desc, 256, env, osdp)
    got = search_plan(desc, 256, env, osdp)
    assert got.decisions == want_dec
    _assert_cost_equal(got.cost, want_cost, f"multi_pod/{solver}")


def test_grouped_dfs_exact_with_duplicate_items():
    """Per-layer descriptions collapse into signature groups — the
    grouped branch-and-bound must still match brute force."""
    import itertools
    import math
    from repro.core.search import SliceItem

    rng = random.Random(11)
    for trial in range(10):
        # few distinct signatures, many copies — like per-layer models
        sigs = [(rng.uniform(1, 50), rng.uniform(0.01, 5.0))
                for _ in range(rng.randrange(2, 4))]
        items = []
        for i in range(12):
            sav, ext = sigs[rng.randrange(len(sigs))]
            items.append(SliceItem(f"op{i}", 0, 1, {ZDP: sav}, {ZDP: ext}))
        total = sum(it.savings[ZDP] for it in items)
        need = rng.uniform(0.2, 0.95) * total
        choice, nodes = _solve_dfs(items, need)
        t = sum(items[i].extra_time[c] for i, c in enumerate(choice) if c)
        sav = sum(items[i].savings[c] for i, c in enumerate(choice) if c)
        assert sav >= need - 1e-9
        best = math.inf
        for mask in range(1 << len(items)):
            s = sum(items[i].savings[ZDP] for i in range(len(items))
                    if mask >> i & 1)
            if s < need:
                continue
            tt = sum(items[i].extra_time[ZDP] for i in range(len(items))
                     if mask >> i & 1)
            best = min(best, tt)
        assert t == pytest.approx(best, rel=1e-9), trial
        assert nodes > 0


# --- the 4-mode axis: per-slice remat (selective checkpointing) -------------

def _random_remat(rng, g):
    """Random explicit/inherit remat tuple (None = all inherit)."""
    if rng.random() < 0.3:
        return None
    return tuple(rng.choice([True, False, None]) for _ in range(g))


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("env_name", sorted(ENVS))
def test_evaluator_matches_plan_cost_with_remat_bits(model, env_name):
    """4-mode plans (sharding x explicit remat per slice) must evaluate
    identically through the table path and the direct op_cost walk."""
    desc = describe(get_arch(model), get_shape("train_4k"))
    env = ENVS[env_name]
    modes = ("DP", "ZDP", "ZDP_POD") if env.mesh.multi_pod \
        else ("DP", "ZDP")
    rng = random.Random(hash((model, env_name, "remat")) & 0xFFFF)
    for trial in range(5):
        decs = {}
        for op in desc.operators:
            g = rng.choice([1, 2, 4]) if op.splittable else 1
            decs[op.name] = Decision(
                op.name, tuple(rng.choice(modes) for _ in range(g)),
                _random_remat(rng, g))
        for batch in (16, 256, 1024):
            want = plan_cost(desc, decs, batch, env)
            ev = PlanEvaluator.for_decisions(desc, env, decs)
            got = ev.plan_cost(ev.modes_from_decisions(decs), batch)
            _assert_cost_equal(got, want,
                               f"{model}/{env_name}/b{batch}/remat")


@pytest.mark.parametrize("model", MODELS)
def test_remat_flip_deltas_match_full_evaluation(model):
    """O(1) flips across all 9 extended columns (sharding x remat
    state) must track the direct evaluation exactly."""
    from repro.core.cost_model import N_EXT
    desc = describe(get_arch(model), get_shape("train_4k"))
    env = ENVS["multi_pod"]
    rng = random.Random(23)
    gran = {op.name: (4 if op.splittable else 1)
            for op in desc.decidable()}
    ev = PlanEvaluator(desc, env, gran)
    ev.begin(np.zeros(ev.n_slices, dtype=np.int8), 256)
    for step in range(300):
        ev.flip(rng.randrange(ev.n_slices), rng.randrange(N_EXT))
        if step % 25 == 0:
            want = plan_cost(desc, ev.decisions(ev.current_modes), 256,
                             env)
            _assert_cost_equal(ev.result(), want, f"{model}/step{step}")
    want = plan_cost(desc, ev.decisions(ev.current_modes), 256, env)
    _assert_cost_equal(ev.result(), want, f"{model}/final")


def test_extended_modes_round_trip():
    from repro.core.cost_model import N_EXT
    desc = describe(get_arch("phi4-mini-3.8b"), get_shape("train_4k"))
    ev = PlanEvaluator(desc, ENVS["single_pod"],
                       {op.name: (4 if op.splittable else 1)
                        for op in desc.decidable()})
    rng = random.Random(5)
    m = np.array([rng.randrange(N_EXT) for _ in range(ev.n_slices)],
                 dtype=np.int8)
    assert (ev.modes_from_decisions(ev.decisions(m)) == m).all()


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("flag", [True, False])
def test_forced_uniform_explicit_remat_matches_legacy_flag(model, flag):
    """On stacked descriptions, a plan with explicit uniform remat bits
    must cost exactly what the legacy global CostEnv.checkpointing flag
    gives (the pre-PR Profiler), decision layout unchanged — the global
    settings stay expressible inside the 4-mode axis."""
    desc = describe(get_arch(model), get_shape("train_4k"))
    env_legacy = CostEnv(DeviceInfo(), SINGLE_POD_MESH, checkpointing=flag)
    rng = random.Random(hash((model, flag)) & 0xFFFF)
    for trial in range(5):
        legacy = _random_plan(desc, rng, ("DP", "ZDP"))
        explicit = {name: Decision(name, d.modes,
                                   (flag,) * len(d.modes))
                    for name, d in legacy.items()}
        for batch in (16, 256):
            want = plan_cost(desc, legacy, batch, env_legacy)
            # explicit bits are env-independent: evaluate them under
            # the OPPOSITE env default to prove nothing leaks through
            env_other = CostEnv(DeviceInfo(), SINGLE_POD_MESH,
                                checkpointing=not flag)
            got = plan_cost(desc, explicit, batch, env_other)
            _assert_cost_equal(got, want, f"{model}/{flag}/b{batch}")


@pytest.mark.parametrize("solver", ("dfs", "ilp"))
def test_legacy_bool_configs_decisions_unchanged(solver):
    """checkpointing=True/False searches must return remat-free
    decisions (remat inherited from the env flag), exactly as pre-PR."""
    desc = describe(get_arch("phi4-mini-3.8b"), get_shape("train_4k"))
    for flag in (True, False):
        env = CostEnv(DeviceInfo(), SINGLE_POD_MESH, checkpointing=flag)
        res = search_plan(desc, 256, env, OSDPConfig(
            search=solver, memory_limit_bytes=8 * 2**30,
            checkpointing=flag))
        assert all(d.remat is None for d in res.decisions.values())


def test_solver_effort_is_reported():
    """nodes_visited: dfs = nodes expanded, ilp = integer variables +
    branch-and-bound nodes — both populated for the bench JSON."""
    desc = describe(get_arch("phi4-mini-3.8b"), get_shape("train_4k"))
    env = ENVS["single_pod"]
    for solver in ("dfs", "ilp"):
        # 4 GiB: below the all-DP footprint, so every solver must work
        res = search_plan(desc, 256, env, OSDPConfig(
            search=solver, memory_limit_bytes=4 * 2**30))
        assert res.nodes_visited > 0, solver

"""The exact ILP backend (core/ilp.py): correctness, the anytime mode,
the SearchResult optimality certificate, and the PR-3 regression pin.

Deterministic companion to tests/test_solver_oracle.py (the hypothesis
differential suite): these run in every environment, including ones
without hypothesis.  Only the milp-backend cases skip when scipy is
absent — that skip is pinned in conftest's EXPECTED_SKIP_MODULES.
"""
import math
import random

import pytest

from repro.configs import (DeviceInfo, SINGLE_POD_MESH, OSDPConfig,
                           SOLVERS, get_arch, get_shape)
from repro.configs.base import SELECTIVE
from repro.core.cost_model import CostEnv
from repro.core.descriptions import describe
from repro.core.ilp import HAVE_SCIPY_MILP, ILP_BACKENDS, solve_ilp
from repro.core.search import (SliceItem, _solve_dfs, _solve_greedy,
                               search_plan)

MODES = ("ZDP", "ZDP+R", "DP+R")
BACKENDS = [
    pytest.param("milp", marks=pytest.mark.skipif(
        not HAVE_SCIPY_MILP, reason="scipy.optimize.milp unavailable")),
    "bnb",
]


def _mk_multi(rng, n, start=0):
    """n items with 1-3 distinct modes and continuous random costs
    (distinct ratios almost surely: unique optimum, no decode ties)."""
    items = []
    for i in range(start, start + n):
        modes = MODES[:rng.randint(1, len(MODES))]
        items.append(SliceItem(
            f"op{i}", 0, 1,
            {m: rng.uniform(1, 100) for m in modes},
            {m: rng.uniform(0.01, 10.0) for m in modes}))
    return items


def _mk_grouped(rng, n_sigs, copies):
    """copies interchangeable items per signature (per-layer stacks) —
    the grouping/decode path the real model descriptions exercise."""
    items = []
    for s in range(n_sigs):
        modes = MODES[:rng.randint(1, len(MODES))]
        sav = {m: rng.uniform(1, 100) for m in modes}
        ext = {m: rng.uniform(0.01, 10.0) for m in modes}
        for c in range(copies):
            items.append(SliceItem(f"op{s}_{c}", 0, 1, dict(sav),
                                   dict(ext)))
    return items


def _cost(items, choice):
    return sum(items[i].extra_time[c]
               for i, c in enumerate(choice) if c)


def _cover(items, choice):
    return sum(items[i].savings[c]
               for i, c in enumerate(choice) if c)


def _brute(items, need):
    """Exact reference by exhaustive enumeration (multi-mode)."""
    import itertools
    best = math.inf
    menus = [[None] + list(it.savings) for it in items]
    for combo in itertools.product(*menus):
        sav = sum(items[i].savings[c]
                  for i, c in enumerate(combo) if c)
        if sav >= need:
            best = min(best, sum(items[i].extra_time[c]
                                 for i, c in enumerate(combo) if c))
    return best


def _capacity(items):
    return sum(max(it.savings.values()) for it in items)


# --- exactness --------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", range(6))
def test_exact_vs_brute_force(seed, backend):
    rng = random.Random(seed)
    items = _mk_multi(rng, 8)
    need = rng.uniform(0.2, 0.9) * _capacity(items)
    res = solve_ilp(items, need, backend=backend)
    assert res.backend == backend
    assert res.optimal and res.gap == 0.0
    assert _cover(items, res.choice) >= need - 1e-9
    t = _cost(items, res.choice)
    assert res.objective == pytest.approx(t, rel=1e-12)
    assert res.lower_bound == pytest.approx(t, rel=1e-12)
    assert t == pytest.approx(_brute(items, need), rel=1e-9)


@pytest.mark.skipif(not HAVE_SCIPY_MILP,
                    reason="scipy.optimize.milp unavailable")
@pytest.mark.parametrize("seed", range(6))
def test_backends_agree_byte_identical(seed):
    """milp and bnb reach the same unique optimum — identical choices,
    not just equal costs (continuous costs: ties have measure zero)."""
    rng = random.Random(50 + seed)
    items = _mk_grouped(rng, 5, 5)
    need = rng.uniform(0.3, 0.8) * _capacity(items)
    a = solve_ilp(items, need, backend="milp")
    b = solve_ilp(items, need, backend="bnb")
    assert a.optimal and b.optimal
    assert a.objective == pytest.approx(b.objective, rel=1e-9)
    assert a.choice == b.choice


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("seed", range(6))
def test_matches_dfs_byte_identical(seed, backend):
    """The decode contract: grouped counts map back to per-item choices
    in the dfs's canonical order, so the decisions match _solve_dfs
    exactly wherever both are exact."""
    rng = random.Random(100 + seed)
    items = _mk_grouped(rng, 4, 6)
    rng.shuffle(items)                    # decode must survive any order
    need = rng.uniform(0.3, 0.8) * _capacity(items)
    res = solve_ilp(items, need, backend=backend)
    choice_dfs, _ = _solve_dfs(items, need)
    assert res.optimal
    assert res.objective == pytest.approx(_cost(items, choice_dfs),
                                          rel=1e-9)
    assert list(res.choice) == list(choice_dfs)


@pytest.mark.parametrize("backend", BACKENDS)
def test_trivial_and_uncoverable(backend):
    rng = random.Random(3)
    items = _mk_multi(rng, 5)
    triv = solve_ilp(items, 0.0, backend=backend)
    assert triv.optimal and triv.objective == 0.0
    assert triv.choice == [None] * 5
    # uncoverable: proven infeasible, max-saving fallback (repair
    # escalates dfs's uncovered answer to the same all-max plan)
    need = 1.5 * _capacity(items)
    res = solve_ilp(items, need, backend=backend)
    assert res.optimal and math.isinf(res.objective)
    assert math.isinf(res.gap)
    expect = [max(it.savings, key=it.savings.get) for it in items]
    assert list(res.choice) == expect
    _, t_greedy = _solve_greedy(items, need)
    assert math.isinf(t_greedy)


def test_bad_backend_rejected():
    items = _mk_multi(random.Random(0), 3)
    with pytest.raises(ValueError, match="backend"):
        solve_ilp(items, 10.0, backend="simplex")
    if not HAVE_SCIPY_MILP:
        with pytest.raises(ImportError, match="scipy"):
            solve_ilp(items, 10.0, backend="milp")


# --- anytime mode -----------------------------------------------------------

def _hard_instance():
    """An instance where the ratio-greedy incumbent is strictly
    suboptimal and the tree is deep enough that a tiny budget cannot
    close the gap (verified: unbudgeted bnb beats greedy on it)."""
    rng = random.Random(11)
    items = _mk_multi(rng, 40)
    need = 0.62 * _capacity(items)
    return items, need


def test_anytime_node_budget_returns_incumbent_and_bound():
    items, need = _hard_instance()
    exact = solve_ilp(items, need, backend="bnb")
    assert exact.optimal
    trunc = solve_ilp(items, need, backend="bnb", node_budget=3)
    assert not trunc.optimal
    assert _cover(items, trunc.choice) >= need - 1e-9
    # the incumbent is feasible but worse; the bound is admissible
    assert trunc.objective >= exact.objective - 1e-9
    assert trunc.lower_bound <= exact.objective + 1e-9
    assert trunc.lower_bound <= trunc.objective + 1e-9
    assert trunc.gap >= 0.0
    # the gap genuinely separates: greedy incumbent != optimum here
    assert trunc.objective > exact.objective * (1 + 1e-9)


def test_anytime_time_budget_returns_incumbent_and_bound():
    items, need = _hard_instance()
    trunc = solve_ilp(items, need, backend="bnb", time_budget=1e-9)
    assert not trunc.optimal
    assert _cover(items, trunc.choice) >= need - 1e-9
    assert trunc.lower_bound <= trunc.objective + 1e-9


@pytest.mark.skipif(not HAVE_SCIPY_MILP,
                    reason="scipy.optimize.milp unavailable")
def test_milp_with_time_budget_stays_feasible():
    """A generous time budget must not degrade the milp path (HiGHS
    closes these instances in milliseconds)."""
    items, need = _hard_instance()
    res = solve_ilp(items, need, backend="milp", time_budget=30.0)
    assert _cover(items, res.choice) >= need - 1e-9
    assert res.lower_bound <= res.objective + 1e-9
    ref = solve_ilp(items, need, backend="bnb")
    assert res.objective <= ref.objective * (1 + 1e-9)


# --- nodes_visited semantics (unified across solvers) -----------------------

def test_nodes_visited_monotone_in_budget():
    """One effort scalar per solver, in the backend's natural unit
    (see the SearchResult comment).  The guaranteed monotone axis is
    the solver's *budget* on one fixed instance — a truncated run is a
    prefix of the full one — not instance size (better pruning on a
    bigger instance can legitimately expand fewer nodes)."""
    items, need = _hard_instance()
    dfs_nodes = [_solve_dfs(items, need, node_budget=b)[1]
                 for b in (10, 1000, 2_000_000)]
    assert dfs_nodes == sorted(dfs_nodes)
    assert dfs_nodes[0] <= 10 + 1 and dfs_nodes[-1] > 0
    ilp_nodes = [solve_ilp(items, need, backend="bnb",
                           node_budget=b).nodes
                 for b in (3, 2_000_000)]
    assert ilp_nodes == sorted(ilp_nodes) and ilp_nodes[0] >= 1


def test_nodes_visited_short_circuit_zeros():
    """0 is a legitimate effort value: dfs's root capacity prune bails
    before exploring.  The ilp still reports its model size (>= 1) on
    the same instance."""
    items = _mk_multi(random.Random(7), 8)
    need = 1.5 * _capacity(items)
    assert _solve_dfs(items, need)[1] == 0
    assert solve_ilp(items, need, backend="bnb").nodes >= 1
    assert solve_ilp(items, 0.0).nodes >= 1


# --- the SearchResult certificate through search_plan -----------------------

QWEN_LIM = int(2.3 * 2**30)               # inside the [2.22, 2.60] window


def _qwen_search(solver, lim=QWEN_LIM):
    desc = describe(get_arch("qwen1.5-0.5b"), get_shape("train_4k"))
    env = CostEnv(DeviceInfo(), SINGLE_POD_MESH, checkpointing=False)
    return search_plan(desc, 256, env, OSDPConfig(
        search=solver, memory_limit_bytes=lim,
        operator_splitting=True, default_slice_granularity=4,
        checkpointing=False))


def test_search_plan_ilp_matches_dfs_byte_identical():
    """solver="ilp" through the full engine reproduces the dfs plan
    exactly on a real model where the dfs is exact (its node budget
    does not truncate) — the acceptance bar."""
    r_ilp = _qwen_search("ilp")
    r_dfs = _qwen_search("dfs")
    assert r_ilp.feasible and r_dfs.feasible
    assert r_ilp.decisions == r_dfs.decisions
    assert r_ilp.cost.time == r_dfs.cost.time
    # the certificate only the ilp carries
    assert r_ilp.proven_optimal is True
    assert r_ilp.solver_backend in ("milp", "bnb")
    assert r_ilp.lower_bound is not None
    assert r_ilp.lower_bound >= 0.0 and math.isfinite(r_ilp.lower_bound)
    for r in (r_dfs,):
        assert r.proven_optimal is None
        assert r.lower_bound is None
        assert r.solver_backend == ""


def test_search_plan_nodes_visited_populated_per_solver():
    """At 2.45 GiB dfs and the ilp both do real cover work."""
    for solver in SOLVERS:
        res = _qwen_search(solver, lim=int(2.45 * 2**30))
        assert res.feasible, solver
        assert res.nodes_visited >= 1, solver


def test_osdp_api_exposes_certificate():
    from repro.core import osdp
    # between the fewest bytes a plan needs (2.65 GiB, its tied head
    # counted) and all-DP's (3.06 GiB), so the limit binds
    plan = osdp(get_arch("qwen1.5-0.5b"), get_shape("train_4k"),
                SINGLE_POD_MESH, memory_limit_gib=2.8, search="ilp",
                checkpointing=False)
    assert plan.search is not None and plan.search.feasible
    assert plan.search.proven_optimal is True
    assert plan.search.solver_backend in ("milp", "bnb")


# --- the PR-3 regression pin: the truncated dfs loses dominance ---------------

def test_selective_remat_ilp_dominates_truncated_dfs():
    """The case the audit was built for (PR 3 selective checkpointing):
    on the 4-mode phi4 per-layer problem at 16 GiB the dfs runs with a
    node cap (the unbudgeted search does not terminate in minutes on a
    problem the ILP closes in milliseconds), so its plan carries a real
    gap — measured 0.069% with the head tied to the embedding (2.27%
    with the untied head).
    Pin it: the ILP must strictly dominate, and the measured gap must
    stay in its band (a collapse to 0 means the budget cap silently
    moved; a blow-up means the search regressed)."""
    from benchmarks.paper_models import MESH_8GPU, RTX_TITAN_8
    desc = describe(get_arch("phi4-mini-3.8b"), get_shape("train_4k"),
                    per_layer=True)
    env = CostEnv(RTX_TITAN_8, MESH_8GPU, checkpointing=False)
    res = {}
    for solver in SOLVERS:
        res[solver] = search_plan(desc, 8, env, OSDPConfig(
            search=solver, memory_limit_bytes=16 * 2**30,
            operator_splitting=True, default_slice_granularity=4,
            checkpointing=SELECTIVE))
        assert res[solver].feasible, solver
    t_ilp = res["ilp"].cost.time
    assert res["ilp"].proven_optimal is True
    gap = {s: res[s].cost.time / t_ilp - 1.0 for s in SOLVERS}
    # ILP strictly dominates the truncated dfs
    assert 2e-4 < gap["dfs"] < 2e-3, gap


# --- config surface ---------------------------------------------------------

def test_solver_alias_and_validation():
    # `search` is the one name; there is no `solver=` alias
    assert OSDPConfig(search="ilp").search == "ilp"
    with pytest.raises(TypeError, match="solver"):
        OSDPConfig(solver="ilp")
    for name in ("simplex", "greedy"):
        with pytest.raises(ValueError, match="search"):
            OSDPConfig(search=name)
    with pytest.raises(ValueError, match="ilp_backend"):
        OSDPConfig(ilp_backend="cplex")
    with pytest.raises(ValueError, match="ilp_time_budget_s"):
        OSDPConfig(ilp_time_budget_s=-1.0)
    assert set(ILP_BACKENDS) == {"auto", "milp", "bnb"}
    assert SOLVERS == ("dfs", "ilp")

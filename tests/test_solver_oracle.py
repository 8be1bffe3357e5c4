"""Differential oracle for the dfs search, its greedy incumbent and the
ILP (hypothesis).

Property-based companion to tests/test_ilp.py: random multi-mode
instances small enough to enumerate exhaustively, and the theorem each
is supposed to satisfy:

  * ilp (both backends) == brute force == dfs, to 1e-9;
  * the ratio greedy (dfs's incumbent) never beats the optimum, and on
    single-mode instances its overshoot is bounded by its final pick
    (the ratio-prefix theorem: the prefix minus the last item is the
    cheapest fractional cover of its own coverage, which undershoots
    the need — so greedy <= OPT + ext of the last item taken);
  * uncoverable instances are detected by dfs, its incumbent and the
    ilp, with the byte-identical fallback on single-mode instances
    (multi-mode fallbacks differ; search_plan's repair escalates all
    of them to the same all-max plan — asserted by the audit's
    decisions_identical column on the committed infeasible rows).
"""
import itertools
import math

import hypothesis.strategies as st
import pytest
from hypothesis import example, given, settings

from repro.core.ilp import HAVE_SCIPY_MILP, solve_ilp
from repro.core.search import SliceItem, _solve_dfs, _solve_greedy

MODES = ("ZDP", "ZDP+R", "DP+R")


@st.composite
def instances(draw, max_items=7, max_modes=3,
              min_frac=0.05, max_frac=1.3):
    n = draw(st.integers(1, max_items))
    items = []
    for i in range(n):
        modes = MODES[:draw(st.integers(1, max_modes))]
        sav = {m: draw(st.floats(1.0, 100.0)) for m in modes}
        ext = {m: draw(st.floats(0.01, 10.0)) for m in modes}
        items.append(SliceItem(f"op{i}", 0, 1, sav, ext))
    cap = sum(max(it.savings.values()) for it in items)
    need = draw(st.floats(min_frac, max_frac)) * cap
    return items, need


def _cost(items, choice):
    return sum(items[i].extra_time[c]
               for i, c in enumerate(choice) if c)


def _cover(items, choice):
    return sum(items[i].savings[c]
               for i, c in enumerate(choice) if c)


def _brute(items, need):
    best = math.inf
    menus = [[None] + list(it.savings) for it in items]
    for combo in itertools.product(*menus):
        sav = sum(items[i].savings[c]
                  for i, c in enumerate(combo) if c)
        if sav >= need:
            best = min(best, sum(items[i].extra_time[c]
                                 for i, c in enumerate(combo) if c))
    return best


def _close(a, b):
    return math.isclose(a, b, rel_tol=1e-9, abs_tol=1e-9)


# need == the full capacity, whose float sum depends on the order the
# solver adds the savings in
_FULL_COVER = ([SliceItem("op0", 0, 1, {"ZDP": 1.0}, {"ZDP": 1.0}),
                SliceItem("op1", 0, 1, {"ZDP": 1.0}, {"ZDP": 1.0}),
                SliceItem("op2", 0, 1, {"ZDP": 1.0, "ZDP+R": 2.0},
                          {"ZDP": 0.5, "ZDP+R": 1.0}),
                SliceItem("op3", 0, 1, {"ZDP": 12.820390474699648},
                          {"ZDP": 1.0})],
               16.82039047469965)


@settings(max_examples=80, deadline=None)
@given(instances())
@example(_FULL_COVER)
def test_ilp_bnb_matches_brute_force(inst):
    items, need = inst
    ref = _brute(items, need)
    res = solve_ilp(items, need, backend="bnb")
    assert res.optimal
    if math.isinf(ref):
        assert math.isinf(res.objective)
    else:
        assert _cover(items, res.choice) >= need - 1e-9
        assert _close(_cost(items, res.choice), ref)
        assert _close(res.objective, ref)
        assert _close(res.lower_bound, ref)


@pytest.mark.skipif(not HAVE_SCIPY_MILP,
                    reason="scipy.optimize.milp unavailable")
@settings(max_examples=80, deadline=None)
@given(instances())
def test_ilp_milp_matches_brute_force(inst):
    items, need = inst
    ref = _brute(items, need)
    res = solve_ilp(items, need, backend="milp")
    assert res.optimal
    if math.isinf(ref):
        assert math.isinf(res.objective)
    else:
        assert _cover(items, res.choice) >= need - 1e-9
        assert _close(_cost(items, res.choice), ref)


@settings(max_examples=80, deadline=None)
@given(instances())
def test_dfs_matches_ilp_cost(inst):
    """The paper's solver is exact wherever its node budget does not
    truncate — always, at oracle sizes."""
    items, need = inst
    choice, _ = _solve_dfs(items, need)
    res = solve_ilp(items, need, backend="bnb")
    if math.isinf(res.objective):
        assert _cover(items, choice) < need
    else:
        assert _cover(items, choice) >= need - 1e-9
        assert _close(_cost(items, choice), res.objective)


@settings(max_examples=80, deadline=None)
@given(instances(max_modes=1))
def test_greedy_bounded_by_prefix_theorem(inst):
    items, need = inst
    ref = _brute(items, need)
    choice, t = _solve_greedy(items, need)
    if math.isinf(ref):
        assert math.isinf(t)
        return
    assert _cover(items, choice) >= need - 1e-9
    assert t >= ref - 1e-9
    last = max((items[i].extra_time[c]
                for i, c in enumerate(choice) if c), default=0.0)
    assert t <= ref + last + 1e-9


@settings(max_examples=60, deadline=None)
@given(instances(max_modes=1, min_frac=1.01, max_frac=1.6))
def test_uncoverable_single_mode_identical_fallback(inst):
    """Single-mode uncoverable: dfs, its greedy incumbent and the ilp
    land on the same all-shard fallback, byte for byte."""
    items, need = inst
    expect = [max(it.savings, key=it.savings.get) for it in items]
    assert list(_solve_dfs(items, need)[0]) == expect
    g_choice, g_t = _solve_greedy(items, need)
    assert list(g_choice) == expect and math.isinf(g_t)
    res = solve_ilp(items, need, backend="bnb")
    assert list(res.choice) == expect
    assert res.optimal and math.isinf(res.objective)


@settings(max_examples=60, deadline=None)
@given(instances(min_frac=1.01, max_frac=1.6))
def test_uncoverable_multi_mode_detected_by_all(inst):
    """Multi-mode uncoverable: every backend signals it (coverage
    short of the need / inf objective) — the identical final plan is
    restored by search_plan's all-max escalation."""
    items, need = inst
    assert _cover(items, _solve_dfs(items, need)[0]) < need
    assert math.isinf(_solve_greedy(items, need)[1])
    res = solve_ilp(items, need, backend="bnb")
    assert res.optimal and math.isinf(res.objective)
    assert math.isinf(res.gap)

"""Selective per-slice activation checkpointing: the 4-mode axis.

Pins the ISSUE-3 acceptance properties: the selective search dominates
both global checkpointing settings at equal memory limits, at least
one model flips from infeasible(remat-off)/slower(remat-on) to
feasible-and-faster, the legacy fig9 columns stay byte-identical, and
a selective plan compiles to a matching jax.checkpoint policy.
"""
import dataclasses

import numpy as np
import pytest

from repro.configs import (DeviceInfo, SINGLE_POD_MESH, OSDPConfig,
                           get_arch, get_shape)
from repro.configs.base import SELECTIVE
from repro.core.cost_model import DP, ZDP, CostEnv, Decision
from repro.core.descriptions import describe
from repro.core.search import schedule

DEV = DeviceInfo()
ENV_ON = CostEnv(DEV, SINGLE_POD_MESH, checkpointing=True)
ENV_OFF = CostEnv(DEV, SINGLE_POD_MESH, checkpointing=False)


def _sched(desc, env, lim_gib, checkpointing, solver="dfs", batches=(256,)):
    return schedule(desc, env, OSDPConfig(
        memory_limit_bytes=lim_gib * 2**30, checkpointing=checkpointing,
        search=solver, operator_splitting=True,
        default_slice_granularity=4, allow_pod_hierarchical=False),
        batch_candidates=batches)


def _thr(res):
    return res.cost.throughput if res.feasible else 0.0


# --- dominance: selective >= max(global on, global off) ---------------------

@pytest.mark.parametrize("solver", ("dfs", "ilp"))
@pytest.mark.parametrize("model,lim_gib", [
    ("phi4-mini-3.8b", 3), ("phi4-mini-3.8b", 6), ("phi4-mini-3.8b", 12),
    ("mamba2-2.7b", 4), ("mamba2-2.7b", 10),
    ("qwen1.5-0.5b", 2), ("qwen1.5-0.5b", 8),
    ("dbrx-132b", 14),
])
def test_selective_dominates_both_global_settings(model, lim_gib, solver):
    desc = describe(get_arch(model), get_shape("train_4k"))
    t_on = _thr(_sched(desc, ENV_ON, lim_gib, True, solver))
    t_off = _thr(_sched(desc, ENV_OFF, lim_gib, False, solver))
    t_sel = _thr(_sched(desc, ENV_OFF, lim_gib, SELECTIVE, solver))
    assert t_sel >= max(t_on, t_off) * (1 - 1e-9), (
        model, lim_gib, solver, t_on, t_off, t_sel)


def test_infeasible_off_slower_on_flips_to_mixed():
    """The headline: remat-off cannot fit, remat-on merely survives,
    and the mixed plan is feasible AND strictly faster than remat-on —
    with a genuinely mixed remat assignment."""
    desc = describe(get_arch("phi4-mini-3.8b"), get_shape("train_4k"))
    off = _sched(desc, ENV_OFF, 6, False)
    on = _sched(desc, ENV_ON, 6, True)
    sel = _sched(desc, ENV_OFF, 6, SELECTIVE)
    assert not off.feasible
    assert on.feasible and sel.feasible
    assert sel.cost.throughput > on.cost.throughput * (1 + 1e-6)
    n_on = sum(sum(1 for r in (d.remat or ()) if r is True)
               for d in sel.decisions.values())
    n_off = sum(sum(1 for r in (d.remat or ()) if r is False)
                for d in sel.decisions.values())
    assert n_on > 0 and n_off > 0, "expected a genuinely mixed plan"
    assert sel.cost.memory <= 6 * 2**30 * (1 + 1e-9)


def test_selective_remat_benchmark_rows():
    """benchmarks/selective_remat.py on a reduced sweep: dominance on
    every row and at least one flip (full sweep asserts internally)."""
    from benchmarks.selective_remat import main
    rows = main(out=lambda *_: None,
                models=("mamba2-2.7b",), limits=(4, 10, 14))
    assert any(r["flip"] for r in rows)
    for r in rows:
        assert r["selective"] >= max(r["on"], r["off"]) * (1 - 1e-9), r


# --- legacy fig9 columns stay byte-identical --------------------------------

def test_fig9_legacy_row_byte_identical():
    """One pinned fig9 row (nd-48x1024 @ 8 GiB), computed with the
    exact configs benchmarks/fig9_checkpointing.py uses: the printed
    FSDP_ckpt / OSDP_ckpt / speedup fields must reproduce the pre-
    selective-remat engine's output byte for byte."""
    from benchmarks.fig9_checkpointing import BATCHES
    from benchmarks.paper_models import MESH_8GPU, RTX_TITAN_8, \
        paper_shape
    from repro.core.descriptions import describe as _describe  # noqa: F401
    from benchmarks.paper_models import nd_ws_description, _gpt
    env = CostEnv(RTX_TITAN_8, MESH_8GPU, checkpointing=True)
    desc = nd_ws_description(_gpt("nd-48x1024", 48, 1024),
                             paper_shape(8))
    lim = 8 * 2**30
    fsdp = schedule(desc, env, OSDPConfig(
        force_mode="ZDP", memory_limit_bytes=lim,
        operator_splitting=False, allow_pod_hierarchical=False,
        checkpointing=True), batch_candidates=BATCHES)
    osdp = schedule(desc, env, OSDPConfig(
        memory_limit_bytes=lim, operator_splitting=True,
        default_slice_granularity=4, allow_pod_hierarchical=False,
        checkpointing=True), batch_candidates=BATCHES)
    t_f = fsdp.cost.throughput if fsdp.feasible else 0.0
    t_o = osdp.cost.throughput if osdp.feasible else 0.0
    row = f"{t_f:.0f},{t_o:.0f},{(t_o / t_f - 1) * 100:.1f}"
    assert row == "27552,28097,2.0"   # pre-PR golden, PR 3


# --- plan -> program: the jax.checkpoint policy -----------------------------

def test_selective_plan_compiles_to_checkpoint_policy():
    import jax
    from conftest import make_batch, tiny_run
    from repro.core.plan import Plan
    from repro.models.registry import build_model
    from repro.optim import AdamWConfig
    from repro.train.loop import make_train_step

    run = tiny_run("phi4-mini-3.8b")
    run = dataclasses.replace(run, osdp=dataclasses.replace(
        run.osdp, checkpointing=SELECTIVE))
    decs = {
        "layers.ffn_w13": Decision("layers.ffn_w13", (DP, DP),
                                   (True, True)),
        "layers.ffn_w2": Decision("layers.ffn_w2", (DP,), (True,)),
        "layers.attn_qkv": Decision("layers.attn_qkv", (DP,), (False,)),
        "layers.attn_out": Decision("layers.attn_out", (ZDP, DP),
                                    (False, True)),
    }
    built = build_model(run, Plan(run, None, decs, None, None))
    # mixed plan -> a save-list policy naming the kept activations
    assert isinstance(built.model.remat, tuple)
    assert "layers/attn/wq" in built.model.remat
    assert "layers/ffn/w13" not in built.model.remat
    step_fn, init_fn = make_train_step(built, AdamWConfig(lr=1e-3),
                                       donate=False)
    params, opt = init_fn(jax.random.PRNGKey(0))
    _, _, metrics = step_fn(params, opt, make_batch(run.model, 2, 64))
    assert np.isfinite(float(metrics["loss"]))

    # uniform-keep plan -> no checkpoint at all
    keep = {k: Decision(k, d.modes, (False,) * len(d.modes))
            for k, d in decs.items()}
    assert build_model(run, Plan(run, None, keep, None, None)
                       ).model.remat is False
    # legacy plan (no explicit bits) -> the global flag
    legacy = {k: Decision(k, d.modes) for k, d in decs.items()}
    run_on = dataclasses.replace(run, osdp=dataclasses.replace(
        run.osdp, checkpointing=True))
    assert build_model(run_on, Plan(run_on, None, legacy, None, None)
                       ).model.remat is True


def test_truthy_checkpointing_keeps_legacy_remat():
    """checkpointing accepted any truthy value when it was a plain
    bool field — 1 must still mean 'global remat on', not silently
    flip to no-remat."""
    cfg = OSDPConfig(checkpointing=1)
    assert cfg.env_checkpointing is True and not cfg.selective_remat
    assert OSDPConfig(checkpointing=0).env_checkpointing is False
    assert OSDPConfig(checkpointing=SELECTIVE).env_checkpointing is False
    # ...all the way through to the compiled model and the summary
    from conftest import tiny_run
    from repro.core.plan import remat_summary
    from repro.models.registry import build_model
    run = tiny_run("qwen1.5-0.5b")
    run = dataclasses.replace(run, osdp=dataclasses.replace(
        run.osdp, checkpointing=1))
    assert build_model(run).model.remat is True
    assert remat_summary({}, run.osdp) == "global on"


def test_force_mode_rejects_selective():
    """force_mode bypasses the search, so there is no remat axis to
    decide — the combination must error loudly, not silently degrade
    to a global no-remat plan."""
    with pytest.raises(ValueError, match="force_mode"):
        OSDPConfig(checkpointing=SELECTIVE, force_mode="ZDP")


def test_misspelled_selective_rejected():
    """Any string other than "selective" would silently fall back to
    the legacy global engine — reject it instead."""
    with pytest.raises(ValueError, match="checkpointing"):
        OSDPConfig(checkpointing="Selective")


def test_plan_summary_reports_remat():
    from repro.core.api import osdp as osdp_api
    plan = osdp_api(get_arch("qwen1.5-0.5b"), get_shape("train_4k"),
                    SINGLE_POD_MESH, memory_limit_gib=2.0,
                    checkpointing=SELECTIVE)
    assert "remat" in plan.summary()
    assert plan.search is not None and plan.search.feasible

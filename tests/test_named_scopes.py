"""Every layer of the compiled train step carries its named scope.

Device-time readers split the step by the `jax.named_scope` names in
each HLO instruction's `op_name` and by the pass marks that JAX's
transforms add. These compile tiny steps on the CPU and check that
every matmul names its layer and that each pass is there to find.
"""
import jax
import pytest

from conftest import (hlo_op_names, make_batch, scopes_of, step_passes,
                      tiny_run, unscoped_matmuls)
from repro.models.registry import build_model
from repro.optim import AdamWConfig, init_state
from repro.train.loop import make_train_step

# arch -> the block scopes its layers must show
FAMILIES = {
    "qwen1.5-0.5b": ("attention/qkv", "attention/core", "attention/out",
                     "ffn"),
    "dbrx-132b": ("attention/core", "moe"),
    "mamba2-2.7b": ("ssm",),
    "hymba-1.5b": ("attention/core", "ssm", "ffn"),
}


def compiled_step_text(arch: str) -> str:
    run = tiny_run(arch)
    built = build_model(run)
    step_fn, _ = make_train_step(built, AdamWConfig(), donate=False)
    params = built.abstract_params()
    opt = jax.eval_shape(init_state, params)
    batch = make_batch(run.model, 2, 64)
    return step_fn.lower(params, opt, batch).compile().as_text()


@pytest.mark.parametrize("arch", sorted(FAMILIES))
def test_train_step_scopes(arch):
    text = compiled_step_text(arch)
    assert unscoped_matmuls(text) == []
    assert step_passes(text) == {"forward", "recompute", "backward",
                                 "optimizer"}
    named = {s for op in hlo_op_names(text).values() for s in scopes_of(op)}
    assert set(FAMILIES[arch]) | {"embed", "norm", "loss",
                                  "optimizer"} <= named


# the products only attention's own backward forms: dV, dP, dQ and dK
ATTENTION_BACKWARD = ("bkgqt,bkgqh->btkh", "bkgqh,btkh->bkgqt",
                      "bkgqt,btkh->bqkgh", "bkgqt,bqkgh->btkh")


def test_attention_backward_scopes():
    """The ops of attention's custom backward keep the `attention/core`
    scope and carry the backward pass's `transpose(` mark."""
    ops = hlo_op_names(compiled_step_text("qwen1.5-0.5b")).values()
    for product in ATTENTION_BACKWARD:
        found = [op for op in ops if f"/{product}/" in op]
        assert found, product
        for op in found:
            assert scopes_of(op) == ["attention/core"], op
            assert "transpose(" in op and "rematted_computation" not in op


def test_scopes_of_reads_transform_wrappers():
    assert scopes_of("jit(step)/transpose(jvp(loss))/dot_general") == [
        "loss"]
    assert scopes_of("jit(step)/jvp()/while/body/closed_call/attention/"
                     "core/closed_call/while/body/dot_general") == [
        "attention/core"]
    assert scopes_of("jit(step)/jvp()/while/body/closed_call/add") == []

"""The float32 reference against the program at a tiny size on the CPU:
the training loss and gradients, and the float8 control further off."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from chipbench import gen, weights  # noqa: E402
from chipbench.drive_train import merged_norms, to_program  # noqa: E402
from chipbench.reference.dense import Reference  # noqa: E402

TINY = dict(family="dense", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
            head_dim=16, d_ff=96, vocab_size=300, qkv_bias=True,
            tie_embeddings=True, act="swiglu", rope="rope",
            rope_theta=1e4)


def program(m, kind="train", B=2, S=64):
    from repro.configs import (MeshConfig, ModelConfig, OSDPConfig,
                               RunConfig, ShapeConfig)
    from repro.core.plan import make_plan
    from repro.models.registry import build_model
    cfg = ModelConfig(name="tiny", **m)
    run = RunConfig(model=cfg, shape=ShapeConfig("t", S, B, kind),
                    mesh=MeshConfig((1, 1), ("data", "model")),
                    osdp=OSDPConfig(checkpointing=kind == "train"))
    return build_model(run, make_plan(run))


@pytest.fixture(scope="module")
def setup():
    built = program(TINY)
    key = weights.seed_key(2**35 + 3)
    params = to_program(weights.make(TINY, key), built)
    ref_params = {k: v.astype(jnp.float32)
                  for k, v in weights.make(TINY, key).items()}
    batch = gen.train_batch({"batch": 2, "seq": 64}, 300, 5, 0)
    return built, params, ref_params, batch


def test_program_leaves_are_the_whole_tensors(setup):
    built, params, ref_params, _ = setup
    assert set(params) == set(ref_params)
    for k, v in built.abstract_params().items():
        assert params[k].shape == v.shape and params[k].dtype == v.dtype


def test_loss_and_gradients_match(setup):
    built, params, ref_params, batch = setup
    (loss, _), grads = jax.value_and_grad(built.model.loss_fn,
                                          has_aux=True)(params, batch)
    ref = Reference(TINY, q_block=16, ce_block=16)
    rloss, rgrads = jax.value_and_grad(ref.loss)(
        ref_params, batch["tokens"], batch["labels"])
    assert float(loss) == pytest.approx(float(rloss), rel=2e-3)
    g, rg = merged_norms(grads), merged_norms(rgrads)
    for k in rg:
        assert g[k] == pytest.approx(rg[k], rel=0.05, abs=1e-3), k


def test_lower_precision_reference_is_further_off(setup):
    _, _, ref_params, batch = setup
    exact = Reference(TINY).loss(ref_params, batch["tokens"],
                                 batch["labels"])
    low = Reference(TINY, lowp=jnp.float8_e4m3fn).loss(
        ref_params, batch["tokens"], batch["labels"])
    mid = Reference(TINY, lowp=jnp.bfloat16).loss(
        ref_params, batch["tokens"], batch["labels"])
    assert abs(float(low - exact)) > 3 * abs(float(mid - exact))

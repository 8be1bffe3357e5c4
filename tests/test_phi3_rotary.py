"""Partial rotary, LongRoPE and the RMSNorm epsilon in the program.

At their defaults (whole heads, plain RoPE, epsilon 1e-6) the model
computes bit for bit what it computed before they existed, which is
what keeps the qwen1.5-0.5b cell's program unchanged; Phi-4-mini's
zoo entry carries the published values.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, tiny_run
from repro.configs import get_arch, get_shape, reduced, supported_shapes
from repro.models import attention, transformer
from repro.models.common import apply_rope, attn_geometry
from repro.models.registry import build_model


def old_apply_rope(x, positions, theta):
    """The rotary embedding as the program computed it before partial
    rotary and LongRoPE."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / hd))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def old_norm(cfg, x, scale, bias=None):
    from repro.models.common import layernorm, rmsnorm
    if cfg.norm == "layernorm":
        return layernorm(x, scale, bias)
    return rmsnorm(x, scale)


def test_whole_head_rope_is_the_old_rope():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 4, 64),
                          jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(64), (2, 64))
    for theta in (1e4, 1e6):
        new = jax.jit(apply_rope, static_argnums=2)(x, pos, theta)
        old = jax.jit(old_apply_rope, static_argnums=2)(x, pos, theta)
        np.testing.assert_array_equal(np.asarray(new, np.float32),
                                      np.asarray(old, np.float32))


def test_default_model_is_bit_identical_to_the_old_one(monkeypatch):
    """qwen1.5-0.5b reduced, with its epsilon 1e-6 and whole-head RoPE
    given explicitly: the loss and every gradient equal, bit for bit,
    those of the model with the old rotary and norm patched in."""
    import dataclasses
    run = tiny_run("qwen1.5-0.5b")
    run = dataclasses.replace(run, model=dataclasses.replace(
        run.model, norm_eps=1e-6, rotary_fraction=1.0))
    built = build_model(run)
    params = built.init(jax.random.PRNGKey(0))
    batch = make_batch(run.model, 2, 64)

    def grads():
        return jax.jit(jax.value_and_grad(built.model.loss_fn,
                                          has_aux=True))(params, batch)

    (loss, _), g = grads()
    monkeypatch.setattr(
        attention, "rotate",
        lambda cfg, x, pos: old_apply_rope(x, pos, cfg.rope_theta))
    monkeypatch.setattr(transformer, "norm", old_norm)
    (old_loss, _), old_g = grads()
    assert float(loss) == float(old_loss)
    for k in g:
        np.testing.assert_array_equal(np.asarray(g[k], np.float32),
                                      np.asarray(old_g[k], np.float32))


def test_norm_epsilon_is_the_configs():
    import dataclasses
    run = tiny_run("qwen1.5-0.5b")
    params = build_model(run).init(jax.random.PRNGKey(0))
    x = jnp.full((1, 4, run.model.d_model), 1e-3, jnp.float32)
    scale = params["final_norm/scale"]
    out = {}
    for eps in (1e-6, 1e-2):
        cfg = dataclasses.replace(run.model, norm_eps=eps)
        out[eps] = float(transformer.norm(cfg, x, scale)[0, 0, 0])
    # x / sqrt(x^2 + eps): near 1 at 1e-6, far from it at 1e-2
    assert out[1e-6] == pytest.approx(1e-3 / math.sqrt(1e-6 + 1e-6),
                                      rel=1e-2)
    assert out[1e-2] == pytest.approx(1e-3 / math.sqrt(1e-6 + 1e-2),
                                      rel=1e-2)


def test_phi4_zoo_entry_is_the_published_config():
    cfg = get_arch("phi4-mini-3.8b")
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab_size) == (3072, 32, 24, 8, 8192, 200064)
    assert cfg.tie_embeddings and cfg.norm_eps == 1e-5
    assert cfg.rope == "longrope" and cfg.rotary_dim == 96
    assert len(cfg.rope_short_factor) == 48
    assert cfg.rope_attention_factor == pytest.approx(math.sqrt(17 / 12))
    assert "2412.08905" not in cfg.source
    # 3.84 B parameters with the head tied and the vocabulary padded
    assert cfg.param_count() == pytest.approx(3.8359e9, rel=1e-3)
    # LongRoPE past its short factors is refused, so are longer shapes
    assert supported_shapes(cfg) == ["train_4k"]
    small = reduced(cfg)
    assert len(small.rope_short_factor) == small.rotary_dim // 2


def test_longrope_refuses_positions_past_the_short_factors():
    cfg = reduced(get_arch("phi4-mini-3.8b"))
    geom = attn_geometry(cfg, 1)
    attention.init_kv_cache(cfg, geom, 1, 4096)
    with pytest.raises(ValueError, match="4097 positions"):
        attention.init_kv_cache(cfg, geom, 1, 4097)
    run = tiny_run("phi4-mini-3.8b", seq=4097, batch=1)
    built = build_model(run)
    params = jax.eval_shape(built.init, jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in make_batch(run.model, 1, 4097).items()}
    with pytest.raises(ValueError, match="4097 positions"):
        jax.eval_shape(built.model.loss_fn, params, batch)
    assert get_shape("train_4k").seq_len == 4096

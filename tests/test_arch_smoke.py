"""Per-architecture smoke tests (deliverable f).

For each of the 10 assigned architectures: instantiate the REDUCED
variant of the same family (2 layers, d_model<=512, <=4 experts) and
run one forward + one train step on CPU, asserting output shapes and
no NaNs. Decoder archs additionally run prefill + one decode step.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_batch, tiny_run
from repro.configs import ARCHS, get_arch, reduced
from repro.models.registry import build_model
from repro.optim import AdamWConfig
from repro.train.loop import make_train_step

ALL_ARCHS = sorted(ARCHS)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_reduced_config_valid(arch):
    cfg = reduced(get_arch(arch))
    assert cfg.n_layers <= 2
    assert cfg.d_model <= 512
    assert cfg.moe_experts <= 4
    cfg.validate()


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_forward_shapes_and_finite(arch):
    run = tiny_run(arch)
    built = build_model(run)
    cfg = run.model
    params = built.init(jax.random.PRNGKey(0))
    B, S = 2, 64
    batch = make_batch(cfg, B, S)
    x, aux = jax.jit(built.model.forward)(params, batch)
    assert x.shape == (B, S, cfg.d_model), (arch, x.shape)
    assert np.isfinite(np.asarray(x, np.float32)).all(), arch
    logits = built.model.logits(params, x)
    assert logits.shape == (B, S, cfg.padded_vocab)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_one_train_step(arch):
    run = tiny_run(arch)
    built = build_model(run)
    step_fn, init_fn = make_train_step(built, AdamWConfig(lr=1e-3),
                                       donate=False)
    params, opt = init_fn(jax.random.PRNGKey(0))
    batch = make_batch(run.model, 2, 64)
    p2, opt2, metrics = step_fn(params, opt, batch)
    assert np.isfinite(float(metrics["loss"])), arch
    assert np.isfinite(float(metrics["grad_norm"])), arch
    # params actually changed
    changed = any(
        not np.array_equal(np.asarray(params[k], np.float32),
                           np.asarray(p2[k], np.float32))
        for k in params)
    assert changed, f"{arch}: no parameter moved"


DECODERS = [a for a in ALL_ARCHS if ARCHS[a].is_decoder]


@pytest.mark.parametrize("arch", DECODERS)
def test_prefill_decode(arch):
    run = tiny_run(arch, shape="decode_32k")
    built = build_model(run)
    cfg = run.model
    m = built.model
    params = built.init(jax.random.PRNGKey(0))
    B, S = 2, 32
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab_size)}
    if cfg.family == "vlm":
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :, None], (B, S, 3))
    logits, caches = jax.jit(m.prefill)(params, batch)
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all(), arch
    tok = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1
                     ).astype(jnp.int32)[:, None]
    kw = {}
    if cfg.rope == "mrope":
        kw["positions3"] = jnp.full((B, 1, 3), S, jnp.int32)
    lg2, caches2 = jax.jit(m.decode_step)(params, caches, tok, jnp.int32(S),
                                          **kw)
    assert lg2.shape == (B, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(lg2, np.float32)).all(), arch


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "hymba-1.5b"])
def test_decode_matches_full_forward(arch):
    """Sub-quadratic archs: stepwise decode == full forward (recurrence
    correctness), up to bf16 noise."""
    run = tiny_run(arch, shape="decode_32k")
    built = build_model(run)
    cfg = run.model
    m = built.model
    params = built.init(jax.random.PRNGKey(0))
    B, S = 1, 24
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              cfg.vocab_size)
    logits_full, _ = jax.jit(m.prefill)(params, {"tokens": toks})
    _, caches = jax.jit(m.prefill)(params, {"tokens": toks[:, :S]})
    lg, _ = jax.jit(m.decode_step)(params, caches, toks[:, S:S + 1],
                                   jnp.int32(S))
    a = np.asarray(lg[:, 0, :cfg.vocab_size], np.float32)
    b = np.asarray(logits_full[:, 0, :cfg.vocab_size], np.float32)
    np.testing.assert_allclose(a, b, atol=0.15, rtol=0.1)


def test_encoder_only_skips():
    cfg = get_arch("hubert-xlarge")
    from repro.configs import supported_shapes
    shapes = supported_shapes(cfg)
    assert "decode_32k" not in shapes and "long_500k" not in shapes
    assert set(shapes) == {"train_4k", "prefill_32k"}


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_count_matches_description(arch):
    from repro.core.descriptions import describe, sanity_check
    from repro.configs import get_shape
    cfg = get_arch(arch)
    desc = describe(cfg, get_shape("train_4k"))
    sanity_check(desc)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_built_params_match_logical_count(arch):
    """Materialized reduced-model params == closed-form count (+ padding)."""
    run = tiny_run(arch)
    built = build_model(run)
    cfg = run.model
    params = built.init(jax.random.PRNGKey(0))
    n = sum(int(np.prod(p.shape)) for p in params.values())
    want = cfg.param_count()
    # stored count may exceed logical due to query-head padding (none on
    # the 1-way test mesh) — on tp=1 they must match exactly
    assert n == want, (arch, n, want)

"""Property-based model invariants (hypothesis)."""
import dataclasses

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.models.attention import attention_ref, flash_attention
from repro.models.common import attn_geometry
from repro.models.ssm import ssd_chunk_scan, ssd_ref
from repro.configs import get_arch


@given(seq=st.integers(8, 48), window=st.integers(1, 64),
       seed=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_window_geq_seq_equals_full(seq, window, seed):
    """SWA with window >= seq is exactly full causal attention."""
    k0 = jax.random.PRNGKey(seed)
    ks = jax.random.split(k0, 3)
    q = jax.random.normal(ks[0], (1, seq, 1, 2, 8))
    k = jax.random.normal(ks[1], (1, seq, 1, 8))
    v = jax.random.normal(ks[2], (1, seq, 1, 8))
    full = attention_ref(q, k, v, causal=True, window=0)
    win = attention_ref(q, k, v, causal=True, window=max(window, seq))
    np.testing.assert_allclose(np.asarray(win), np.asarray(full), atol=1e-6)


@given(bq=st.sampled_from([8, 16, 32]), bk=st.sampled_from([8, 16, 32]),
       seed=st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_flash_block_size_invariance(bq, bk, seed):
    """Online-softmax result independent of block sizes."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    S = 32
    q = jax.random.normal(ks[0], (1, S, 2, 2, 8))
    k = jax.random.normal(ks[1], (1, S, 2, 8))
    v = jax.random.normal(ks[2], (1, S, 2, 8))
    a = flash_attention(q, k, v, causal=True, bq=bq, bk=bk)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)


@given(chunk=st.sampled_from([4, 8, 16, 32]), seed=st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_ssd_equals_sequential_recurrence(chunk, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    B, S, nh, hd, ns = 1, 32, 2, 8, 4
    x = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    a_log = jax.random.uniform(ks[2], (nh,), minval=0.0, maxval=1.0)
    b = jax.random.normal(ks[3], (B, S, ns)) * 0.5
    c = jax.random.normal(ks[4], (B, S, ns)) * 0.5
    y, s = ssd_chunk_scan(x, dt, a_log, b, c, chunk)
    y_ref, s_ref = ssd_ref(x, dt, a_log, b, c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), atol=1e-4,
                               rtol=1e-3)


@pytest.mark.parametrize("arch,tp", [
    ("arctic-480b", 16), ("dbrx-132b", 16), ("hymba-1.5b", 16),
    ("qwen2-vl-2b", 16), ("llama3-405b", 16), ("phi4-mini-3.8b", 16),
    ("qwen1.5-0.5b", 16), ("moonshot-v1-16b-a3b", 16),
    ("hubert-xlarge", 16),
])
def test_attn_geometry_tp_divisibility(arch, tp):
    """Padded GQA geometry must reshape cleanly on the 16-way model axis
    (or fall back to replication) — the dry-run's correctness premise."""
    cfg = get_arch(arch)
    g = attn_geometry(cfg, tp)
    if g.tp:
        assert (g.n_kv * g.group_padded) % tp == 0
        assert g.q_flat % tp == 0
        assert g.group_padded >= g.group
        assert g.padded_heads <= 1.5 * cfg.n_heads
    assert g.n_kv == cfg.n_kv_heads  # kv heads never padded (replicated)


def test_padded_heads_zero_contribution():
    """Query-head padding is masked: logits identical to tp=1 build up to
    dtype noise requires multi-device; here we check the mask shape
    math — padded head outputs are zeroed before wo."""
    from repro.models.attention import _group_mask
    cfg = get_arch("arctic-480b")
    g = attn_geometry(cfg, 16)
    m = _group_mask(g, jnp.float32)
    assert m.shape == (1, g.group_padded)
    assert float(m.sum()) == g.group

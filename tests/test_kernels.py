"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=1e-3)


# --- split_matmul -----------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(64, 64, 64), (128, 256, 64),
                                   (256, 384, 128), (64, 512, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_split_matmul_sweep(m, k, n, dtype):
    kx, kw = jax.random.split(jax.random.PRNGKey(m + k + n))
    x = jax.random.normal(kx, (m, k), dtype)
    w = jax.random.normal(kw, (k, n), dtype)
    y = ops.split_matmul(x, w, bm=64, bn=64, bk=64, interpret=True)
    y_ref = ref.split_matmul_ref(x, w)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


def test_split_matmul_is_operator_splitting():
    """K-grid count == paper slice granularity: result independent of g."""
    x = jax.random.normal(jax.random.PRNGKey(0), (128, 512), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (512, 128), jnp.float32)
    outs = [np.asarray(ops.split_matmul(x, w, bk=bk, bm=128, bn=128,
                                        interpret=True))
            for bk in (512, 256, 128, 64)]  # g = 1, 2, 4, 8
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-4, rtol=1e-5)


# --- flash_attention --------------------------------------------------------

@pytest.mark.parametrize("shape", [
    # B, KV, G, S, T, hd
    (1, 1, 1, 64, 64, 32),
    (2, 2, 3, 128, 128, 32),
    (1, 4, 2, 64, 192, 64),     # cross lengths (prefill chunking)
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [0, 17])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(shape, causal, window, dtype):
    B, KV, G, S, T, hd = shape
    ks = jax.random.split(jax.random.PRNGKey(sum(shape)), 3)
    q = jax.random.normal(ks[0], (B, KV, G, S, hd), dtype) * 0.5
    k = jax.random.normal(ks[1], (B, KV, T, hd), dtype) * 0.5
    v = jax.random.normal(ks[2], (B, KV, T, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=32, bk=32, interpret=True)
    out_ref = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               **_tol(dtype))


def test_flash_matches_model_path():
    """Kernel and the model's jnp blockwise flash agree."""
    from repro.models.attention import flash_attention as jnp_flash
    B, KV, G, S, hd = 2, 2, 2, 96, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, S, KV, G, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    a = jnp_flash(q, k, v, causal=True, window=13, bq=32, bk=32)
    b = ops.flash_attention(q.transpose(0, 2, 3, 1, 4),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=True, window=13,
                            bq=32, bk=32, interpret=True
                            ).transpose(0, 3, 1, 2, 4)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                               rtol=1e-4)


# --- ssd_scan ----------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    # B, S, nh, hd, ns, chunk, bh
    (1, 32, 2, 8, 4, 8, 2),
    (2, 64, 4, 16, 8, 16, 2),
    (1, 128, 8, 32, 16, 32, 4),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(shape, dtype):
    B, S, nh, hd, ns, chunk, bh = shape
    ks = jax.random.split(jax.random.PRNGKey(sum(shape)), 5)
    x = (jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    a_log = jax.random.uniform(ks[2], (nh,), minval=0.0, maxval=1.5)
    b = (jax.random.normal(ks[3], (B, S, ns)) * 0.5).astype(dtype)
    c = (jax.random.normal(ks[4], (B, S, ns)) * 0.5).astype(dtype)
    y = ops.ssd_scan(x, dt, a_log, b, c, chunk=chunk, bh=bh, interpret=True)
    y_ref = ref.ssd_scan_ref(x, dt, a_log, b, c)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=(5e-2 if dtype == jnp.bfloat16 else 1e-4),
                               rtol=2e-2)


def test_ssd_chunk_invariance():
    """y must be independent of the chunk size (state-passing correct)."""
    B, S, nh, hd, ns = 1, 96, 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    a_log = jax.random.uniform(ks[2], (nh,), minval=0.0, maxval=1.0)
    b = jax.random.normal(ks[3], (B, S, ns)) * 0.5
    c = jax.random.normal(ks[4], (B, S, ns)) * 0.5
    outs = [np.asarray(ops.ssd_scan(x, dt, a_log, b, c, chunk=q,
                                    interpret=True))
            for q in (8, 16, 32, 96)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-4, rtol=1e-4)

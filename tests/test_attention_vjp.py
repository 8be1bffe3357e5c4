"""The blockwise attention's own backward against autodiff oracles.

`flash_attention` saves q, k, v, its float32 output and each row's
log-sum-exp, and recomputes each block's probabilities on the backward
pass. Its gradients must match `jax.grad` of the naive `attention_ref`
and, within float32 round-off, `jax.vjp` of the forward-only scan it
replaced (`_scan_attention` below, kept here as the oracle), which
visits every block pair where `flash_attention` visits only the pairs
`_live_range` schedules.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (NEG_INF, _live_range, _scores,
                                    attention_ref, flash_attention)


def _scan_attention(q, k, v, *, causal, window=0, q_offset=0, bq=512,
                    bk=1024):
    """The blockwise attention as it was before it had its own VJP: two
    nested `lax.scan`s that autodiff differentiates through."""
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    scale = 1.0 / math.sqrt(hd)
    bq, bk = min(bq, S), min(bk, T)
    Sp, Tp = -(-S // bq) * bq, -(-T // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    nq, nk = Sp // bq, Tp // bk
    qb = jnp.moveaxis(qp.reshape(B, nq, bq, KV, G, hd), 1, 0)
    kb = jnp.moveaxis(kp.reshape(B, nk, bk, KV, hd), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nk, bk, KV, hd), 1, 0)

    def q_step(_, qi_blk):
        qi, q_blk = qi_blk
        q_pos = q_offset + qi * bq + jnp.arange(bq)

        def k_step(carry, kj_blk):
            kj, k_blk, v_blk = kj_blk
            m, l, acc = carry
            k_pos = kj * bk + jnp.arange(bk)
            s = jnp.einsum("bqkgh,btkh->bkgqt", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            msk = (k_pos[None, :] < T)
            if causal:
                msk = msk & (k_pos[None, :] <= q_pos[:, None])
            if window:
                msk = msk & (q_pos[:, None] - k_pos[None, :] < window)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0), (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, jnp.moveaxis(out, 3, 1)

    _, ob = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    out = jnp.moveaxis(ob, 0, 1).reshape(B, Sp, KV, G, hd)[:, :S]
    return out.astype(q.dtype)


# name -> shapes, mask, blocks, dtype, oracles; `group` < G leaves the
# last heads of each group as padding, zeroed as `_out_proj` zeroes them
CASES = {
    "causal": dict(S=64, T=64, causal=True),
    "non_causal": dict(S=64, T=64, causal=False),
    "window": dict(S=64, T=64, causal=True, window=20),
    "q_offset": dict(S=40, T=64, causal=True, q_offset=24),
    "ragged_blocks": dict(S=50, T=50, causal=True, bq=16, bk=32),
    "ragged_non_causal": dict(S=37, T=45, causal=False, bq=16, bk=32),
    "padded_group": dict(S=64, T=64, causal=True, G=3, group=2),
    "window_offset_ragged": dict(S=45, T=61, causal=True, window=24,
                                 q_offset=16, bq=16, bk=32),
    "bf16": dict(S=64, T=64, causal=True, dtype=jnp.bfloat16),
    "bf16_ragged_window": dict(S=50, T=50, causal=True, window=20, bq=16,
                               bk=32, dtype=jnp.bfloat16),
    "checkpoint_scan": dict(S=64, T=64, causal=True, layers=2),
    "checkpoint_scan_bf16": dict(S=50, T=50, causal=True, layers=2,
                                 dtype=jnp.bfloat16),
    "old_scan_f32": dict(S=50, T=50, causal=True, window=20, G=3, group=2,
                         oracles=("scan",)),
    "old_scan_bf16": dict(S=64, T=64, causal=True, dtype=jnp.bfloat16,
                          oracles=("scan",)),
    # small blocks, so that most block pairs are dead and skipped
    "dead_pairs_causal": dict(S=256, T=256, causal=True, bq=32, bk=64,
                              oracles=("ref", "scan")),
    "dead_pairs_bq_over_bk": dict(S=192, T=192, causal=True, bq=64, bk=16,
                                  oracles=("ref", "scan")),
    "dead_pairs_window_offset_ragged": dict(
        S=150, T=200, causal=True, window=20, q_offset=50, bq=32, bk=64,
        oracles=("ref", "scan")),
    "dead_pairs_checkpoint_scan": dict(S=256, T=256, causal=True, bq=32,
                                       bk=64, layers=2,
                                       oracles=("ref", "scan")),
}


def _inputs(c, seed=0):
    B, KV, hd = 2, 2, 16
    G = c.get("G", 2)
    dtype = c.get("dtype", jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (B, c["S"], KV, G, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, c["T"], KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, c["T"], KV, hd)).astype(dtype)
    w = jax.random.normal(ks[3], q.shape, jnp.float32)
    w = w * (jnp.arange(G) < c.get("group", G))[:, None]
    return q, k, v, w


def _grads(attn, c, q, k, v, w):
    """Gradients of sum(w * attn(q, k, v)) for q, k and v; with `layers`,
    of the same attention under `jax.checkpoint` in a `lax.scan` whose
    every layer adds its output to q, as the model's layer scan does."""
    mask = dict(causal=c["causal"], window=c.get("window", 0),
                q_offset=c.get("q_offset", 0))

    def f(q, k, v):
        return attn(q, k, v, **mask)

    def loss(q, k, v):
        if "layers" not in c:
            return jnp.sum(w * f(q, k, v).astype(jnp.float32))
        body = jax.checkpoint(lambda x, _: (x + f(x, k, v), None))
        x, _ = jax.lax.scan(body, q, None, length=c["layers"])
        return jnp.sum(w * x.astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


@pytest.mark.parametrize("case", sorted(CASES))
def test_flash_attention_grads(case):
    c = CASES[case]
    blocks = dict(bq=c.get("bq", 32), bk=c.get("bk", 32))
    q, k, v, w = _inputs(c)
    got = _grads(lambda *a, **m: flash_attention(*a, **m, **blocks),
                 c, q, k, v, w)
    bf16 = c.get("dtype") == jnp.bfloat16
    for oracle in c.get("oracles", ("ref",)):
        if oracle == "scan":
            want = _grads(
                lambda *a, **m: _scan_attention(*a, **m, **blocks),
                c, q, k, v, w)
        else:
            want = _grads(attention_ref, c, q, k, v, w)
        for name, g, r in zip("qkv", got, want):
            assert g.dtype == r.dtype == q.dtype, name
            g = np.asarray(g, np.float32)
            r = np.asarray(r, np.float32)
            scale = float(np.abs(r).max())
            assert scale > 0, name
            if bf16:   # a few bf16 roundings of each gradient's terms
                tol = 2e-2 * scale
            elif oracle == "scan":   # float32 round-off
                tol = 2e-6 * scale
            else:
                tol = 2e-5 * scale
            np.testing.assert_allclose(g, r, atol=tol, rtol=0,
                                       err_msg=f"{name} against {oracle}")


def _live_pairs(*, causal, window, q_offset, S, T, bq, bk):
    """Brute force: (nq, nk) bools, a pair live iff `_scores` leaves any
    element of it unmasked between a real query row and a key column."""
    nq, nk = -(-S // bq), -(-T // bk)
    q_pos = q_offset + jnp.arange(nq * bq)
    k_pos = jnp.arange(nk * bk)
    q = jnp.zeros((1, nq * bq, 1, 1, 1))
    k = jnp.zeros((1, nk * bk, 1, 1))
    s = _scores(q, k, q_pos, k_pos, T=T, causal=causal, window=window,
                scale=1.0)
    seen = np.asarray(s[0, 0, 0] > NEG_INF)[:S]
    seen = np.pad(seen, ((0, nq * bq - S), (0, 0)))
    return seen.reshape(nq, bq, nk, bk).any(axis=(1, 3))


def _schedule(live, *, by_key, **shape):
    """(nq, nk) bools of the pairs `_live_range` schedules, seen from the
    query blocks (by_key=False) or from the key blocks."""
    n_out = live.shape[1] if by_key else live.shape[0]
    got = np.zeros(live.shape, bool)
    for o in range(n_out):
        start, stop = (int(x) for x in _live_range(o, by_key=by_key, **shape))
        if by_key:
            got[start:stop, o] = True
        else:
            got[o, start:stop] = True
    return got


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("bq,bk", [(16, 32), (32, 32), (32, 16)])
def test_live_range_matches_mask(causal, bq, bk):
    """Both directions of the schedule give exactly the pairs that hold
    an unmasked element, over windows, offsets and ragged shapes."""
    for window in (0, 5, 20, 40):
        for q_offset in (0, 24):
            for S, T in ((64, 64), (45, 61), (40, 64), (64, 40)):
                shape = dict(causal=causal, window=window, q_offset=q_offset,
                             S=S, T=T, bq=bq, bk=bk)
                live = _live_pairs(**shape)
                for by_key in (False, True):
                    np.testing.assert_array_equal(
                        _schedule(live, by_key=by_key, **shape), live,
                        err_msg=f"{shape} by_key={by_key}")


def test_live_pairs_at_cell_shapes():
    """Both train cells of the benchmark (2 x 4096 on one chip, 1 x 4096
    a chip on four) run causal attention over 4096 tokens at the default
    512 x 1024 blocks: 20 of each layer's 32 block pairs are live,
    whichever way the schedule is read."""
    shape = dict(causal=True, window=0, q_offset=0, S=4096, T=4096, bq=512,
                 bk=1024)
    live = _live_pairs(**shape)
    assert live.shape == (8, 4)
    assert int(live.sum()) == 20
    for by_key in (False, True):
        np.testing.assert_array_equal(_schedule(live, by_key=by_key,
                                                **shape), live)

"""GQA attention: blockwise-flash training path + cached decode path.

Layouts (see common.AttnGeom):
  q: (B, S, KV, Gp, hd)   — grouped by kv head; Gp includes padding
  k/v: (B, T, KV, hd)     — kv heads replicated over the model axis

The training/prefill path is an online-softmax blockwise ("flash")
attention written in pure jnp, so the (S, T) score matrix never
materializes — mandatory at the 32k/500k assigned shapes. An outer
`lax.scan` over query blocks (key blocks in the backward) runs an inner
loop over only the block pairs the mask leaves an element of:
`_live_range` gives each outer block its contiguous range of inner
blocks from `causal`, `window`, `q_offset` and the shapes, so a causal
4096-token sequence at 512 x 1024 blocks visits 20 of its 32 pairs.
The Pallas kernel in `repro.kernels.flash_attention` implements the
same contract for the TPU hot path and is validated against the same
oracle.

Its backward is its own (`jax.custom_vjp`, FlashAttention-2's recipe):
the forward saves q, k, v, the float32 output and each row's
log-sum-exp, and the backward recomputes each block's scores and
probabilities from them, so no per-block tensor outlives its block.

Decode: the KV cache tags every slot with its absolute position
(`pos`, -1 = empty), which makes full-cache and rolling sliding-window
caches uniform: validity/window masking is pure position arithmetic.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import AttnGeom, check_positions, rotate
from repro.sharding.specs import ParamSet, seg_matmul

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# blockwise flash attention (pure jnp)
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool, window: int = 0, q_offset: int = 0,
                    bq: int = 512, bk: int = 1024,
                    scale: Optional[float] = None) -> jax.Array:
    """q:(B,S,KV,G,hd) k,v:(B,T,KV,hd) -> (B,S,KV,G,hd)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _flash(q, k, v, causal, window, q_offset, min(bq, q.shape[1]),
                  min(bk, k.shape[1]), scale)


def _blocks(q, k, v, bq, bk):
    """Zero-pad S/T to block multiples and stack the blocks on a leading
    axis: q (nq,B,bq,KV,G,hd), k/v (nk,B,bk,KV,hd)."""
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    Sp, Tp = -(-S // bq) * bq, -(-T // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    nq, nk = Sp // bq, Tp // bk
    qb = jnp.moveaxis(qp.reshape(B, nq, bq, KV, G, hd), 1, 0)
    kb = jnp.moveaxis(kp.reshape(B, nk, bk, KV, hd), 1, 0)
    vb = jnp.moveaxis(vp.reshape(B, nk, bk, KV, hd), 1, 0)
    return qb, kb, vb


def _scores(q_blk, k_blk, q_pos, k_pos, *, T, causal, window, scale):
    """Masked scaled scores of one block pair: (B,KV,G,bq,bk) float32."""
    s = jnp.einsum("bqkgh,btkh->bkgqt", q_blk, k_blk,
                   preferred_element_type=jnp.float32) * scale
    msk = (k_pos[None, :] < T)
    if causal:
        msk = msk & (k_pos[None, :] <= q_pos[:, None])
    if window:
        msk = msk & (q_pos[:, None] - k_pos[None, :] < window)
    return jnp.where(msk[None, None, None], s, NEG_INF)


def _live_range(outer, *, by_key, causal, window, q_offset, S, T, bq, bk):
    """The contiguous range [start, stop) of inner blocks holding at
    least one element `_scores` leaves unmasked against outer block
    `outer` (the one traced value; every other argument is a Python
    int). by_key=False: `outer` is a query block, the range covers key
    blocks; by_key=True: the reverse.

    Query row r sits at q_offset + r, key column c at c, and `_scores`
    keeps c < T, c <= q (causal) and q - c < window (window > 0): the
    distance d = q - c lies in [d_lo, d_hi]. So query rows [a, b] see
    key columns [a + q_offset - d_hi, b + q_offset - d_lo], and key
    columns [a, b] are seen by query rows [a + d_lo - q_offset,
    b + d_hi - q_offset]; each clipped to the real rows or columns."""
    far = S + T + abs(q_offset)             # beyond any distance
    d_lo = 0 if causal else -far
    d_hi = window - 1 if window else far
    if by_key:
        n_out, b_out, n_in, b_in = T, bk, S, bq
        lo, hi = d_lo - q_offset, d_hi - q_offset
    else:
        n_out, b_out, n_in, b_in = S, bq, T, bk
        lo, hi = q_offset - d_hi, q_offset - d_lo
    a = outer * b_out
    b = jnp.minimum(a + b_out, n_out) - 1
    first = jnp.maximum(a + lo, 0)
    last = jnp.minimum(b + hi, n_in - 1)
    start = first // b_in
    return start, jnp.where(first <= last, last // b_in + 1, start)


def _flash_forward(q, k, v, causal, window, q_offset, bq, bk, scale):
    """The online softmax: float32 output (B,S,KV,G,hd) and each row's
    log-sum-exp (B,KV,G,S). An outer scan over query blocks; for each,
    a loop over only the key blocks `_live_range` gives it, each pair
    still masked by `_scores`. A skipped pair would add exactly nothing:
    with the causal mask its p is exp(NEG_INF - m) = 0, and a window's
    first live block wipes what came before it with corr = 0."""
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    score = partial(_scores, T=T, causal=causal, window=window,
                    scale=scale)
    live = partial(_live_range, by_key=False, causal=causal, window=window,
                   q_offset=q_offset, S=S, T=T, bq=bq, bk=bk)
    qb, kb, vb = _blocks(q, k, v, bq, bk)
    nq = qb.shape[0]

    def q_step(_, qi_blk):
        qi, q_blk = qi_blk
        q_pos = q_offset + qi * bq + jnp.arange(bq)

        def k_step(kj, carry):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_index_in_dim(kb, kj, keepdims=False)
            v_blk = jax.lax.dynamic_index_in_dim(vb, kj, keepdims=False)
            k_pos = kj * bk + jnp.arange(bk)
            s = score(q_blk, k_blk, q_pos, k_pos)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", p, v_blk.astype(jnp.float32))
            return m_new, l_new, acc_new

        m0 = jnp.full((B, KV, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, bq, hd), jnp.float32)
        m, l, acc = jax.lax.fori_loop(*live(qi), k_step, (m0, l0, a0))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B,KV,G,bq,hd) -> (B,bq,KV,G,hd)
        return None, (jnp.moveaxis(out, 3, 1), m + jnp.log(l))

    _, (ob, lse) = jax.lax.scan(q_step, None, (jnp.arange(nq), qb))
    out = jnp.moveaxis(ob, 0, 1).reshape(B, nq * bq, KV, G, hd)[:, :S]
    # (nq,B,KV,G,bq) -> (B,KV,G,S)
    lse = jnp.moveaxis(lse, 0, 3).reshape(B, KV, G, nq * bq)[..., :S]
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, window, q_offset, bq, bk, scale):
    out, _ = _flash_forward(q, k, v, causal, window, q_offset, bq, bk, scale)
    return out.astype(q.dtype)


def _flash_fwd(q, k, v, causal, window, q_offset, bq, bk, scale):
    out, lse = _flash_forward(q, k, v, causal, window, q_offset, bq, bk,
                              scale)
    return out.astype(q.dtype), (q, k, v, out, lse)


def _flash_bwd(causal, window, q_offset, bq, bk, scale, res, d_out):
    """FlashAttention-2's backward: per block pair, recompute the scores
    and p = exp(s - lse), then dV += p^T dO, dP = dO V^T,
    dS = p (dP - D) scale, dQ += dS K, dK += dS^T Q, with
    D = rowsum(dO * O). An outer scan over key blocks; for each, a loop
    over only the query blocks `_live_range` gives it, carrying this key
    block's dK, dV and the float32 dQ, into which each pair adds its
    block in place."""
    q, k, v, out, lse = res
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    score = partial(_scores, T=T, causal=causal, window=window, scale=scale)
    live = partial(_live_range, by_key=True, causal=causal, window=window,
                   q_offset=q_offset, S=S, T=T, bq=bq, bk=bk)
    qb, kb, vb = _blocks(q, k, v, bq, bk)
    nq, nk = qb.shape[0], kb.shape[0]
    Sp = nq * bq

    def q_major(x):
        """(B,S,KV,G,...) -> (nq,B,KV,G,bq,...), zero-padded rows."""
        x = jnp.pad(x, [(0, 0), (0, Sp - S)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape(B, nq, bq, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 4)

    do = d_out.astype(jnp.float32)
    dob = q_major(do)                                    # (nq,B,KV,G,bq,hd)
    deltab = q_major((do * out).sum(axis=-1))            # (nq,B,KV,G,bq)
    lse = jnp.pad(lse, ((0, 0), (0, 0), (0, 0), (0, Sp - S)))
    lseb = jnp.moveaxis(lse.reshape(B, KV, G, nq, bq), 3, 0)

    def k_step(dq, kj_blk):
        kj, k_blk, v_blk = kj_blk
        k_pos = kj * bk + jnp.arange(bk)
        v32 = v_blk.astype(jnp.float32)

        def q_step(qi, carry):
            dq, dk, dv = carry
            q_blk, do_blk, lse_blk, delta_blk = (
                jax.lax.dynamic_index_in_dim(x, qi, keepdims=False)
                for x in (qb, dob, lseb, deltab))
            q_pos = q_offset + qi * bq + jnp.arange(bq)
            s = score(q_blk, k_blk, q_pos, k_pos)
            p = jnp.exp(s - lse_blk[..., None])
            dv = dv + jnp.einsum("bkgqt,bkgqh->btkh", p, do_blk)
            dp = jnp.einsum("bkgqh,btkh->bkgqt", do_blk, v32)
            ds = p * (dp - delta_blk[..., None]) * scale
            dq_blk = jnp.einsum("bkgqt,btkh->bqkgh", ds, k_blk,
                                preferred_element_type=jnp.float32)
            dk = dk + jnp.einsum("bkgqt,bqkgh->btkh", ds, q_blk,
                                 preferred_element_type=jnp.float32)
            dq = jax.lax.dynamic_update_index_in_dim(
                dq, jax.lax.dynamic_index_in_dim(dq, qi, keepdims=False)
                + dq_blk, qi, 0)
            return dq, dk, dv

        z = jnp.zeros((B, bk, KV, hd), jnp.float32)
        dq, dk, dv = jax.lax.fori_loop(*live(kj), q_step, (dq, z, z))
        return dq, (dk, dv)

    dq0 = jnp.zeros(qb.shape, jnp.float32)
    dq, (dk, dv) = jax.lax.scan(k_step, dq0, (jnp.arange(nk), kb, vb))
    dq = jnp.moveaxis(dq, 0, 1).reshape(B, Sp, KV, G, hd)[:, :S]
    dk = jnp.moveaxis(dk, 0, 1).reshape(B, nk * bk, KV, hd)[:, :T]
    dv = jnp.moveaxis(dv, 0, 1).reshape(B, nk * bk, KV, hd)[:, :T]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_flash.defvjp(_flash_fwd, _flash_bwd)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool, window: int = 0,
                  q_offset: int = 0) -> jax.Array:
    """Naive oracle — same contract as flash_attention."""
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    s = jnp.einsum("bqkgh,btkh->bkgqt", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(hd)
    q_pos = q_offset + jnp.arange(S)
    k_pos = jnp.arange(T)
    msk = jnp.ones((S, T), bool)
    if causal:
        msk = msk & (k_pos[None, :] <= q_pos[:, None])
    if window:
        msk = msk & (q_pos[:, None] - k_pos[None, :] < window)
    s = jnp.where(msk[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqt,btkh->bqkgh", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------

def _proj_qkv(cfg: ModelConfig, geom: AttnGeom, pset: ParamSet,
              lp: Dict[str, jax.Array], x: jax.Array
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    q = seg_matmul(x, lp, pset, "layers/attn/wq", 0)
    k = seg_matmul(x, lp, pset, "layers/attn/wk", 0)
    v = seg_matmul(x, lp, pset, "layers/attn/wv", 0)
    if cfg.qkv_bias:
        q = q + lp["layers/attn/bq"]
        k = k + lp["layers/attn/bk"]
        v = v + lp["layers/attn/bv"]
    q = q.reshape(B, S, geom.n_kv, geom.group_padded, geom.head_dim)
    k = k.reshape(B, S, geom.n_kv, geom.head_dim)
    v = v.reshape(B, S, geom.n_kv, geom.head_dim)
    return q, k, v


def _group_mask(geom: AttnGeom, dtype) -> jax.Array:
    """(KV, Gp) 1/0 mask zeroing padded q heads."""
    return (jnp.arange(geom.group_padded) < geom.group).astype(dtype)[None, :]


def _out_proj(geom: AttnGeom, pset: ParamSet, lp: Dict[str, jax.Array],
              o: jax.Array) -> jax.Array:
    """o: (B,S,KV,Gp,hd) -> (B,S,d); masks padded heads to exact zero."""
    B, S = o.shape[:2]
    o = o * _group_mask(geom, o.dtype)[None, None, :, :, None]
    o = o.reshape(B, S, geom.q_flat)
    return seg_matmul(o, lp, pset, "layers/attn/wo", 0)


# ---------------------------------------------------------------------------
# block entry points
# ---------------------------------------------------------------------------

def attn_forward(cfg: ModelConfig, geom: AttnGeom, pset: ParamSet,
                 lp: Dict[str, jax.Array], x: jax.Array,
                 positions: jax.Array, *, window: int = 0) -> jax.Array:
    """Training / prefill attention over a full sequence."""
    with jax.named_scope("attention/qkv"):
        q, k, v = _proj_qkv(cfg, geom, pset, lp, x)
        q = rotate(cfg, q.reshape(*q.shape[:2], -1, geom.head_dim),
                   positions).reshape(q.shape)
        k = rotate(cfg, k, positions)
    win = window or cfg.sliding_window
    with jax.named_scope("attention/core"):
        o = flash_attention(q, k, v, causal=cfg.causal, window=win)
    with jax.named_scope("attention/out"):
        return _out_proj(geom, pset, lp, o)


def init_kv_cache(cfg: ModelConfig, geom: AttnGeom, batch: int,
                  cache_len: int, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Per-layer stacked cache pytree (leading L axis)."""
    check_positions(cfg, cache_len)
    L = cfg.n_layers
    return {
        "k": jnp.zeros((L, batch, cache_len, geom.n_kv, geom.head_dim), dtype),
        "v": jnp.zeros((L, batch, cache_len, geom.n_kv, geom.head_dim), dtype),
        "pos": jnp.full((L, batch, cache_len), -1, jnp.int32),
    }


def attn_decode(cfg: ModelConfig, geom: AttnGeom, pset: ParamSet,
                lp: Dict[str, jax.Array], x: jax.Array, t: jax.Array,
                cache: Dict[str, jax.Array], *,
                window: int = 0,
                positions3: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: (B,1,d); t: step index — a scalar (whole
    batch in lockstep) or a (B,) vector (continuous batching: each
    sequence at its own position); cache holds this layer's slices
    {k:(B,Sc,KV,hd), v:..., pos:(B,Sc)}."""
    B = x.shape[0]
    Sc = cache["k"].shape[1]
    t_vec = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
    if cfg.rope == "mrope":
        pos_arg = positions3                       # (B,1,3)
    else:
        pos_arg = t_vec[:, None]                   # (B,1)
    with jax.named_scope("attention/qkv"):
        q, k, v = _proj_qkv(cfg, geom, pset, lp, x)
        if cfg.rope != "none":
            q = rotate(cfg, q.reshape(B, 1, -1, geom.head_dim), pos_arg
                       ).reshape(q.shape)
            k = rotate(cfg, k, pos_arg)
    # per-sequence ring-buffer slot: a scatter row-by-row (identical to
    # the old dynamic_update_slice when every t is equal)
    slot = jnp.where(Sc > 0, t_vec % Sc, 0).astype(jnp.int32)
    bidx = jnp.arange(B)
    k_cache = cache["k"].at[bidx, slot].set(k[:, 0])
    v_cache = cache["v"].at[bidx, slot].set(v[:, 0])
    pos_cache = cache["pos"].at[bidx, slot].set(t_vec)

    # single-row softmax over the cache (scores are (B,KV,Gp,1,Sc) — small)
    with jax.named_scope("attention/core"):
        s = jnp.einsum("bqkgh,btkh->bkgqt", q, k_cache,
                       preferred_element_type=jnp.float32)
        s = s / math.sqrt(geom.head_dim)
        valid = pos_cache >= 0
        if window:
            valid = valid & (t_vec[:, None] - pos_cache < window)
        valid = valid & (pos_cache <= t_vec[:, None])
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgqt,btkh->bqkgh", p, v_cache.astype(jnp.float32)
                       ).astype(x.dtype)
    with jax.named_scope("attention/out"):
        out = _out_proj(geom, pset, lp, o)
    return out, {"k": k_cache, "v": v_cache, "pos": pos_cache}

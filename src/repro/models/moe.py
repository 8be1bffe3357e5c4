"""Mixture-of-Experts layer: top-k router + capacity dispatch.

Dispatch is index-based (sort-free Shazeer-style with capacity): for
each expert we compute the positions of the tokens routed to it (rank
within expert via a cumulative-sum over the one-hot routing mask —
O(T·E) int ops, no (T,E,C) one-hot dispatch tensor), gather the tokens
into an (E, C, d) buffer, run the expert FFNs as a single grouped
einsum over the expert axis (TP = expert parallelism: E is sharded
over `model`), and combine with router weights via scatter-add.
Tokens overflowing an expert's capacity are dropped (standard capacity
semantics); the aux load-balance loss pushes the router away from that
regime.

Router runs in fp32; aux loss = E * sum_e f_e * p_e (Switch-style).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.sharding.specs import ParamSet, gather_weight


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    cap = int(cfg.moe_capacity_factor * n_tokens * cfg.moe_top_k
              / cfg.moe_experts)
    return max(8, -(-cap // 8) * 8)


def route(cfg: ModelConfig, router_w: jax.Array, x: jax.Array
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (T,d) -> (probs (T,k), experts (T,k), aux_loss)."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)               # (T,E)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe_top_k)    # (T,k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch aux loss: fraction of tokens per expert * mean router prob
    E = cfg.moe_experts
    onehot = jax.nn.one_hot(top_e[:, 0], E)               # primary choice
    f = onehot.mean(0)
    p = probs.mean(0)
    aux = E * jnp.sum(f * p)
    return top_p, top_e, aux


def _expert_constrain(x: jax.Array, mesh, axis: int = 0) -> jax.Array:
    """Pin the expert axis to the `model` mesh axis (expert parallelism)
    with every other dim replicated. Without this, a d-sharded residual
    stream makes GSPMD partial-sum the (E, C, ff) expert activations in
    fp32 across the model axis (§Perf pair-2 pathology: ~28 GB
    all-reduce per matmul per layer) instead of gathering the much
    smaller (E, C, d) input."""
    if mesh is None or x.shape[axis] % mesh.shape["model"] != 0:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    parts = [None] * x.ndim
    parts[axis] = "model"
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


@jax.named_scope("moe")
def moe_forward(cfg: ModelConfig, pset: ParamSet, lp: Dict[str, jax.Array],
                x: jax.Array, mesh=None) -> Tuple[jax.Array, jax.Array]:
    """x: (B,S,d) -> (y: (B,S,d), aux_loss scalar)."""
    B, S, d = x.shape
    T = B * S
    E, k, ff = cfg.moe_experts, cfg.moe_top_k, cfg.d_ff
    C = _capacity(cfg, T)
    xt = x.reshape(T, d)

    top_p, top_e, aux = route(cfg, lp["layers/moe/router"], xt)

    # flatten (token, choice) pairs -> assignment list of length T*k
    flat_e = top_e.reshape(-1)                            # (T*k,)
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), k)
    # rank of each assignment within its expert
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)   # (T*k, E)
    rank = jnp.cumsum(onehot, axis=0) - onehot            # rank per expert
    my_rank = jnp.take_along_axis(rank, flat_e[:, None], axis=1)[:, 0]
    keep = my_rank < C
    slot = flat_e * C + jnp.where(keep, my_rank, 0)       # (T*k,)

    # index-gather dispatch (§Perf pair-2 iter 2): the only scatter
    # builds tiny int32 slot->token maps; tokens then move via a single
    # gather whose output is expert-sharded (GSPMD lowers it to bf16
    # gathers instead of the fp32 scatter-add all-reduce).
    safe = jnp.where(keep, slot, E * C)   # dropped -> scratch slot E*C
    idx = jnp.zeros((E * C + 1,), jnp.int32).at[safe].set(
        flat_tok.astype(jnp.int32))[:E * C]
    occ = jnp.zeros((E * C + 1,), bool).at[safe].set(True)[:E * C]
    xe = xt[idx] * occ[:, None].astype(x.dtype)           # (E*C, d)
    xe = _expert_constrain(xe.reshape(E, C, d), mesh)

    # expert FFNs (E sharded over model axis)
    w13 = gather_weight(lp, pset, "layers/moe/w13")       # (E, d, 2ff)
    w2 = gather_weight(lp, pset, "layers/moe/w2")         # (E, ff, d)
    h = jnp.einsum("ecd,edf->ecf", xe, w13)
    h = _expert_constrain(h, mesh)
    if cfg.act == "swiglu":
        g1, g3 = h[..., :ff], h[..., ff:]
        h = jax.nn.silu(g1.astype(jnp.float32)).astype(x.dtype) * g3
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    ye = _expert_constrain(jnp.einsum("ecf,efd->ecd", h, w2), mesh)  # (E,C,d)

    # combine: gather each assignment's expert output and reduce over
    # the k choices — flat_tok is contiguous repeat(arange(T), k), so
    # this is a scatter-free reshape-sum.
    ye_flat = ye.reshape(E * C, d)
    contrib = ye_flat[slot] * (flat_p * keep)[:, None].astype(x.dtype)
    y = contrib.reshape(T, k, d).sum(axis=1)
    return y.reshape(B, S, d), aux.astype(jnp.float32)


def moe_ref(cfg: ModelConfig, router_w, w13, w2, x: jax.Array
            ) -> jax.Array:
    """Dense oracle (no capacity drops): every token times its top-k
    experts, computed with full dense expert application."""
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    top_p, top_e, _ = route(cfg, router_w, xt)
    y = jnp.zeros((T, d), jnp.float32)
    for e in range(cfg.moe_experts):
        h = xt @ w13[e]
        ff = cfg.d_ff
        if cfg.act == "swiglu":
            h = (jax.nn.silu(h[..., :ff].astype(jnp.float32))
                 .astype(x.dtype) * h[..., ff:])
        else:
            h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
        out_e = (h @ w2[e]).astype(jnp.float32)
        w = ((top_e == e) * top_p).sum(-1)                # (T,)
        y = y + out_e * w[:, None]
    return y.reshape(B, S, d).astype(x.dtype)

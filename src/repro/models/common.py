"""Shared model building blocks: norms, RoPE / M-RoPE, GQA geometry.

GQA geometry on a fixed 16-way `model` axis (DESIGN.md §6):
  * q/k/v/o projections use flat (n_heads*head_dim) layouts; the flat
    dim is TP-sharded.
  * For reshape (flat -> (kv, group, hd)) to preserve the sharding, we
    need (kv * group) % tp == 0. If the config's head count doesn't
    satisfy that, q heads are padded *per kv group* (layout
    (kv, group_padded, hd)); padded heads are masked to exact zero
    before the out-projection, so gradients to their weights vanish and
    the model is semantically identical to the unpadded config.
  * kv heads are replicated across the model axis (kv tensors are small
    under GQA); see DESIGN.md for the cache sharding that compensates.
  * If padding would exceed PAD_LIMIT of the true head count, attention
    runs without TP (params/compute replicated on the model axis) —
    OSDP's memory search then naturally leans ZDP for those weights.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

PAD_LIMIT = 1.5


@dataclass(frozen=True)
class AttnGeom:
    """Resolved GQA geometry for a given model-axis size."""

    n_heads: int          # true q heads
    n_kv: int
    head_dim: int
    group: int            # true q heads per kv head
    group_padded: int     # padded group size (>= group)
    tp: bool              # whether attention projections are TP-sharded

    @property
    def padded_heads(self) -> int:
        return self.n_kv * self.group_padded

    @property
    def q_flat(self) -> int:
        return self.padded_heads * self.head_dim

    @property
    def kv_flat(self) -> int:
        return self.n_kv * self.head_dim


def attn_geometry(cfg: ModelConfig, tp_size: int) -> AttnGeom:
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    g = h // kv
    gp = g
    if tp_size > 1:
        while (kv * gp) % tp_size != 0:
            gp += 1
    if gp * kv > PAD_LIMIT * h:
        return AttnGeom(h, kv, hd, g, g, tp=False)
    return AttnGeom(h, kv, hd, g, gp, tp=(tp_size > 1))


# --- norms -------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return y.astype(x.dtype) * scale + bias


def norm(cfg: ModelConfig, x: jax.Array, scale: jax.Array,
         bias: Optional[jax.Array] = None) -> jax.Array:
    if cfg.norm == "layernorm":
        assert bias is not None
        return layernorm(x, scale, bias, eps=cfg.norm_eps or 1e-5)
    return rmsnorm(x, scale, eps=cfg.norm_eps or 1e-6)


# --- rotary embeddings -------------------------------------------------------

def rope_freqs(head_dim: int, theta: float,
               factors: Tuple[float, ...] = ()) -> jax.Array:
    """Inverse frequencies of the `head_dim // 2` rotated pairs, each
    divided by its LongRoPE factor where `factors` are given."""
    half = head_dim // 2
    exps = jnp.arange(half, dtype=jnp.float32) * 2 / head_dim
    if factors:
        return 1.0 / (jnp.asarray(factors, jnp.float32) * theta ** exps)
    return 1.0 / (theta ** exps)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               rotary_dim: int = 0, factors: Tuple[float, ...] = (),
               scale: float = 1.0) -> jax.Array:
    """x: (..., S, n, hd); positions: broadcastable to (..., S).

    The first `rotary_dim` dims (all by default) rotate as two halves
    paired (HF's `rotate_half` layout), the rest pass through; cos and
    sin are multiplied by `scale` (LongRoPE's attention factor)."""
    hd = x.shape[-1]
    rot = rotary_dim or hd
    freqs = rope_freqs(rot, theta, factors)             # (rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs   # (..., S, rot/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf if rot == hd else xf[..., :rot], 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if rot < hd:
        out = jnp.concatenate([out, xf[..., rot:]], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    positions3: (..., S, 3) = (t, h, w) index per token. The hd/2
    frequency slots are split into `sections` (t, h, w); each section
    rotates by its own position component.
    """
    hd = x.shape[-1]
    half = hd // 2
    st, sh, sw = sections
    assert st + sh + sw == half, (sections, half)
    freqs = rope_freqs(hd, theta)                       # (half,)
    # per-slot position: section t uses positions3[...,0], etc.
    sec_id = jnp.concatenate([
        jnp.zeros((st,), jnp.int32), jnp.ones((sh,), jnp.int32),
        jnp.full((sw,), 2, jnp.int32)])
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(sec_id, positions3.shape[:-1] + (half,)).astype(
            jnp.int32),
        axis=-1)                                        # (..., S, half)
    ang = pos * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def positions_for(cfg: ModelConfig, batch: dict, seq: int) -> jax.Array:
    """Token positions: (B,S) for rope, (B,S,3) for mrope."""
    if cfg.rope == "mrope":
        return batch["positions"]
    if "positions" in batch:
        return batch["positions"]
    ref = batch.get("tokens", batch.get("frames"))
    b = ref.shape[0]
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (b, seq))


def check_positions(cfg: ModelConfig, n: int) -> None:
    """Refuse `n` positions past those the rotary embedding is defined
    for (LongRoPE's longer contexts need long factors it does not
    hold)."""
    if cfg.max_positions and n > cfg.max_positions:
        raise ValueError(
            f"{cfg.name}: {n} positions; its LongRoPE short factors cover "
            f"{cfg.max_positions} and the long factors are not held")


def rotate(cfg: ModelConfig, x: jax.Array, positions: jax.Array) -> jax.Array:
    if cfg.rope == "none":
        return x
    if cfg.rope == "mrope":
        return apply_mrope(x, positions, cfg.rope_theta, cfg.mrope_sections)
    if cfg.rope == "longrope":
        check_positions(cfg, positions.shape[-1])
        return apply_rope(x, positions, cfg.rope_theta, cfg.rotary_dim,
                          cfg.rope_short_factor, cfg.rope_attention_factor)
    return apply_rope(x, positions, cfg.rope_theta, cfg.rotary_dim)

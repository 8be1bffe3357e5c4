"""Dense FFN (SwiGLU / GeLU) with OSDP operator-splitting hooks."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.cost_model import Decision
from repro.core.operator_split import chunked_ffn
from repro.sharding.specs import ParamSet, seg_matmul


@jax.named_scope("ffn")
def ffn_forward(cfg: ModelConfig, pset: ParamSet, lp: Dict[str, jax.Array],
                x: jax.Array, prefix: str = "layers/ffn",
                granularity: int = 1) -> jax.Array:
    """x: (B,S,d) -> (B,S,d).

    Three execution paths:
      * plan split the op into mixed-mode segments -> seg_matmul
        (paper §3.3 per-slice modes);
      * uniform mode but splitting requested -> chunked_ffn (sequential
        slice processing caps the live hidden / gathered weight);
      * otherwise plain matmuls.
    """
    w13_path, w2_path = f"{prefix}/w13", f"{prefix}/w2"
    mixed = pset.layouts[w13_path].is_split or pset.layouts[w2_path].is_split
    if mixed:
        h = seg_matmul(x, lp, pset, w13_path, 0)
        h = _act(cfg, h)
        return seg_matmul(h, lp, pset, w2_path, 0)
    w13 = lp[w13_path]
    w2 = lp[w2_path]
    if granularity > 1:
        return chunked_ffn(x, w13, w2, granularity, cfg.act)
    return _act(cfg, x @ w13) @ w2


def _act(cfg: ModelConfig, h: jax.Array) -> jax.Array:
    if cfg.act == "swiglu":
        ff = h.shape[-1] // 2
        return (jax.nn.silu(h[..., :ff].astype(jnp.float32))
                .astype(h.dtype) * h[..., ff:])
    return jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)

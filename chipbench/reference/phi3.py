"""Plain float32 reference of a Phi-3 decoder (Phi-4-mini): the dense
reference (`reference/dense.py`) with Phi-3's rotary embedding.

Written from the published equations (HF `Phi3RotaryEmbedding`,
`apply_rotary_pos_emb` and LongRoPE's parameters), not from the
program: only the first `rotary_fraction` of each head rotates, as two
halves paired (`rotate_half`), and the rest passes through. Up to
`rope_original_max` positions each pair's inverse frequency is
1 / (short_factor[i] * theta^(2i / rotary_dim)), and cos and sin are
both multiplied by the attention factor. Longer sequences would take
the long factors, which the configuration does not hold: refused.
"""
from __future__ import annotations

import jax.numpy as jnp

from chipbench.reference import dense


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


class Reference(dense.Reference):
    def __init__(self, m: dict, **kw):
        kw.setdefault("eps", m["norm_eps"])
        super().__init__(m, **kw)
        self.rot = int(self.hd * m["rotary_fraction"])

    def rope(self, x, pos):
        """x: (B, S, n, hd); pos: (S,)."""
        m = self.m
        if pos.shape[0] > m["rope_original_max"]:
            raise ValueError(f"{pos.shape[0]} positions: past the short "
                             f"factors' {m['rope_original_max']}")
        exps = jnp.arange(0, self.rot, 2, dtype=jnp.float32) / self.rot
        inv = 1.0 / (jnp.asarray(m["rope_short_factor"], jnp.float32)
                     * m["rope_theta"] ** exps)
        freqs = pos.astype(jnp.float32)[:, None] * inv
        emb = jnp.concatenate([freqs, freqs], axis=-1)
        cos = (jnp.cos(emb) * m["rope_attention_factor"])[:, None, :]
        sin = (jnp.sin(emb) * m["rope_attention_factor"])[:, None, :]
        xr, xp = x[..., :self.rot], x[..., self.rot:]
        return jnp.concatenate([xr * cos + rotate_half(xr) * sin, xp],
                               axis=-1)

"""Plain float32 reference of a dense decoder and of its AdamW step.

Written from the architecture's description, not from the program:
pre-norm blocks of RMSNorm, grouped-query attention with rotary
positions (the two halves of each head rotated as a pair), and a gated
SiLU FFN whose first half gates the second; a final RMSNorm and a head
tied to the embedding, over a vocabulary padded to a multiple of 256
whose padded entries never win. The loss is the mean next-token cross
entropy over the labels that are not negative.

It computes in float32 with matmuls at `highest` precision, in blocks
of queries and of token positions, each recomputed on the backward
pass, so that it fits beside nothing else on one chip. `lowp` names a
lower precision for every matmul of the step: that is the control, the
same mathematics at less precision than the configuration states. Each
forward operand is rounded to `lowp`, and each gradient that enters a
matmul of the backward pass to `GRAD_TYPE[lowp]` (float8 training's
recipe: e4m3 forward, e5m2 backward), every tensor scaled to its type's
range as such matmuls are fed; the backward matmuls read the rounded
forward operands.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST


GRAD_TYPE = {jnp.dtype(jnp.float8_e4m3fn): jnp.float8_e5m2}


def _scaled(x, dtype):
    """`x` rounded to `dtype` with one scale per tensor: the largest
    magnitude maps to the largest finite value of a float8 type."""
    top = float(jnp.finfo(dtype).max)
    if top > 1e5:               # a wide type (bfloat16) needs no scale
        return x.astype(dtype).astype(jnp.float32)
    s = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * s).astype(dtype).astype(jnp.float32) / s


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _round(x, dtype):
    """A matmul operand rounded to `dtype`; its gradient passes as it is."""
    return _scaled(x, dtype)


@_round.defjvp
def _round_jvp(dtype, primals, tangents):
    return _round(primals[0], dtype), tangents[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_cotangent(x, dtype):
    """`x` as it is; the gradient that flows back through it is rounded
    to `dtype` before it enters the backward matmuls."""
    return x


def _round_cotangent_fwd(x, dtype):
    return x, None


def _round_cotangent_bwd(dtype, _, g):
    return (_scaled(g, dtype),)


_round_cotangent.defvjp(_round_cotangent_fwd, _round_cotangent_bwd)


class Reference:
    def __init__(self, m: dict, *, lowp=None, q_block: int = 1024,
                 ce_block: int = 512, eps: float = 1e-6):
        self.m = m
        self.lowp = lowp
        self.q_block = q_block
        self.ce_block = ce_block
        self.eps = eps
        self.hd = m.get("head_dim") or m["d_model"] // m["n_heads"]

    # -- pieces ---------------------------------------------------------------
    def mm(self, spec: str, a, b):
        if self.lowp is None:
            return jnp.einsum(spec, a, b, precision=HIGHEST)
        a, b = _round(a, self.lowp), _round(b, self.lowp)
        return _round_cotangent(jnp.einsum(spec, a, b, precision=HIGHEST),
                                GRAD_TYPE.get(jnp.dtype(self.lowp),
                                              self.lowp))

    def rmsnorm(self, x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + self.eps) * scale

    def rope(self, x, pos):
        """x: (B, S, n, hd); pos: (S,)."""
        half = self.hd // 2
        freqs = 1.0 / (self.m.get("rope_theta", 1e4)
                       ** (jnp.arange(half, dtype=jnp.float32) * 2
                           / self.hd))
        ang = pos.astype(jnp.float32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                               axis=-1)

    def _attn_block(self, q, k, v, *, start: int):
        """Queries start..start+len(q) over keys 0..start+len(q)."""
        n = q.shape[1]
        k, v = k[:, :start + n], v[:, :start + n]
        s = self.mm("bqkgh,btkh->bkgqt", q, k) / math.sqrt(self.hd)
        qp = start + jnp.arange(n)
        kp = jnp.arange(start + n)
        s = jnp.where((kp[None, :] <= qp[:, None])[None, None, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        return self.mm("bkgqt,btkh->bqkgh", p, v)

    def layer(self, x, lp):
        m = self.m
        B, S, _ = x.shape
        kv, G = m["n_kv_heads"], m["n_heads"] // m["n_kv_heads"]
        pos = jnp.arange(S)
        h = self.rmsnorm(x, lp["layers/attn/norm_scale"])
        q = self.mm("bsd,de->bse", h, lp["layers/attn/wq"])
        k = self.mm("bsd,de->bse", h, lp["layers/attn/wk"])
        v = self.mm("bsd,de->bse", h, lp["layers/attn/wv"])
        if "layers/attn/bq" in lp:
            q = q + lp["layers/attn/bq"]
            k = k + lp["layers/attn/bk"]
            v = v + lp["layers/attn/bv"]
        q = self.rope(q.reshape(B, S, kv * G, self.hd), pos)
        k = self.rope(k.reshape(B, S, kv, self.hd), pos)
        v = v.reshape(B, S, kv, self.hd)
        q = q.reshape(B, S, kv, G, self.hd)
        outs = []
        for start in range(0, S, self.q_block):
            blk = functools.partial(self._attn_block, start=start)
            outs.append(jax.checkpoint(blk)(
                q[:, start:start + self.q_block], k, v))
        o = jnp.concatenate(outs, axis=1).reshape(B, S, kv * G * self.hd)
        x = x + self.mm("bse,ed->bsd", o, lp["layers/attn/wo"])
        h = self.rmsnorm(x, lp["layers/ffn/norm_scale"])
        h = self.mm("bsd,df->bsf", h, lp["layers/ffn/w13"])
        ff = h.shape[-1] // 2
        h = jax.nn.silu(h[..., :ff]) * h[..., ff:]
        return x + self.mm("bsf,fd->bsd", h, lp["layers/ffn/w2"])

    def hidden(self, params, tokens):
        """Final hidden states, before the final norm: (B, S, d)."""
        x = jnp.take(params["embed/tok"], tokens, axis=0)
        layers = {k: v for k, v in params.items() if k.startswith("layers/")}

        def body(x, lp):
            return self.layer(x, lp), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, layers)
        return x

    def logits(self, params, x):
        """x: (..., d) hidden -> (..., V_padded) float32 logits."""
        x = self.rmsnorm(x, params["final_norm/scale"])
        if "head/out" in params:
            out = self.mm("...d,dv->...v", x, params["head/out"])
        else:
            out = self.mm("...d,vd->...v", x, params["embed/tok"])
        vocab = jnp.arange(out.shape[-1])
        return jnp.where(vocab < self.m["vocab_size"], out, NEG)

    def _blocks(self, a):
        """(B, S, ...) -> (B * S / c, c, ...): the positions of all rows,
        `ce_block` at a time, so that a block's logits stay the same size
        however the tokens are cut into rows."""
        n = a.shape[0] * a.shape[1]
        c = min(self.ce_block, n)
        return a.reshape(n // c, c, *a.shape[2:])

    def loss(self, params, tokens, labels):
        x = self.hidden(params, tokens)

        def body(tot, blk):
            xb, lb = blk
            logp = jax.nn.log_softmax(self.logits(params, xb), axis=-1)
            nll = -jnp.take_along_axis(logp, jnp.maximum(lb, 0)[..., None],
                                       axis=-1)[..., 0]
            return tot + jnp.where(lb >= 0, nll, 0.0).sum(), None

        tot, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros(()),
                              (self._blocks(x), self._blocks(labels)))
        return tot / jnp.maximum((labels >= 0).sum(), 1)


# -- AdamW, as the configuration's optimizer states it -------------------------

def decays(name: str) -> bool:
    """No weight decay on norm scales and biases."""
    return not any(s in name for s in ("norm", "bias"))


def warmup_cosine(step: int, warmup: int, total: int,
                  min_ratio: float = 0.1) -> float:
    if step < warmup:
        return step / max(1, warmup)
    prog = min(max((step - warmup) / max(1, total - warmup), 0.0), 1.0)
    return min_ratio + (1 - min_ratio) * 0.5 * (1 + math.cos(math.pi * prog))


def adamw(opt: dict, master: Dict, m: Dict, v: Dict, grads: Dict,
          step, lr):
    """One update of the float32 masters at optimizer step `step` (1 for
    the first) and learning rate `lr`, both arrays so that one compiled
    program serves every step. Returns (master, m, v, pre-clip global
    gradient norm)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
    bc1, bc2 = 1 - opt["b1"] ** step, 1 - opt["b2"] ** step
    out_p, out_m, out_v = {}, {}, {}
    for k, g in grads.items():
        g = g * scale
        mk = opt["b1"] * m[k] + (1 - opt["b1"]) * g
        vk = opt["b2"] * v[k] + (1 - opt["b2"]) * g * g
        upd = (mk / bc1) / (jnp.sqrt(vk / bc2) + opt["eps"])
        wd = opt["weight_decay"] if decays(k) else 0.0
        out_p[k] = master[k] - lr * (upd + wd * master[k])
        out_m[k], out_v[k] = mk, vk
    return out_p, out_m, out_v, gnorm

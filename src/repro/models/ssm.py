"""Mamba2 / SSD (state-space duality) block. [arXiv:2405.21060]

Block: in_proj -> [z | x | B | C | dt] -> causal depthwise conv on
(x,B,C) -> SSD chunk scan -> gated RMSNorm(z) -> out_proj.

SSD chunk scan (the paper's "quadratic-linear duality"): the sequence
is processed in chunks of Q steps; within a chunk the recurrence is
the quadratic attention-like form, across chunks a linear state
recurrence carries (nh, hd, ns) states. This is O(S·Q) compute and
O(S) memory, and is the algorithm the Pallas `ssd_scan` kernel tiles
for VMEM (kernels/ssd_scan.py), both validated against the naive
sequential oracle `ssd_ref`.

Sharding: d_inner (and therefore the SSD heads) is TP-sharded over
`model`; B/C/dt are small and replicated; the state is head-sharded.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import rmsnorm
from repro.sharding.specs import ParamSet, seg_matmul

CONV_K = 4  # depthwise conv kernel width (Mamba2 default)


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------

def ssd_chunk_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array,
                   b: jax.Array, c: jax.Array, chunk: int,
                   init_state: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, jax.Array]:
    """SSD over a sequence.

    x: (B,S,nh,hd)  dt: (B,S,nh)  a_log: (nh,) [stores log(-A) > 0]
    b, c: (B,S,ns)  (single group, shared across heads)
    returns (y: (B,S,nh,hd), final_state: (B,nh,hd,ns))
    """
    B, S, nh, hd = x.shape
    ns = b.shape[-1]
    Q = min(chunk, S)
    pad = (-S) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // Q

    a = -jnp.exp(a_log.astype(jnp.float32))              # (nh,) A < 0
    dt = dt.astype(jnp.float32)
    dA = dt * a                                          # (B,Sp,nh) log-decay
    xd = x.astype(jnp.float32) * dt[..., None]           # dt-weighted input

    # chunked views
    dAc = dA.reshape(B, nc, Q, nh)
    xc = xd.reshape(B, nc, Q, nh, hd)
    bc = b.reshape(B, nc, Q, ns).astype(jnp.float32)
    cc = c.reshape(B, nc, Q, ns).astype(jnp.float32)

    csum = jnp.cumsum(dAc, axis=2)                       # (B,nc,Q,nh)
    # intra-chunk (quadratic within chunk):
    #   att[i,j] = exp(csum_i - csum_j) * (c_i . b_j)  for i >= j
    diff = csum[:, :, :, None, :] - csum[:, :, None, :, :]   # (B,nc,Q,Q,nh)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # mask BEFORE exp: masked (i<j) entries have diff>0 and would inf/NaN
    # the backward pass through where(mask, exp(diff), 0)
    att = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -jnp.inf))
    cb = jnp.einsum("bnis,bnjs->bnij", cc, bc)           # (B,nc,Q,Q)
    y_intra = jnp.einsum("bnij,bnijh,bnjhd->bnihd", cb, att, xc)

    # end-of-chunk states: S_n = sum_j exp(csum_last - csum_j) b_j x_j^T
    decay_to_end = jnp.exp(csum[:, :, -1:, :] - csum)    # (B,nc,Q,nh)
    states = jnp.einsum("bnjs,bnjh,bnjhd->bnhds",
                        bc, decay_to_end, xc)            # (B,nc,nh,hd,ns)

    # inter-chunk recurrence over chunks
    chunk_decay = jnp.exp(csum[:, :, -1, :])             # (B,nc,nh)
    s0 = (init_state.astype(jnp.float32) if init_state is not None
          else jnp.zeros((B, nh, hd, ns), jnp.float32))

    def step(s_prev, inp):
        dec, s_new = inp                                 # (B,nh), (B,nh,hd,ns)
        s = s_prev * dec[:, :, None, None] + s_new
        return s, s_prev

    chunk_decay_t = jnp.moveaxis(chunk_decay, 1, 0)      # (nc,B,nh)
    states_t = jnp.moveaxis(states, 1, 0)                # (nc,B,nh,hd,ns)
    final_state, prev_states = jax.lax.scan(
        step, s0, (chunk_decay_t, states_t))
    prev_states = jnp.moveaxis(prev_states, 0, 1)        # (B,nc,nh,hd,ns)

    # inter-chunk contribution: y_i += (c_i . S_prev) * exp(csum_i)
    y_inter = jnp.einsum("bnis,bnih,bnhds->bnihd",
                         cc, jnp.exp(csum), prev_states)
    y = (y_intra + y_inter).reshape(B, Sp, nh, hd)[:, :S]
    return y, final_state


def ssd_ref(x, dt, a_log, b, c,
            init_state: Optional[jax.Array] = None):
    """Naive O(S) sequential oracle (per-step recurrence)."""
    B, S, nh, hd = x.shape
    ns = b.shape[-1]
    a = -jnp.exp(a_log.astype(jnp.float32))
    s = (init_state.astype(jnp.float32) if init_state is not None
         else jnp.zeros((B, nh, hd, ns), jnp.float32))
    dt = dt.astype(jnp.float32)
    ys = []
    for t in range(S):
        dec = jnp.exp(dt[:, t] * a)                      # (B,nh)
        upd = jnp.einsum("bs,bnh->bnhs", b[:, t].astype(jnp.float32),
                         x[:, t].astype(jnp.float32) * dt[:, t][..., None])
        s = s * dec[:, :, None, None] + upd
        ys.append(jnp.einsum("bs,bnhs->bnh", c[:, t].astype(jnp.float32), s))
    return jnp.stack(ys, axis=1), s


def ssd_decode_step(x, dt, a_log, b, c, state):
    """One token: x:(B,nh,hd) dt:(B,nh) b,c:(B,ns) state:(B,nh,hd,ns)."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    dec = jnp.exp(dt.astype(jnp.float32) * a)            # (B,nh)
    upd = jnp.einsum("bs,bnh->bnhs", b.astype(jnp.float32),
                     x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    s = state.astype(jnp.float32) * dec[:, :, None, None] + upd
    y = jnp.einsum("bs,bnhs->bnh", c.astype(jnp.float32), s)
    return y, s


# ---------------------------------------------------------------------------
# conv + block assembly
# ---------------------------------------------------------------------------

def causal_conv(u: jax.Array, w: jax.Array,
                state: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv. u:(B,S,C) w:(K,C). Returns (out, new_state)
    where state is the trailing K-1 inputs (for decode)."""
    K = w.shape[0]
    if state is None:
        ctx = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        ctx = jnp.concatenate([state.astype(u.dtype), u], axis=1)
    out = sum(ctx[:, i:i + u.shape[1]] * w[i] for i in range(K))
    new_state = ctx[:, -(K - 1):] if K > 1 else ctx[:, :0]
    return jax.nn.silu(out.astype(jnp.float32)).astype(u.dtype), new_state


def _split_proj(cfg: ModelConfig, pset: ParamSet, lp: Dict[str, jax.Array],
                x: jax.Array):
    di, ns, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads
    zx = seg_matmul(x, lp, pset, "layers/ssm/w_zx", 0)     # (B,S,2di)
    bcdt = seg_matmul(x, lp, pset, "layers/ssm/w_bcdt", 0)  # (B,S,2ns+nh)
    z, xin = zx[..., :di], zx[..., di:]
    b, c, dt_raw = (bcdt[..., :ns], bcdt[..., ns:2 * ns], bcdt[..., 2 * ns:])
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + lp["layers/ssm/dt_bias"].astype(jnp.float32))
    return z, xin, b, c, dt


@jax.named_scope("ssm")
def ssm_forward(cfg: ModelConfig, pset: ParamSet, lp: Dict[str, jax.Array],
                x: jax.Array) -> jax.Array:
    """Training / prefill SSD block. x: (B,S,d) -> (B,S,d)."""
    B, S, _ = x.shape
    di, ns, nh, hd = (cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads,
                      cfg.ssm_head_dim)
    z, xin, b, c, dt = _split_proj(cfg, pset, lp, x)
    conv_in = jnp.concatenate([xin, b, c], axis=-1)
    conv_out, _ = causal_conv(conv_in, lp["layers/ssm/conv_w"])
    xin, b, c = (conv_out[..., :di], conv_out[..., di:di + ns],
                 conv_out[..., di + ns:])
    xh = xin.reshape(B, S, nh, hd)
    y, _ = ssd_chunk_scan(xh, dt, lp["layers/ssm/A_log"], b, c,
                          cfg.ssm_chunk)
    y = y + xh.astype(jnp.float32) * lp["layers/ssm/D"].astype(
        jnp.float32)[None, None, :, None]
    y = y.reshape(B, S, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                lp["layers/ssm/gate_norm"])
    return seg_matmul(y, lp, pset, "layers/ssm/wo", 0)


def init_ssm_cache(cfg: ModelConfig, batch: int) -> Dict[str, jax.Array]:
    L, di, ns, nh, hd = (cfg.n_layers, cfg.ssm_d_inner, cfg.ssm_state,
                         cfg.ssm_n_heads, cfg.ssm_head_dim)
    return {
        "state": jnp.zeros((L, batch, nh, hd, ns), jnp.float32),
        "conv": jnp.zeros((L, batch, CONV_K - 1, di + 2 * ns), jnp.float32),
    }


@jax.named_scope("ssm")
def ssm_decode(cfg: ModelConfig, pset: ParamSet, lp: Dict[str, jax.Array],
               x: jax.Array, cache: Dict[str, jax.Array]
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x:(B,1,d); cache: this layer's {state, conv}."""
    B = x.shape[0]
    di, ns, nh, hd = (cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_n_heads,
                      cfg.ssm_head_dim)
    z, xin, b, c, dt = _split_proj(cfg, pset, lp, x)
    conv_in = jnp.concatenate([xin, b, c], axis=-1)        # (B,1,di+2ns)
    conv_out, conv_state = causal_conv(conv_in, lp["layers/ssm/conv_w"],
                                       state=cache["conv"])
    xin, b, c = (conv_out[..., :di], conv_out[..., di:di + ns],
                 conv_out[..., di + ns:])
    y, state = ssd_decode_step(
        xin[:, 0].reshape(B, nh, hd), dt[:, 0], lp["layers/ssm/A_log"],
        b[:, 0], c[:, 0], cache["state"])
    y = y + (xin[:, 0].reshape(B, nh, hd).astype(jnp.float32)
             * lp["layers/ssm/D"].astype(jnp.float32)[None, :, None])
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                lp["layers/ssm/gate_norm"])
    out = seg_matmul(y, lp, pset, "layers/ssm/wo", 0)
    return out, {"state": state, "conv": conv_state.astype(jnp.float32)}

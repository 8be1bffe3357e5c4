"""ssd_scan — Mamba2 SSD chunk scan as a TPU Pallas kernel.

Grid: (B, nh/bh, S/Q) with the chunk dimension sequential; the running
inter-chunk state (bh, ns, hd) lives in VMEM scratch. Each grid step
computes, per head, the intra-chunk quadratic form (Q x Q attention-like
matrix, MXU work) plus the contribution of the carried state, then
updates the state — the chunk-parallel/recurrent split of the SSD paper
mapped onto the (parallel, parallel, arbitrary) TPU grid.

Layouts: x (B, S, nh, hd), dt (B, S, nh), b/c (B, S, ns), a_log (nh,)
-> y (B, S, nh, hd). Single B/C group shared by all heads (as in the
model path). The wrapper moves heads ahead of the sequence and
precomputes the dt-weighted input and log-decay, so every in-kernel
product is a plain 2-D matmul on (Q, .) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NT = (((1,), (1,)), ((), ()))     # a @ b.T


def _kernel(xd_ref, dac_ref, dar_ref, bt_ref, c_ref, y_ref, state_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    bt = bt_ref[0].astype(jnp.float32)       # (ns, Q)
    c = c_ref[0].astype(jnp.float32)         # (Q, ns)
    Q = c.shape[0]
    mask = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tril = mask.astype(jnp.float32)
    ones_q = jnp.ones((Q, Q), jnp.float32)
    ones_s = jnp.ones((bt.shape[0], Q), jnp.float32)
    cb = jnp.dot(c, bt, preferred_element_type=jnp.float32)     # (Q, Q)

    for h in range(xd_ref.shape[1]):
        xd = xd_ref[0, h]                    # (Q, hd) dt-weighted input
        # in-chunk cumulative log-decay as lower-triangular matmuls
        # (the TPU lowering has no cumsum), as a column and as a row
        csum = jnp.dot(tril, dac_ref[0, h],
                       preferred_element_type=jnp.float32)      # (Q, 1)
        csum_row = jax.lax.dot_general(
            dar_ref[0, h], tril, _NT,
            preferred_element_type=jnp.float32)                 # (1, Q)
        # mask before exp (masked diffs are positive -> inf otherwise)
        att = jnp.exp(jnp.where(mask, csum - csum_row, -jnp.inf)) * cb
        y = jnp.dot(att, xd, preferred_element_type=jnp.float32)

        # contribution of the carried state + state update
        s_prev = state_ref[h]                # (ns, hd)
        y = y + jnp.exp(csum) * jnp.dot(
            c, s_prev, preferred_element_type=jnp.float32)
        # the chunk's total log-decay, repeated down a column (matmuls
        # with ones: Mosaic cannot broadcast a (1, 1) value to a tile)
        total = jnp.dot(ones_q, dac_ref[0, h],
                        preferred_element_type=jnp.float32)     # (Q, 1)
        total_s = jnp.dot(ones_s, dac_ref[0, h],
                          preferred_element_type=jnp.float32)   # (ns, 1)
        s_new = jnp.dot(bt, xd * jnp.exp(total - csum),
                        preferred_element_type=jnp.float32)     # (ns, hd)
        state_ref[h] = s_prev * jnp.exp(total_s) + s_new
        y_ref[0, h] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "bh", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a_log: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 256, bh: int = 0,
             interpret: bool = False) -> jax.Array:
    """SSD over (B, S, nh, hd); returns y (no final state — training path).

    `bh` heads share one grid step (default: 8, or all heads when fewer
    or when 8 does not divide them)."""
    B, S, nh, hd = x.shape
    ns = b.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    bh = bh or (8 if nh % 8 == 0 else nh)
    assert nh % bh == 0, (nh, bh)
    dt = dt.astype(jnp.float32)
    dA = (dt * -jnp.exp(a_log.astype(jnp.float32))).transpose(0, 2, 1)
    xd = (x.astype(jnp.float32) * dt[..., None]).transpose(0, 2, 1, 3)
    grid = (B, nh // bh, S // Q)
    y = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, Q, hd), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, bh, Q, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, bh, 1, Q), lambda bi, hi, ci: (bi, hi, 0, ci)),
            pl.BlockSpec((1, ns, Q), lambda bi, hi, ci: (bi, 0, ci)),
            pl.BlockSpec((1, Q, ns), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, bh, Q, hd),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nh, S, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((bh, ns, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xd, dA[..., None], dA[:, :, None, :], b.transpose(0, 2, 1), c)
    return y.transpose(0, 2, 1, 3)

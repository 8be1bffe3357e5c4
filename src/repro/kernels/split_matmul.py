"""split_matmul — OSDP operator splitting (§3.3) as a TPU Pallas kernel.

The paper splits a huge MatMul into slices processed sequentially so
only one gathered slice is live. On TPU the natural granularity is the
VMEM tile: this kernel blocks x:(M,K) @ w:(K,N) on a (M/bm, N/bn, K/bk)
grid with the K dimension iterated sequentially ("arbitrary" semantics)
and an fp32 VMEM accumulator — at any instant exactly one (bk, bn)
weight tile is resident on-chip, which *is* the paper's slice-and-sum
schedule with slice_granularity = K/bk (DESIGN.md §3).

Block shapes default to MXU-aligned 512x512x512, are clamped to the
problem size, and shrink to the largest tile-aligned divisor of a
dimension they do not divide (d_ff 2816 takes 256-wide blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _fit_block(dim: int, want: int, align: int) -> int:
    """Largest block <= `want` that divides `dim`, a multiple of `align`
    unless it is `want` itself; the whole dimension when none is."""
    b = min(want, dim)
    if dim % b == 0:
        return b
    for cand in range(b - b % align, 0, -align):
        if dim % cand == 0:
            return cand
    return dim


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def split_matmul(x: jax.Array, w: jax.Array, *, bm: int = 512,
                 bn: int = 512, bk: int = 512,
                 interpret: bool = False) -> jax.Array:
    """x: (M, K) @ w: (K, N) -> (M, N); K blocked sequentially."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    # TPU tiles: rows in multiples of 16 (bf16 packing), lanes of 128
    bm, bn, bk = _fit_block(m, bm, 16), _fit_block(n, bn, 128), \
        _fit_block(k, bk, 128)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)

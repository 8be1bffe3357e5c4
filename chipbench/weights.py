"""Weights of a dense decoder, made from the seed by the benchmark.

The program never makes the weights of a cell: `canonical_specs` lists
every tensor under the program's parameter names, each whole (one array
per name, layers stacked on the leading axis), and `make` draws each
from the seed alone. The reference calls the same function again, so
both sides start from the same values without either taking them from
the other.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

Spec = Tuple[str, Tuple[int, ...], str]   # (name, shape, init)


def padded_vocab(m: dict) -> int:
    mult = m.get("vocab_pad_multiple", 256)
    return -(-m["vocab_size"] // mult) * mult


def canonical_specs(m: dict) -> List[Spec]:
    d, L, ff = m["d_model"], m["n_layers"], m["d_ff"]
    hd = m.get("head_dim") or d // m["n_heads"]
    q, kv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    ff_mult = 2 if m.get("act", "swiglu") == "swiglu" else 1
    specs: List[Spec] = [("embed/tok", (padded_vocab(m), d), "embed"),
                         ("final_norm/scale", (d,), "ones")]
    if not m.get("tie_embeddings", False):
        specs.append(("head/out", (d, padded_vocab(m)), "fan_in"))
    specs += [("layers/attn/wq", (L, d, q), "fan_in"),
              ("layers/attn/wk", (L, d, kv), "fan_in"),
              ("layers/attn/wv", (L, d, kv), "fan_in")]
    if m.get("qkv_bias", False):
        specs += [("layers/attn/bq", (L, q), "bias"),
                  ("layers/attn/bk", (L, kv), "bias"),
                  ("layers/attn/bv", (L, kv), "bias")]
    specs += [("layers/attn/wo", (L, q, d), "fan_in"),
              ("layers/attn/norm_scale", (L, d), "ones"),
              ("layers/ffn/w13", (L, d, ff_mult * ff), "fan_in"),
              ("layers/ffn/w2", (L, ff, d), "fan_in"),
              ("layers/ffn/norm_scale", (L, d), "ones")]
    return specs


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number, 64 bits and beyond."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    rest = seed >> 32
    while rest:
        key = jax.random.fold_in(key, rest & 0xFFFFFFFF)
        rest >>= 32
    return key


def _leaf(key, name: str, shape, init: str, dtype) -> jax.Array:
    if init == "ones":
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    if init == "fan_in":
        scale = 1.0 / math.sqrt(shape[-2])
    else:                       # embed, bias
        scale = 0.02
    x = jax.random.normal(k, shape, jnp.float32) * scale
    # Rounded by an explicit op: a compiler that keeps excess precision
    # may drop a bfloat16 -> float32 round trip inside one program, and
    # then a program that makes the weights and reads them back in
    # float32 would see values that no bfloat16 tensor holds.
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, fi.nexp, fi.nmant).astype(dtype)


def make(m: dict, key: jax.Array, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Every tensor, whole, in `dtype`. Trace it inside one `jax.jit`."""
    return {name: _leaf(key, name, shape, init, dtype)
            for name, shape, init in canonical_specs(m)}

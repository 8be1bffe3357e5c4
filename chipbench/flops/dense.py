"""Model FLOPs of a dense decoder (attention + gated FFN, tied or untied
head), counted from shapes.

Counted: every matmul of the forward pass (projections, FFN, LM head)
at 2 FLOPs per multiply-add, and causal attention at half of the full
square. Training is forward plus backward, three times the forward.
Not counted: recomputation, the embedding gather, norms, softmax.
"""
from __future__ import annotations


def _dims(m: dict):
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return m["d_model"], m["n_layers"], m["n_heads"], m["n_kv_heads"], hd


def matmul_params_per_layer(m: dict) -> int:
    d, _, h, kv, hd = _dims(m)
    ff_mult = 3 if m.get("act", "swiglu") == "swiglu" else 2
    return d * h * hd + 2 * d * kv * hd + h * hd * d + ff_mult * d * m["d_ff"]


def head_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"]


def attn_fwd(m: dict, seq: int) -> float:
    """QK^T and PV of one sequence through every layer, over the causal
    half of the square."""
    _, L, h, _, hd = _dims(m)
    return L * h * 4.0 * hd * seq * seq / 2


def train_step(m: dict, batch: int, seq: int) -> float:
    """Forward and backward of `batch` sequences of `seq` tokens."""
    tokens = batch * seq
    body = m["n_layers"] * matmul_params_per_layer(m) + head_params(m)
    return 3.0 * (2.0 * tokens * body + batch * attn_fwd(m, seq))

"""A configuration file, as published (`hidden_size`, ...), read into
the sizes the rest of the benchmark and the program use."""
from __future__ import annotations

FAMILIES = ("dense",)
ACT = {"silu": "swiglu"}


def program_kwargs(cfg: dict) -> dict:
    """Keyword arguments of the program's `ModelConfig` (name aside)."""
    if cfg["family"] not in FAMILIES:
        raise ValueError(f"family {cfg['family']!r}: known {FAMILIES}")
    rope = cfg.get("rope_scaling")
    if rope is not None or cfg.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("the program rotates whole heads with plain RoPE")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "family": cfg["family"],
        "n_layers": cfg["num_hidden_layers"],
        "d_model": d,
        "n_heads": h,
        "n_kv_heads": cfg["num_key_value_heads"],
        "head_dim": cfg.get("head_dim", d // h),
        "d_ff": cfg["intermediate_size"],
        "vocab_size": cfg["vocab_size"],
        "qkv_bias": cfg["qkv_bias"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "act": ACT[cfg["hidden_act"]],
        "rope": "rope",
        "rope_theta": float(cfg["rope_theta"]),
    }

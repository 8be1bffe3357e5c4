"""The Phi-3 family (Phi-4-mini): the dense decoder with partial rotary,
LongRoPE and its own RMSNorm epsilon. Found by the configuration's
`family` key, as `families/dense.py` is."""
from __future__ import annotations

import math

from chipbench import models
from chipbench.flops.dense import train_step  # noqa: F401
from chipbench.reference.phi3 import Reference  # noqa: F401
from chipbench.weights import canonical_specs, make  # noqa: F401

ROTARY_KEYS = ("rope_scaling", "partial_rotary_factor")


def program_kwargs(cfg: dict) -> dict:
    """Keyword arguments of the program's `ModelConfig` (name aside):
    the dense decoder's, with the share of each head that rotates, the
    LongRoPE short factors and attention factor, and the epsilon."""
    rope = cfg["rope_scaling"]
    if rope.get("type") != "longrope":
        raise ValueError(f"rope_scaling {rope.get('type')!r}: the phi3 "
                         f"family reads longrope")
    plain = {k: v for k, v in cfg.items() if k not in ROTARY_KEYS}
    m = models.program_kwargs(dict(plain, family="dense"))
    orig = cfg["original_max_position_embeddings"]
    factor = cfg["max_position_embeddings"] / orig
    attn = rope.get("attention_factor")
    if attn is None:        # LongRoPE's default, as the model was trained
        attn = (math.sqrt(1 + math.log(factor) / math.log(orig))
                if factor > 1 else 1.0)
    return dict(m, rope="longrope",
                rotary_fraction=cfg.get("partial_rotary_factor", 1.0),
                rope_short_factor=tuple(rope["short_factor"]),
                rope_original_max=orig, rope_attention_factor=attn,
                norm_eps=cfg["rms_norm_eps"])

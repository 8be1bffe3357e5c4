"""phi4-mini-3.8b — Phi-4-mini. [hf:microsoft/Phi-4-mini-instruct]
(report arXiv:2503.01743)

Dense decoder: GQA (24 query heads over 8 kv heads of 128), SwiGLU,
200k vocabulary with the head tied to the embedding, RMSNorm at 1e-5.
The rotary embedding turns the first 96 of each head's 128 dims
(`partial_rotary_factor` 0.75) with LongRoPE: within the 4096 positions
of `original_max_position_embeddings` the short factors apply, and cos
and sin carry the attention factor sqrt(1 + ln(131072 / 4096) /
ln(4096)) = sqrt(17 / 12). The short factors are taken as all 1.0; the
long factors of longer contexts are not held, so the program refuses
more than 4096 positions.
"""
import math

from repro.configs.base import DENSE, ModelConfig

MAX_POSITIONS, ORIGINAL_MAX = 131072, 4096

CONFIG = ModelConfig(
    name="phi4-mini-3.8b",
    family=DENSE,
    n_layers=32,
    d_model=3072,
    n_heads=24,
    n_kv_heads=8,
    d_ff=8192,
    vocab_size=200064,
    act="swiglu",
    rope="longrope",
    rope_theta=10_000.0,
    rotary_fraction=0.75,
    rope_short_factor=(1.0,) * 48,
    rope_original_max=ORIGINAL_MAX,
    rope_attention_factor=math.sqrt(
        1 + math.log(MAX_POSITIONS / ORIGINAL_MAX) / math.log(ORIGINAL_MAX)),
    norm_eps=1e-5,
    tie_embeddings=True,
    source="[hf:microsoft/Phi-4-mini-instruct]",
)

"""FLOP counts of the benchmark against hand counts."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from chipbench import models  # noqa: E402
from chipbench.flops import dense  # noqa: E402


def model(name):
    with open(ROOT / "chipbench" / "configs" / f"{name}.json") as f:
        return models.program_kwargs(json.load(f))


def test_qwen_train_step_by_hand():
    m = model("qwen1.5-0.5b")
    # per layer: q, k, v, o of 1024 x 1024, gated FFN 3 x 1024 x 2816
    per_layer = 4 * 1024 * 1024 + 3 * 1024 * 2816
    body = 24 * per_layer + 1024 * 151936          # tied head as a matmul
    assert dense.matmul_params_per_layer(m) == per_layer
    attn = 24 * 16 * 4 * 64 * 4096 * 4096 / 2      # causal half, fwd
    want = 3 * (2 * 8192 * body + 2 * attn)
    assert dense.train_step(m, 2, 4096) == pytest.approx(want, rel=1e-12)
    # rounded: 6 x 464 M x 8192, plus 4.95e12 of causal attention
    assert dense.train_step(m, 2, 4096) == pytest.approx(2.78e13, rel=0.01)
    assert 3 * 2 * attn == pytest.approx(4.95e12, rel=0.01)


def test_grouped_query_train_step_by_hand():
    # Phi-4-mini's widths at 8 layers: q and o are 3072 x 3072, k and v
    # 3072 x 1024 (8 of 24 heads), untied head
    m = dict(d_model=3072, n_layers=8, n_heads=24, n_kv_heads=8,
             head_dim=128, d_ff=8192, vocab_size=200064, act="swiglu")
    per_layer = 2 * 3072 * 3072 + 2 * 3072 * 1024 + 3 * 3072 * 8192
    assert dense.matmul_params_per_layer(m) == per_layer
    body = 8 * per_layer + 3072 * 200064
    attn = 8 * 24 * 4 * 128 * 4096 * 4096 / 2
    want = 3 * (2 * 16384 * body + 4 * attn)
    assert dense.train_step(m, 4, 4096) == pytest.approx(want, rel=1e-12)
    assert dense.train_step(m, 4, 4096) == pytest.approx(1.50e14, rel=0.02)

"""The traffic generator: a seed fixes the inputs, and every seed gets
the same amount of work."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import gen  # noqa: E402

TRAFFIC = ROOT / "chipbench" / "traffic"
BIG = 2**33 + 12345          # seeds are wider than 32 bits


def mix(name):
    with open(TRAFFIC / f"{name}.json") as f:
        return json.load(f)


def test_train_batches_repeat_for_a_seed_and_differ_by_index():
    m = dict(mix("train_2x4096"), seq=256)
    a = gen.train_batch(m, 151936, BIG, 0)
    b = gen.train_batch(m, 151936, BIG, 0)
    c = gen.train_batch(m, 151936, BIG, 1)
    d = gen.train_batch(m, 151936, BIG + 1, 0)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == (2, 256) and a["tokens"].dtype == np.int32
    np.testing.assert_array_equal(a["labels"][:, :-1], a["tokens"][:, 1:])
    assert (a["tokens"] != c["tokens"]).mean() > 0.9
    assert (a["tokens"] != d["tokens"]).mean() > 0.9
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 151936

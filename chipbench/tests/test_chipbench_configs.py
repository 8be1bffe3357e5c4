"""The benchmark's files: configurations at their published widths, and
a BENCHMARK.json whose every name leads to a file."""
import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402

from chipbench import models  # noqa: E402

HERE = ROOT / "chipbench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def load(path):
    with open(path) as f:
        return json.load(f)


BENCH = load(ROOT / "BENCHMARK.json")

PUBLISHED = {
    "qwen1.5-0.5b": dict(hidden_size=1024, intermediate_size=2816,
                         num_attention_heads=16, num_key_value_heads=16,
                         num_hidden_layers=24, vocab_size=151936,
                         rope_theta=1e6, rms_norm_eps=1e-6,
                         tie_word_embeddings=True),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_config_holds_published_widths(name):
    cfg = load(HERE / "configs" / f"{name}.json")
    for k, v in PUBLISHED[name].items():
        assert cfg[k] == v, k
    for conf in BENCH["configs"]:
        if conf["name"] == name:
            assert conf["file"] == f"chipbench/configs/{name}.json"
            assert cfg["reduced"] == conf["reduced"]
            assert cfg["source"] == conf["source"]
    for k in cfg["reduced"]:
        assert k in cfg.get("published", {}), k
        assert not k.endswith(("_dim", "_rank", "_size")), k


def test_program_config_from_file():
    from repro.configs.base import ModelConfig
    m = models.program_kwargs(load(HERE / "configs" / "qwen1.5-0.5b.json"))
    cfg = ModelConfig(name="qwen", **m)
    cfg.validate()
    assert cfg.resolved_head_dim == 64 and cfg.qkv_bias
    # 464 M parameters with the vocabulary padded to 152064
    assert cfg.param_count() == pytest.approx(464e6, rel=0.01)


def test_benchmark_names_lead_to_files():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200
        assert (HERE / "traffic" / f"{w['traffic']}.json").exists()
        assert (HERE / "limits" / f"{w['name']}.json").exists()
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, len(BENCH["workloads"]) // 2)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert (HERE / "metrics" / f"{m['name']}.py").exists()
        for cell in m["workloads"]:
            moved = next(e for e in BENCH["end_to_end"]
                         if e["name"] == m["moves"])
            assert cell in moved.get("workloads", [cell])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_reports_setup_and_one_more_of_each():
    sys.path.insert(0, str(HERE))
    from chipbench.run import metric_rows
    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in metric_rows(BENCH, w["name"],
                                                "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert metric_rows(BENCH, w["name"], "per_layer")

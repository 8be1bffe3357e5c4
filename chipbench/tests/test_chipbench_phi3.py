"""The Phi-3 family at a tiny Phi-shaped size on the CPU: partial rotary
(0.75 of each head), grouped-query attention in groups of 3, a tied
head, RMSNorm at 1e-5, and LongRoPE short factors that are not all 1,
so that each is seen.

- the program's loss and gradients against `reference/phi3.py`, on one
  device and on a (4, 1) mesh of host devices under the searched plan
  and under forced ZDP;
- the reference's and the program's rotary against the installed
  `transformers` Phi-3 rotary, an independent reading of the equations;
- whole runs of a tiny four-chip train cell, sound and with a fault
  planted, judged under the cell's limits;
- the configuration file, and the collective-time reduction.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import collectives, gen, trace as tr, weights  # noqa: E402
from chipbench.drive_train import merged_norms, to_program  # noqa: E402
from chipbench.families import phi3  # noqa: E402
from chipbench.reference.phi3 import Reference  # noqa: E402

HERE = ROOT / "chipbench"
CELL = "phi4mini-L8-osdp4-train-4k"
SHORT = [1.0, 1.0, 1.25, 1.5, 2.0, 3.0]
# 6 heads of 16 over 2 kv heads; 12 of each head's 16 dims rotate; a
# memory limit under which the plan mixes DP and ZDP on four chips
TINY = {"name": "tiny-phi3", "family": "phi3", "hidden_size": 96,
        "intermediate_size": 128, "num_attention_heads": 6,
        "num_hidden_layers": 2, "num_key_value_heads": 2,
        "vocab_size": 300, "hidden_act": "silu", "rms_norm_eps": 1e-5,
        "rope_theta": 1e4, "tie_word_embeddings": True, "qkv_bias": False,
        "partial_rotary_factor": 0.75,
        "max_position_embeddings": 131072,
        "original_max_position_embeddings": 4096,
        "rope_scaling": {"type": "longrope", "short_factor": SHORT},
        "deployment": {"memory_limit_gib": 0.0015}}
SEED = 2**36 + 5


def load(path):
    with open(path) as f:
        return json.load(f)


def program(m, B=2, S=64):
    from repro.configs import (MeshConfig, ModelConfig, OSDPConfig,
                               RunConfig, ShapeConfig)
    from repro.core.plan import make_plan
    from repro.models.registry import build_model
    cfg = ModelConfig(name="tiny", **m)
    run = RunConfig(model=cfg, shape=ShapeConfig("t", S, B, "train"),
                    mesh=MeshConfig((1, 1), ("data", "model")),
                    osdp=OSDPConfig())
    return build_model(run, make_plan(run))


# -- the configuration -----------------------------------------------------------

def test_config_holds_published_widths():
    cfg = load(HERE / "configs" / "phi-4-mini.json")
    published = dict(hidden_size=3072, intermediate_size=8192,
                     num_attention_heads=24, num_key_value_heads=8,
                     vocab_size=200064, rope_theta=1e4, rms_norm_eps=1e-5,
                     partial_rotary_factor=0.75, tie_word_embeddings=True,
                     max_position_embeddings=131072,
                     original_max_position_embeddings=4096)
    for k, v in published.items():
        assert cfg[k] == v, k
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32}
    assert cfg["num_hidden_layers"] == 8
    bench = load(ROOT / "BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == "phi-4-mini")
    assert conf["file"] == "chipbench/configs/phi-4-mini.json"
    assert conf["reduced"] == cfg["reduced"]
    assert conf["source"] == cfg["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["config"] == "phi-4-mini"
    assert load(HERE / "traffic" / f"{cell['traffic']}.json")["kind"] \
        == "train_sharded"


def test_program_config_from_file():
    from repro.configs.base import ModelConfig
    m = phi3.program_kwargs(load(HERE / "configs" / "phi-4-mini.json"))
    cfg = ModelConfig(name="phi", **m)
    cfg.validate()
    assert cfg.resolved_head_dim == 128 and cfg.rotary_dim == 96
    assert cfg.n_heads // cfg.n_kv_heads == 3 and cfg.tie_embeddings
    assert cfg.rope_attention_factor == pytest.approx((17 / 12) ** 0.5,
                                                      rel=1e-12)
    assert cfg.norm_eps == 1e-5 and cfg.max_positions == 4096
    # 8 layers of 100.66 M matmul parameters and the 200192 x 3072 tied
    # embedding: 1.42 B
    assert cfg.param_count() == pytest.approx(1.4204e9, rel=1e-3)


def test_program_refuses_positions_past_the_short_factors():
    m = phi3.program_kwargs(TINY)
    built = program(m, S=4097)
    params = built.init(jax.random.PRNGKey(0))
    batch = gen.train_batch({"batch": 1, "seq": 4097}, 300, 1, 0)
    with pytest.raises(ValueError, match="4097 positions"):
        jax.eval_shape(built.model.loss_fn, params, batch)
    ref = Reference(m)
    with pytest.raises(ValueError, match="4097 positions"):
        ref.rope(jnp.zeros((1, 4097, 2, 16)), jnp.arange(4097))


# -- the program against the reference, one device ---------------------------------

def test_loss_and_gradients_match_reference():
    m = phi3.program_kwargs(TINY)
    built = program(m)
    key = weights.seed_key(SEED)
    params = to_program(weights.make(m, key), built)
    ref_params = {k: v.astype(jnp.float32)
                  for k, v in weights.make(m, key).items()}
    batch = gen.train_batch({"batch": 2, "seq": 64}, 300, 5, 0)
    (loss, _), grads = jax.value_and_grad(built.model.loss_fn,
                                          has_aux=True)(params, batch)
    ref = Reference(m, q_block=16, ce_block=16)
    rloss, rgrads = jax.value_and_grad(ref.loss)(
        ref_params, batch["tokens"], batch["labels"])
    assert float(loss) == pytest.approx(float(rloss), rel=2e-3)
    g, rg = merged_norms(grads), merged_norms(rgrads)
    for k in rg:
        assert g[k] == pytest.approx(rg[k], rel=0.05, abs=1e-3), k
    # whole gradients of the attention weights, against the reference's
    # and against the plain dense reference's (whole heads rotated, no
    # factors), which the program must not be near
    from chipbench.reference.dense import Reference as Dense
    _, pgrads = jax.value_and_grad(
        Dense(m, q_block=16, ce_block=16, eps=1e-5).loss)(
            ref_params, batch["tokens"], batch["labels"])

    def gap(a, k):
        d = a[k].astype(jnp.float32) - rgrads[k]
        return float(jnp.linalg.norm(d) / jnp.linalg.norm(rgrads[k]))
    for k in ("layers/attn/wq", "layers/attn/wk"):
        assert gap(grads, k) < 0.05, k
        assert gap(pgrads, k) > 10 * gap(grads, k), k


# -- the rotary against transformers' Phi-3 ------------------------------------------

def hf_rotary(q, k, positions):
    """q: (B, H, S, hd), k: (B, KV, S, hd) numpy -> rotated, by HF."""
    os.environ.setdefault("USE_TF", "0")
    os.environ.setdefault("USE_FLAX", "0")
    torch = pytest.importorskip("torch")
    modeling = pytest.importorskip(
        "transformers.models.phi3.modeling_phi3")
    from transformers import Phi3Config
    cfg = Phi3Config(
        hidden_size=TINY["hidden_size"],
        num_attention_heads=TINY["num_attention_heads"],
        num_key_value_heads=TINY["num_key_value_heads"],
        partial_rotary_factor=TINY["partial_rotary_factor"],
        max_position_embeddings=TINY["max_position_embeddings"],
        original_max_position_embeddings=TINY[
            "original_max_position_embeddings"],
        rope_theta=TINY["rope_theta"],
        rope_scaling={"type": "longrope", "short_factor": SHORT,
                      "long_factor": [4.0] * len(SHORT)})
    rot = modeling.Phi3RotaryEmbedding(cfg)
    pos = torch.as_tensor(positions)[None]
    qt, kt = torch.as_tensor(q), torch.as_tensor(k)
    cos, sin = rot(qt, pos)
    qo, ko = modeling.apply_rotary_pos_emb(qt, kt, cos, sin)
    return qo.numpy(), ko.numpy()


@pytest.fixture(scope="module")
def heads():
    rng = np.random.default_rng(7)
    S = 200
    q = rng.standard_normal((2, 6, S, 16)).astype(np.float32)
    k = rng.standard_normal((2, 2, S, 16)).astype(np.float32)
    # positions spread over the short factors' whole range
    positions = np.sort(rng.choice(4096, S, replace=False))
    return q, k, positions, hf_rotary(q, k, positions)


def test_reference_rotary_matches_transformers(heads):
    q, k, positions, (hq, hk) = heads
    ref = Reference(phi3.program_kwargs(TINY))
    pos = jnp.asarray(positions)
    for x, want in ((q, hq), (k, hk)):
        got = ref.rope(jnp.asarray(x).transpose(0, 2, 1, 3), pos)
        np.testing.assert_allclose(np.asarray(got).transpose(0, 2, 1, 3),
                                   want, rtol=2e-5, atol=2e-5)


def test_program_rotary_matches_transformers(heads):
    from repro.configs.base import ModelConfig
    from repro.models.common import rotate
    q, k, positions, (hq, hk) = heads
    cfg = ModelConfig(name="tiny", **phi3.program_kwargs(TINY))
    pos = jnp.broadcast_to(jnp.asarray(positions), (2, len(positions)))
    for x, want in ((q, hq), (k, hk)):
        got = rotate(cfg, jnp.asarray(x).transpose(0, 2, 1, 3), pos)
        np.testing.assert_allclose(np.asarray(got).transpose(0, 2, 1, 3),
                                   want, rtol=2e-5, atol=2e-5)
    # the last quarter of each head passes through untouched
    got = rotate(cfg, jnp.asarray(q).transpose(0, 2, 1, 3), pos)
    np.testing.assert_array_equal(
        np.asarray(got)[..., 12:], q.transpose(0, 2, 1, 3)[..., 12:])


# -- four host devices  ---------------------------------------------------------------

def unreduced(ctx, timed):
    """The step with the exchange left out: each chip steps with the
    gradient of its own rows. A tensor split over the chips moves, shard
    by shard, by the gradient of the rows of the chip that holds the
    shard; a tensor every chip holds whole moves by that of the first
    chip's rows, whose copy is the one a run reads. The loss reported is
    the first chip's."""
    from repro.optim import AdamWConfig, apply_update, warmup_cosine
    prog, opt_cfg = ctx.program, ctx.mix["optimizer"]
    cfg = AdamWConfig(**{k: opt_cfg[k] for k in (
        "lr", "b1", "b2", "eps", "weight_decay", "grad_clip")})
    model, psh = prog.built.model, prog.psh
    n = prog.mesh.shape["data"]

    def owned(name, shape, k):
        for a, part in enumerate(psh[name].spec):
            if part == "data" or (isinstance(part, tuple) and "data" in part):
                mine = jnp.arange(shape[a]) // (shape[a] // n) == k
                return mine.reshape([-1 if b == a else 1
                                     for b in range(len(shape))])
        return k == 0

    def step(params, opt, batch):
        labels = batch["labels"]
        chip = jnp.arange(labels.shape[0]) // (labels.shape[0] // n)

        def local(k, carry):
            acc, first = carry
            (loss, _), g = jax.value_and_grad(model.loss_fn, has_aux=True)(
                params, dict(batch, labels=jnp.where(
                    (chip == k)[:, None], labels, -1)))
            acc = {name: jnp.where(owned(name, g[name].shape, k), g[name],
                                   acc[name]) for name in acc}
            return acc, jnp.where(k == 0, loss, first)

        grads, loss = jax.lax.fori_loop(
            0, n, local, (jax.tree.map(jnp.zeros_like, params),
                          jnp.zeros((), jnp.float32)))
        scale = warmup_cosine(opt.step + 1, opt_cfg["warmup"],
                              opt_cfg["total_steps"])
        params, opt, met = apply_update(cfg, params, grads, opt, scale)
        return params, opt, dict(met, loss=loss)

    return jax.jit(step, in_shardings=(psh, prog.osh, None),
                   out_shardings=(psh, prog.osh, None),
                   donate_argnums=(0, 1))


SHARDED = """
import json, os, pathlib, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
from chipbench import gen, run, weights
from chipbench.drive_train import merged_norms
from chipbench.drive_train_sharded import Program
from chipbench.families import phi3
from chipbench.reference.phi3 import Reference
sys.path.insert(0, {tests!r})
from test_chipbench_faults import half_batch, unchanged
from test_chipbench_phi3 import unreduced
from chipbench.control_sharded import readings

TINY = json.loads({tiny!r})
files = pathlib.Path({files!r})
m = phi3.program_kwargs(TINY)

# the loss and gradients of one step on the (4, 1) mesh
batch = gen.train_batch({{"batch": 4, "seq": 64}}, 300, 5, 0)
key = weights.seed_key({seed})
ref = Reference(m, q_block=16, ce_block=16)
rp = {{k: v.astype(jnp.float32) for k, v in weights.make(m, key).items()}}
rloss, rgrads = jax.value_and_grad(ref.loss)(rp, batch["tokens"],
                                             batch["labels"])
rg = merged_norms(rgrads)
for mode in (None, "ZDP"):
    prog = Program(TINY, m, {{"batch": 4, "seq": 64}}, jax.devices(),
                   force_mode=mode)
    params, _ = prog.make_state(phi3, m, {seed})
    with jax.set_mesh(prog.mesh):
        (loss, _), grads = jax.jit(jax.value_and_grad(
            prog.built.model.loss_fn, has_aux=True))(params,
                                                     prog.put(batch))
    print(json.dumps({{"kind": "grads", "plan": mode or "searched",
                      "modes": prog.modes(), "loss": float(loss),
                      "ref_loss": float(rloss), "grad": merged_norms(grads),
                      "ref_grad": rg,
                      "split": sum(len(a.sharding.device_set) == 4 and
                                   a.addressable_shards[0].data.size * 4
                                   == a.size for a in params.values())}}),
          flush=True)

faults = {{"sound": None, "unchanged": unchanged, "half_batch": half_batch,
          "unreduced": unreduced}}
bench = {{"configs": [{{"name": "tiny", "file": str(files / "tiny.json")}}],
         "workloads": [{{"name": "t", "config": "tiny", "traffic": "t",
                        "chips": 4}}],
         "end_to_end": [{{"name": "train_tokens_per_s", "unit": "x"}},
                        {{"name": "setup_s", "unit": "s"}}],
         "per_layer": []}}
for name, fault in faults.items():
    class Ctx(run.Context):
        pass
    Ctx.files = files
    if fault is not None:
        Ctx.planted = lambda self, timed, fault=fault: fault(self, timed)
    out = run.execute(bench, "t", {seed}, 1.0, False, jax.devices(),
                      context_cls=Ctx)
    print(json.dumps({{"kind": "run", "fault": name,
                      "correct": out["correct"], "checks": out["checks"],
                      "modes": out["detail"]["plan_modes"],
                      "census": out["detail"]["census"]}}), flush=True)

# the readings that set the cell's limits, one seed with its faults
class Ctx(run.Context):
    pass
Ctx.files = files
ctx = Ctx(bench, bench["workloads"][0], 0, 0.0, False, jax.devices())
readings(ctx, [{seed}], {{{seed}}})
"""


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    """One subprocess with four host devices: one step's loss and
    gradients under the searched plan and forced ZDP, then whole runs of
    a tiny four-chip cell, sound and with each fault planted."""
    d = tmp_path_factory.mktemp("chipbench_phi3")
    (d / "traffic").mkdir()
    (d / "limits").mkdir()
    (d / "tiny.json").write_text(json.dumps(TINY))
    mix = dict(load(HERE / "traffic" / "train_4x4096_osdp4.json"), seq=64,
               pool=2)
    (d / "traffic" / "t.json").write_text(json.dumps(mix))
    (d / "limits" / "t.json").write_text(
        (HERE / "limits" / f"{CELL}.json").read_text())
    code = SHARDED.format(root=str(ROOT), src=str(ROOT / "src"),
                          tests=str(HERE / "tests"),
                          tiny=json.dumps(TINY), files=str(d), seed=SEED)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    rows = [json.loads(x) for x in r.stdout.splitlines()
            if x.startswith("{")]
    return {(x.get("kind", "reading"),
             x.get("plan", x.get("fault", x.get("variant")))): x
            for x in rows}


@pytest.mark.parametrize("plan", ["searched", "ZDP"])
def test_sharded_loss_and_gradients_match_reference(sharded, plan):
    row = sharded[("grads", plan)]
    # the searched plan mixes DP and ZDP under the tiny limit, over the
    # operators that hold weights OSDP may split
    modes = {m for op in ("embed.tok", "layers.attn_qkv", "layers.attn_out",
                          "layers.ffn_w13", "layers.ffn_w2")
             for m in row["modes"][op]}
    assert modes == ({"ZDP"} if plan == "ZDP" else {"DP", "ZDP"}), modes
    assert row["split"] > 0
    assert row["loss"] == pytest.approx(row["ref_loss"], rel=2e-3)
    for k, want in row["ref_grad"].items():
        assert row["grad"][k] == pytest.approx(want, rel=0.05, abs=1e-3), k


def test_sharded_sound_run_is_correct(sharded):
    row = sharded[("run", "sound")]
    assert row["correct"], row["checks"]
    assert row["census"]["split"] > 0
    assert sum(row["census"]["per_device"]) < 4 * row["census"][
        "total_bytes"]


@pytest.mark.parametrize("fault", ["unreduced", "unchanged", "half_batch"])
def test_sharded_fault_is_not_correct(sharded, fault):
    row = sharded[("run", fault)]
    assert not row["correct"], row["checks"]


@pytest.mark.parametrize("variant,correct", [
    ("sound", True), ("control", False), ("half_batch", False),
    ("first_rows", False)])
def test_limit_readings(sharded, variant, correct):
    """`control_sharded.readings` at the tiny size: the program sound,
    the float8 reference, half the batch and the first chip's rows
    alone each judged under the cell's limits."""
    row = sharded[("reading", variant)]
    assert row["correct"] is correct, row["numbers"]


# -- the collective-time reduction -----------------------------------------------------

def test_collective_time_and_its_exposed_part():
    """One device, a window of 10 ms: a `while` spanning 1-9 ms holds a
    fusion (1-3), a synchronous all-gather (3-4), an all-reduce started
    at 4 and done at 7 with a fusion (4.2-6) between, and a last fusion
    (7-9). In flight 3-7; alone 3-4.2 and 6-7."""
    ms = 1e-3
    ops = [(1 * ms, 9 * ms, "%while.1 = (s32[]) while(...)"),
           (1 * ms, 3 * ms, "%fusion.2 = bf16[8] fusion(...)"),
           (3 * ms, 4 * ms, "%all-gather.3 = bf16[32] all-gather(...)"),
           (4 * ms, 4.2 * ms,
            "%all-reduce-start.4 = f32[8] all-reduce-start(...)"),
           (4.2 * ms, 6 * ms, "%fusion.5 = bf16[8] fusion(...)"),
           (6 * ms, 7 * ms,
            "%all-reduce-done.4 = f32[8] all-reduce-done(...)"),
           (7 * ms, 9 * ms, "%fusion.6 = bf16[8] fusion(...)")]
    trace = tr.Trace([tr.Device("/device:TPU:0", ops),
                      tr.Device("/device:TPU:1", ops[:2])],
                     [(0.0, 10 * ms, "cb.window")])
    assert collectives.per_device(trace) == [
        (pytest.approx(4 * ms), pytest.approx(2.2 * ms)), (0.0, 0.0)]
    assert not collectives.is_collective("%fusion.2 = bf16[8] fusion(...)")
    facts = {"kind": "train", "steps": 2}
    from chipbench.run import load_reader
    assert load_reader("collective_ms.train")(facts, trace) == \
        pytest.approx(2.0)
    assert load_reader("exposed_collective_ms.train")(facts, trace) == \
        pytest.approx(1.1)
    assert load_reader("collective_ms.train")(
        {"kind": "train", "steps": 0}, trace) is None


def test_repeated_async_pairs_stay_apart():
    """The same start/done names in two iterations of a loop (1-2.5 ms
    and 6-7.5 ms, fusions between): each done closes its own start, so
    the pairs do not merge into one interval from 1 to 7.5 ms. In
    flight 3 ms; alone 1-1.2, 2-2.5, 6-6.2 and 7-7.5."""
    ms = 1e-3
    start = "%collective-permute-start.4 = bf16[8] collective-permute-start(...)"
    done = "%collective-permute-done.4 = bf16[8] collective-permute-done(...)"
    ops = [(1 * ms, 1.2 * ms, start),
           (1.2 * ms, 2 * ms, "%fusion.5 = bf16[8] fusion(...)"),
           (2 * ms, 2.5 * ms, done),
           (2.5 * ms, 6 * ms, "%fusion.6 = bf16[8] fusion(...)"),
           (6 * ms, 6.2 * ms, start),
           (6.2 * ms, 7 * ms, "%fusion.5 = bf16[8] fusion(...)"),
           (7 * ms, 7.5 * ms, done),
           (7.5 * ms, 9 * ms, "%fusion.7 = bf16[8] fusion(...)")]
    trace = tr.Trace([tr.Device("/device:TPU:0", ops)],
                     [(0.0, 10 * ms, "cb.window")])
    assert collectives.per_device(trace) == [
        (pytest.approx(3 * ms), pytest.approx(1.4 * ms))]

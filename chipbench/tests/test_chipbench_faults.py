"""A run with the timed path broken underneath must come out not
correct, and the control must too. These drive the whole harness at a
tiny size on the CPU (the look for a chip is the one step skipped),
against the limits of the real cells."""
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

from chipbench import run  # noqa: E402

HERE = ROOT / "chipbench"
TINY = {"name": "tiny", "family": "dense", "hidden_size": 64,
        "intermediate_size": 96, "num_attention_heads": 4,
        "num_hidden_layers": 2, "num_key_value_heads": 2,
        "vocab_size": 300, "hidden_act": "silu", "rms_norm_eps": 1e-6,
        "rope_theta": 1e4, "tie_word_embeddings": True, "qkv_bias": True,
        "deployment": {"memory_limit_gib": 15.75}}
SEED = 2**34 + 77


def load(path):
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    d = tmp_path_factory.mktemp("chipbench")
    (d / "traffic").mkdir()
    (d / "limits").mkdir()
    (d / "tiny.json").write_text(json.dumps(TINY))
    train = dict(load(HERE / "traffic" / "train_2x4096.json"), seq=64,
                 pool=2, batch=4)
    (d / "traffic" / "train.json").write_text(json.dumps(train))
    (d / "limits" / "train.json").write_text(
        (HERE / "limits" / "qwen05b-train-4k.json").read_text())
    return d


def execute(files, planted=None):
    """A whole run of the tiny train cell; `planted(ctx, timed)` breaks
    what the window drives."""
    class Ctx(run.Context):
        pass
    Ctx.files = files
    if planted is not None:
        Ctx.planted = lambda self, timed: planted(self, timed)
    bench = {"configs": [{"name": "tiny", "file": str(files / "tiny.json")}],
             "workloads": [{"name": "train", "config": "tiny",
                            "traffic": "train", "chips": 1}],
             "end_to_end": [{"name": "train_tokens_per_s", "unit": "x"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    return run.execute(bench, "train", SEED, 2.0, False, jax.devices(),
                       context_cls=Ctx)


def unchanged(ctx, step):
    """A step that returns its state unchanged."""
    def f(params, opt, batch):
        keep = jax.tree.map(jnp.copy, (params, opt))
        _, _, met = step(params, opt, batch)
        return (*keep, met)
    return f


def half_batch(ctx, step):
    """Half of the batch left out, the mean taken over the rest."""
    def f(params, opt, batch):
        half = batch["labels"].shape[0] // 2
        return step(params, opt, dict(
            batch, labels=batch["labels"].at[half:].set(-1)))
    return f


def control(ctx, step):
    """The float8 reference in the program's place: its loss and
    gradient from the program's float32 masters, through the program's
    own AdamW."""
    from repro.optim import AdamWConfig, apply_update, warmup_cosine
    oc = ctx.mix["optimizer"]
    cfg = AdamWConfig(**{k: oc[k] for k in (
        "lr", "b1", "b2", "eps", "weight_decay", "grad_clip")})
    ref = ctx.family.Reference(ctx.model, lowp=jnp.float8_e4m3fn,
                               eps=ctx.cfg["rms_norm_eps"])

    @jax.jit
    def f(params, opt, batch):
        loss, grads = jax.value_and_grad(ref.loss)(
            opt.master, batch["tokens"], batch["labels"])
        scale = warmup_cosine(opt.step + 1, oc["warmup"], oc["total_steps"])
        params, opt, met = apply_update(cfg, params, grads, opt, scale)
        return params, opt, dict(met, loss=loss)
    return f


def test_sound_train_run_is_correct(files):
    out = execute(files)
    assert out["correct"], out["checks"]


@pytest.mark.parametrize("fault", [unchanged, half_batch, control])
def test_broken_train_step_is_not_correct(files, fault):
    out = execute(files, fault)
    assert not out["correct"], out["checks"]

"""The scope reduction (`scopes.py`): self time of nested device ops,
`op_name` inherited from the op around, the step's module, the pass and
scope of real `op_name`s (taken from the qwen1.5-0.5b train step compiled
for a v5e), the readers without a scope map, the module events of a trace
recorded on a v5e chip, and the scoped run's hooks at a tiny size on the
CPU."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import pytest  # noqa: E402

from chipbench import run, scopes, scoped_run, trace as tr  # noqa: E402
from chipbench.tests.test_chipbench_faults import SEED, files  # noqa: E402,F401

# op_names of the qwen1.5-0.5b train step compiled for a v5e
FORWARD = ("jit(step)/jvp()/while/body/closed_call/attention/out/mul")
FORWARD_SCAN = ("jit(step)/attention/core/closed_call/while/body/"
                "closed_call/closed_call/while")
BACKWARD_LOSS = "jit(step)/transpose(jvp(loss))/div"
BACKWARD_LAYERS = "jit(step)/transpose(jvp())/while"
BACKWARD_ATTN = ("jit(step)/transpose(jvp())/while/body/closed_call/"
                 "checkpoint/attention/core/while")
RECOMPUTE = ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
             "rematted_computation/attention/qkv/add")
RECOMPUTE_LOSS = ("jit(step)/transpose(jvp(loss))/while/body/closed_call/"
                  "checkpoint/rematted_computation/ge")
EMBED_GRAD = "jit(step)/transpose(jvp(embed))/jit(_take)/scatter-add"
OPTIMIZER = "jit(step)/optimizer/lt"


@pytest.mark.parametrize("op_name,expected", [
    (FORWARD, ("forward", "attention/out")),
    (FORWARD_SCAN, ("forward", "attention/core")),
    ("jit(step)/jvp(loss)/max", ("forward", "loss")),
    ("jit(step)/jvp()/while/body/closed_call/norm/mul;reshape",
     ("forward", "norm")),
    (BACKWARD_LOSS, ("backward", "loss")),
    (BACKWARD_LAYERS, ("backward", "none")),
    (BACKWARD_ATTN, ("backward", "attention/core")),
    (EMBED_GRAD, ("backward", "embed")),
    (RECOMPUTE, ("recompute", "attention/qkv")),
    (RECOMPUTE_LOSS, ("recompute", "loss")),
    (OPTIMIZER, ("optimizer", "optimizer")),
    ("", ("unattributed", "none")),
    (None, ("unattributed", "none")),
])
def test_classify(op_name, expected):
    assert scopes.classify(op_name) == expected


def ev(name, op="fusion"):
    """A trace event's name: the instruction's text."""
    return f"%{name} = bf16[2,4096,1024]{{2,1,0}} {op}(%p.1)"


def scoped_trace(window=(0.0, 20.0)):
    """A `while` of the step holding a fusion and an unnamed copy, a
    fusion after it, and an op of another module."""
    d = scopes.Device("/device:TPU:0", ops=[
        (0.0, 10.0, ev("while.1", "while")),
        (1.0, 4.0, ev("fusion.2")),
        (5.0, 6.0, ev("copy.3", "copy")),
        (10.0, 12.0, ev("fusion.4")),
        (15.0, 16.0, ev("fusion.2")),
    ], modules=[(0.0, 12.0, "jit_step(77)"), (14.5, 16.5, "jit_other(5)")])
    return tr.Trace([d], [(window[0], window[1], "cb.window")])


NAMES = {"while.1": BACKWARD_LAYERS, "fusion.2": BACKWARD_ATTN,
         "fusion.4": OPTIMIZER}


def test_self_time_of_nested_ops():
    rows = scopes.self_times(scoped_trace().devices[0].ops, 0.0, 20.0)
    own = {scopes.instruction(r[2]): r[3] for r in rows[:4]}
    assert own == {"while.1": pytest.approx(6.0),
                   "fusion.2": pytest.approx(3.0),
                   "copy.3": pytest.approx(1.0),
                   "fusion.4": pytest.approx(2.0)}
    parents = [r[4] for r in rows]
    assert parents == [None, 0, 0, None, None]
    # self times add up to the busy time
    assert sum(r[3] for r in rows) == pytest.approx(13.0)


def test_inner_op_ends_with_its_parent():
    rows = scopes.self_times([(0.0, 4.0, ev("while.1", "while")),
                              (3.0, 4.000001, ev("fusion.2"))], 0.0, 9.0)
    assert rows[1][1] == 4.0
    assert sum(r[3] for r in rows) == pytest.approx(4.0)


def test_scope_times_inherit_and_keep_to_the_step():
    times = scopes.scope_times(scoped_trace(), NAMES, "jit_step")
    assert times == {
        # the while's own time and the copy inside it, which has no op_name
        ("backward", "none"): pytest.approx(7.0),
        ("backward", "attention/core"): pytest.approx(3.0),
        ("optimizer", "optimizer"): pytest.approx(2.0),
        # the same instruction run by another module
        (scopes.OTHER_MODULES, "none"): pytest.approx(1.0),
    }
    # clipped to the window
    clipped = scopes.scope_times(scoped_trace(window=(2.0, 11.0)), NAMES,
                                 "jit_step")
    assert sum(clipped.values()) == pytest.approx(9.0)
    assert clipped[("backward", "attention/core")] == pytest.approx(2.0)


def test_table_and_metrics():
    times = scopes.scope_times(scoped_trace(), NAMES, "jit_step")
    assert scopes.table(times, 2)["backward"] == {
        "attention/core": pytest.approx(1500.0),
        "none": pytest.approx(3500.0)}
    assert scopes.metric("backward_ms.train", times, 2) == pytest.approx(
        5000.0)
    assert scopes.metric("attn_core_ms.train", times, 2) == pytest.approx(
        1500.0)
    assert scopes.metric("optimizer_ms.train", times, 2) == pytest.approx(
        1000.0)
    assert scopes.metric("forward_ms.train", times, 2) == 0.0


@pytest.mark.parametrize("name", sorted(scopes.METRICS))
def test_readers_need_the_scope_map(name):
    t = scoped_trace()
    facts = {"kind": "train", "steps": 2}
    assert scopes.read(name, facts, t) is None
    assert scopes.read(name, dict(facts, scope_ops={}, step_module="x"),
                       t) is None
    got = scopes.read(name, dict(facts, scope_ops=NAMES,
                                 step_module="jit_step"), t)
    assert got == pytest.approx(scopes.metric(
        name, scopes.scope_times(t, NAMES, "jit_step"), 2))


def test_op_names_of_hlo_text():
    text = (
        "HloModule jit_step, is_scheduled=true\n\n"
        "%fused_computation.1 (param_0: f32[4]) -> f32[4] {\n"
        '  ROOT %mul.3 = f32[4]{0} multiply(%param_0, %param_0), '
        'metadata={op_name="jit(step)/jvp()/mul" source_line=3}\n}\n\n'
        "ENTRY %main.9 (p: f32[4]) -> f32[4] {\n"
        "  %copy.2 = f32[4]{0} copy(%p)\n"
        '  ROOT %fusion.4.clone = f32[4]{0} fusion(%copy.2), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_type="mul" '
        'op_name="jit(step)/optimizer/mul" source_file="a.py"}\n}\n')
    assert scopes.module_name(text) == "jit_step"
    assert scopes.op_names(text) == {"mul.3": "jit(step)/jvp()/mul",
                                     "fusion.4.clone":
                                         "jit(step)/optimizer/mul"}
    assert scopes.instruction(ev("fusion.4.clone")) == "fusion.4.clone"


def test_recorded_chip_trace_modules():
    t = scopes.load(str(DATA / "v5e_tiny.xplane.pb"))
    d, = t.devices
    assert [m[2].split("(")[0] for m in d.modules] == ["jit__lambda"] * 4
    # every op ran inside one of the program's four calls
    assert all(any(s <= o[0] and o[1] <= e for s, e, _ in d.modules)
               for o in d.ops)
    busy = tr.busy_s(t)
    times = scopes.scope_times(t, {}, "jit__lambda")
    assert list(times) == [("unattributed", "none")]
    assert times[("unattributed", "none")] == pytest.approx(busy, rel=1e-9)
    other = scopes.scope_times(t, {}, "jit_step")
    assert list(other) == [(scopes.OTHER_MODULES, "none")]


def test_scoped_run_hooks_on_cpu(files):
    """The scoped run's context keeps the compiled step's map; every
    pass is found in it."""
    made = []

    class Ctx(scoped_run.ScopedContext):
        pass
    Ctx.files = files

    def context(*a):
        made.append(Ctx(*a))
        return made[-1]
    bench = {"configs": [{"name": "tiny", "file": str(files / "tiny.json")}],
             "workloads": [{"name": "train", "config": "tiny",
                            "traffic": "train", "chips": 1}],
             "end_to_end": [{"name": "train_tokens_per_s", "unit": "x"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    out = run.execute(bench, "train", SEED, 1.0, False, jax.devices(),
                      context_cls=context)
    ctx, = made
    assert ctx.step_module == "jit_step"
    found = {scopes.classify(op) for op in ctx.scope_ops.values()}
    assert {p for p, _ in found} >= {"forward", "recompute", "backward",
                                     "optimizer"}
    assert {s for _, s in found} >= {"embed", "norm", "attention/qkv",
                                     "attention/core", "attention/out",
                                     "ffn", "loss", "optimizer"}
    line = scoped_run.scoped(out, ctx)
    assert set(line["scope_metrics"]) == set(scopes.METRICS)
    assert line["scope_cost_s"]["as_text"] > 0

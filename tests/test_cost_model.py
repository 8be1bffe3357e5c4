"""Cost model (§3.1) unit + property tests (hypothesis)."""
import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.configs import (DeviceInfo, MULTI_POD_MESH, SINGLE_POD_MESH,
                           OSDPConfig, get_arch, get_shape)
from repro.core.cost_model import (DP, ZDP, ZDP_POD, CostEnv, Decision,
                                   op_cost, plan_cost, uniform_plan,
                                   zdp_extra_time, zdp_saving)
from repro.core.descriptions import OperatorDesc, describe


ENV = CostEnv(DeviceInfo(), SINGLE_POD_MESH)
ENV_POD = CostEnv(DeviceInfo(), MULTI_POD_MESH)

op_strategy = st.builds(
    OperatorDesc,
    name=st.just("op"),
    param_count=st.integers(min_value=1, max_value=10**10),
    flops_per_token=st.floats(min_value=0, max_value=1e12),
    act_bytes_per_token=st.floats(min_value=0, max_value=1e6),
    splittable=st.booleans(),
    decidable=st.just(True),
    layers=st.integers(min_value=1, max_value=128),
)


@given(op=op_strategy, b=st.integers(1, 64))
@settings(max_examples=200, deadline=None)
def test_zdp_never_increases_memory(op, b):
    c_dp = op_cost(op, Decision("op", (DP,)), b, 1024, ENV)
    c_z = op_cost(op, Decision("op", (ZDP,)), b, 1024, ENV)
    assert c_z.memory <= c_dp.memory + 1e-6


@given(op=op_strategy, b=st.integers(1, 64))
@settings(max_examples=200, deadline=None)
def test_zdp_comm_is_1_5x_dp_plus_ckpt(op, b):
    """Paper Fig. 1: ZDP comm = 3 rounds vs DP's 2 (x(N-1) steps), +1
    round under checkpointing."""
    env = CostEnv(DeviceInfo(alpha=0.0), SINGLE_POD_MESH,
                  checkpointing=False)
    c_dp = op_cost(op, Decision("op", (DP,)), b, 1024, env)
    c_z = op_cost(op, Decision("op", (ZDP,)), b, 1024, env)
    if c_dp.comm_time > 0:
        assert c_z.comm_time == pytest.approx(1.5 * c_dp.comm_time, rel=1e-6)
    env_ck = CostEnv(DeviceInfo(alpha=0.0), SINGLE_POD_MESH,
                     checkpointing=True)
    c_z_ck = op_cost(op, Decision("op", (ZDP,)), b, 1024, env_ck)
    if c_dp.comm_time > 0:
        assert c_z_ck.comm_time == pytest.approx(2.0 * c_dp.comm_time,
                                                 rel=1e-6)


@given(op=op_strategy)
@settings(max_examples=100, deadline=None)
def test_savings_and_extra_time_nonnegative(op):
    assert zdp_saving(op, ENV) >= 0
    assert zdp_extra_time(op, ENV) >= 0
    assert zdp_saving(op, ENV_POD, ZDP_POD) <= zdp_saving(op, ENV_POD, ZDP)


@given(op=op_strategy, b=st.integers(1, 32), g=st.integers(2, 8))
@settings(max_examples=100, deadline=None)
def test_split_reduces_gather_peak(op, b, g):
    """§3.3: gathered-slice peak (and the additive M_extra) = full/g."""
    c1 = op_cost(op, Decision("op", (ZDP,)), b, 1024, ENV)
    cg = op_cost(op, Decision("op", (ZDP,) * g), b, 1024, ENV)
    assert cg.peak_extra == pytest.approx(c1.peak_extra / g, rel=1e-6)
    assert cg.memory <= c1.memory + 1e-9   # smaller transient, same states
    saved = c1.memory - cg.memory
    want = c1.peak_extra * (1 - 1 / g)
    assert saved == pytest.approx(want, rel=1e-6, abs=1e-6)


@given(b1=st.integers(1, 16), b2=st.integers(17, 64))
@settings(max_examples=50, deadline=None)
def test_memory_monotone_in_batch(b1, b2):
    desc = describe(get_arch("phi4-mini-3.8b"), get_shape("train_4k"))
    env = ENV
    p = uniform_plan(desc, DP)
    m1 = plan_cost(desc, p, b1 * env.n_data, env).memory
    m2 = plan_cost(desc, p, b2 * env.n_data, env).memory
    assert m2 >= m1


def test_moe_flops_use_topk_only():
    moe = describe(get_arch("dbrx-132b"), get_shape("train_4k"))
    w13 = next(o for o in moe.operators if o.name == "layers.moe_w13")
    cfg = get_arch("dbrx-132b")
    # flops per token ~ top_k * 2 * d * 2ff * L  (not E * ...)
    want = cfg.moe_top_k * 2 * cfg.d_model * 2 * cfg.d_ff * cfg.n_layers
    assert w13.flops_per_token == pytest.approx(want)
    # params however count every expert
    assert w13.param_count == (cfg.moe_experts * 2 * cfg.d_model
                               * cfg.d_ff * cfg.n_layers)


def test_zdp_pod_stays_on_fast_link():
    """ZDP_POD gathers on ICI only; flat ZDP crosses the pod (DCI) link
    — so for big operators ZDP_POD must be cheaper per byte."""
    op = OperatorDesc("big", 10**9, 0.0, 0.0, layers=1)
    t_flat = zdp_extra_time(op, ENV_POD, ZDP)
    t_pod = zdp_extra_time(op, ENV_POD, ZDP_POD)
    assert t_pod < t_flat
    # but saves less memory
    assert zdp_saving(op, ENV_POD, ZDP_POD) < zdp_saving(op, ENV_POD, ZDP)


@pytest.mark.parametrize("arch,shape,counted", [
    ("qwen1.5-0.5b", "train_4k", True),       # tied, training
    ("phi4-mini-3.8b", "train_4k", True),     # tied, training
    ("llama3-405b", "train_4k", False),       # its own head.out
    ("qwen1.5-0.5b", "decode_32k", False),    # serving: no loss
])
def test_tied_head_counted_in_program_plans(arch, shape, counted):
    """`with_tied_head` gives a tied table the head's matmul, one loss
    block's fp32 + bf16 logits per sequence, and a bf16 copy of the
    table in the head's layout; `make_plan` plans with it. Any other
    description is returned as it is."""
    from repro.configs import RunConfig
    from repro.core.descriptions import (BYTES_PER_PARAM, LOSS_CHUNK,
                                         STATE_BYTES_PER_PARAM,
                                         with_tied_head)
    from repro.core.plan import make_plan
    model, shp = get_arch(arch), get_shape(shape)
    base = describe(model, shp)
    desc = with_tied_head(base)
    if not counted:
        assert desc is base
        return
    e0, e1 = base.operators[0], desc.operators[0]
    assert e1.name == "embed.tok" and e1.layout_copies == 1
    V, d = model.padded_vocab, model.d_model
    assert e1.flops_per_token - e0.flops_per_token == 2.0 * d * V
    assert (e1.act_bytes_per_token - e0.act_bytes_per_token) \
        * shp.seq_len == pytest.approx(V * 6 * LOSS_CHUNK)
    assert e1.state_bytes == e0.param_count * (STATE_BYTES_PER_PARAM
                                               + BYTES_PER_PARAM)
    assert desc.operators[1:] == base.operators[1:]
    plan = make_plan(RunConfig(model=model, shape=shp,
                               mesh=SINGLE_POD_MESH,
                               osdp=OSDPConfig(memory_limit_bytes=2**40)),
                     DeviceInfo())
    assert plan.desc.operators[0] == e1

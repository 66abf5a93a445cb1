"""Bucketed + quantized gradient communication (distributed/grad_comm.py).

Covers ISSUE 1's contract: bit-exact parity of bucketed-bf16 vs the seed's
per-param sync on a 2-rank mesh, the int8 codec round-trip bound, the
error-feedback convergence smoke, deterministic bucket assignment, and the
in-suite regression guard that bucketing keeps the collective count
O(buckets) instead of O(#params).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as optim
import paddle_tpu.distributed as dist
import paddle_tpu.distributed.collective as coll
import paddle_tpu.distributed.env as env_mod
import paddle_tpu.distributed.mesh as mesh_mod
from paddle_tpu.distributed import fleet, grad_comm
from paddle_tpu.framework.tensor import Tensor

rng = np.random.RandomState(0)


@pytest.fixture(autouse=True)
def reset_mesh(fresh_mesh):
    yield  # fresh_mesh (conftest) owns save/clear/restore


def _fake_params(shapes, dtype=np.float32, grads=None):
    """Param-like Tensors with .grad set (what sync() consumes)."""
    params = []
    for i, s in enumerate(shapes):
        p = Tensor(np.zeros(s, dtype))
        p.stop_gradient = False
        p.name = f"p{i}"
        p.grad = Tensor(np.asarray(grads[i], dtype) if grads is not None
                        else rng.standard_normal(s).astype(dtype))
        params.append(p)
    return params


# ------------------------------------------------------------- bucketing
def test_bucket_assignment_is_deterministic_across_ranks():
    """Two independently-built (identical) models — the SPMD rank view —
    must agree on every bucket: same params, offsets, dtypes, sizes."""
    def build():
        paddle.seed(3)
        return nn.Sequential(nn.Linear(64, 128), nn.ReLU(),
                             nn.Linear(128, 32), nn.Linear(32, 8))

    b1 = grad_comm.build_buckets(list(build().parameters()),
                                 comm_buffer_size=0.02,
                                 last_comm_buffer_size=0.01)
    b2 = grad_comm.build_buckets(list(build().parameters()),
                                 comm_buffer_size=0.02,
                                 last_comm_buffer_size=0.01)
    assert [b.signature() for b in b1] == [b.signature() for b in b2]
    assert len(b1) > 1  # the small cap actually splits this model
    # every param appears exactly once
    seen = sorted(i for b in b1 for i in b.param_indices)
    assert seen == list(range(6))  # 3 Linear layers x (weight, bias)


def test_buckets_are_dtype_homogeneous_and_capped():
    params = _fake_params([(256, 256), (256,), (128, 128)])
    # mixed dtypes: one param's grad in bf16
    params[1].grad._value = params[1].grad._value.astype(jnp.bfloat16)
    dtypes = [np.dtype(p.grad._value.dtype) for p in params]
    buckets = grad_comm.build_buckets(params, comm_buffer_size=0.1,
                                      last_comm_buffer_size=0.1,
                                      dtypes=dtypes)
    for b in buckets:
        itemsizes = {np.dtype(dtypes[i]).itemsize for i in b.param_indices}
        assert len(itemsizes) == 1
        assert b.nbytes <= 0.1 * 1024 * 1024 or len(b.param_indices) == 1


def test_comm_buffer_size_knob_is_wired_and_validated():
    net = nn.Linear(4, 2)
    for bad in (0, -3, "not-a-number", None):
        with pytest.raises((ValueError, TypeError)):
            dist.DataParallel(net, comm_buffer_size=bad)
    with pytest.raises(ValueError):
        dist.DataParallel(net, last_comm_buffer_size=-1)
    dp = dist.DataParallel(net, comm_buffer_size=7.5)
    assert dp.comm_buffer_size == 7.5
    # the knob reaches the communicator
    assert dp._grad_communicator().config.comm_buffer_size == 7.5
    with pytest.raises(ValueError):
        grad_comm.GradCommConfig(codec="fp8")


# ------------------------------------------------- parity on a 2-rank mesh
def test_bucketed_bf16_bit_exact_vs_per_param_sync():
    """The coalesced bf16 sync must transmit exactly what the seed's
    per-param cast/all_reduce/cast path transmitted — same psum over the
    same bf16 values, so bit-exact, not just allclose."""
    from jax.sharding import PartitionSpec as P

    m = mesh_mod.set_mesh(
        mesh_mod.build_mesh({"data": 2}, devices=jax.devices()[:2]))
    shapes = [(3, 5), (7,), (2, 2, 4)]
    # per-rank distinct grads, stacked on the mesh dim
    gs = [rng.standard_normal((2,) + s).astype(np.float32) for s in shapes]

    def body(*rank_grads):
        vals = [g.reshape(s) for g, s in zip(rank_grads, shapes)]
        # seed path: one bf16 collective per param
        ref = []
        for v in vals:
            t = Tensor(v.astype(jnp.bfloat16), _internal=True)
            coll.all_reduce(t, op=coll.ReduceOp.AVG)
            ref.append(t._value.astype(jnp.float32))
        # grad_comm path: one bf16 collective per bucket
        params = []
        for v in vals:
            p = Tensor(jnp.zeros(v.shape), _internal=True)
            p.stop_gradient = False
            p.grad = Tensor(v, _internal=True)
            params.append(p)
        comm = grad_comm.GradCommunicator(grad_comm.GradCommConfig("bf16"))
        comm.sync(params, world=2)
        return tuple(ref) + tuple(p.grad._value for p in params)

    outs = mesh_mod.compat_shard_map(
        body, m, P("data"), tuple([P()] * (2 * len(shapes))))(*gs)
    ref, got = outs[:len(shapes)], outs[len(shapes):]
    for r, g in zip(ref, got):
        assert np.array_equal(np.asarray(r), np.asarray(g)), \
            "bucketed bf16 sync drifted from the per-param wire values"


# ---------------------------------------------------------------- int8 codec
def test_int8_roundtrip_error_bound():
    x = jnp.asarray(rng.standard_normal(4096).astype(np.float32) * 3.0)
    scale = grad_comm.int8_scale(x)
    q = grad_comm.int8_encode(x, scale)
    deq = grad_comm.int8_decode(q, scale, world=1, dtype=np.float32)
    # |x| <= 127*scale by construction, so rounding bounds the error by
    # half a quantization step everywhere
    assert float(jnp.abs(x - deq).max()) <= float(scale) * 0.5001
    # the error-feedback residual is exactly what the wire dropped
    res = grad_comm.int8_residual(x, q, scale)
    np.testing.assert_allclose(np.asarray(deq + res), np.asarray(x),
                               rtol=0, atol=1e-6)


def _two_identical_rank_all_reduce(calls=None):
    """Collective fake for two ranks holding identical values: AVG/MAX are
    identity, integer SUM doubles (the quantized payload path)."""
    def fake(t, op=None, group=None, **kw):
        if calls is not None:
            calls.append((str(t._value.dtype), op))
        if op == coll.ReduceOp.SUM and jnp.issubdtype(t._value.dtype,
                                                      jnp.integer):
            t._value = t._value * 2
        return t
    return fake


def test_int8_error_feedback_convergence(monkeypatch):
    """Smoke test (ISSUE 1 acceptance): an MLP trained with the int8
    quantized grad sync + error feedback lands within tolerance of the
    un-quantized run after N steps."""
    x = rng.standard_normal((32, 8)).astype(np.float32)
    w_true = rng.standard_normal((8, 1)).astype(np.float32)
    y = np.tanh(x @ w_true).astype(np.float32)

    def train(codec, steps=60):
        paddle.seed(11)
        net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
        opt = optim.SGD(learning_rate=0.3, parameters=net.parameters())
        comm = (None if codec is None else grad_comm.GradCommunicator(
            grad_comm.GradCommConfig(codec)))
        losses = []
        for _ in range(steps):
            loss = F.mse_loss(net(paddle.to_tensor(x)), paddle.to_tensor(y))
            loss.backward()
            if comm is not None:
                comm.sync([p for p in net.parameters()
                           if not p.stop_gradient], world=2)
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        return losses

    monkeypatch.setattr(coll, "all_reduce", _two_identical_rank_all_reduce())
    exact = train(None)
    int8 = train("int8")
    assert exact[-1] < exact[0] * 0.1, "reference run failed to converge"
    assert int8[-1] < int8[0] * 0.1, "int8+EF run failed to converge"
    assert abs(int8[-1] - exact[-1]) <= max(0.05 * exact[-1], 0.005), \
        (int8[-1], exact[-1])


def test_int8_sync_stats_and_wire_dtypes(monkeypatch):
    calls = []
    monkeypatch.setattr(coll, "all_reduce",
                        _two_identical_rank_all_reduce(calls))
    params = _fake_params([(64, 64), (64,)])
    comm = grad_comm.GradCommunicator(grad_comm.GradCommConfig("int8"))
    before = [np.asarray(p.grad._value).copy() for p in params]
    comm.sync(params, world=2)
    # one scalar MAX (the shared scale) + one integer SUM per bucket
    assert [c[1] for c in calls] == [coll.ReduceOp.MAX, coll.ReduceOp.SUM]
    assert calls[1][0] == "int32"
    assert comm.stats["n_buckets"] == 1
    assert comm.stats["collectives"] == 2
    assert comm.stats["comm_bytes"] == (64 * 64 + 64) * 1 + 4
    # two identical ranks: the averaged grad equals the local quantized
    # grad, within half of the BUCKET-wide quantization step (the scale is
    # per bucket, not per param)
    bucket_scale = float(grad_comm.int8_scale(
        jnp.concatenate([jnp.asarray(b).reshape(-1) for b in before])))
    for p, b in zip(params, before):
        err = np.abs(np.asarray(p.grad._value) - b).max()
        assert err <= bucket_scale * 0.5001


# ------------------------------------------------------- DataParallel wiring
def _set_grads(model):
    n = 0
    for p in model.parameters():
        if not p.stop_gradient:
            p.grad = Tensor(rng.standard_normal(p.shape).astype(
                np.dtype(p._value.dtype)) * 1e-2)
            n += 1
    return n


def test_bucketing_collective_count_guard(monkeypatch):
    """Regression guard (ISSUE 1 acceptance): on the test GPT config,
    apply_collective_grads issues O(buckets) collectives — bounded by
    ceil(total_grad_MB / comm_buffer_size) + dtype-group slack — not
    O(#params) like the seed's per-param loop."""
    from paddle_tpu.models import GPTForCausalLM, gpt_presets

    model = GPTForCausalLM(gpt_presets("gpt-test"), seed=0)
    net = dist.DataParallel(model)
    n_params = _set_grads(model)
    assert n_params > 10  # the bound below must be a real reduction

    calls = []
    monkeypatch.setattr(env_mod, "get_world_size", lambda: 2)
    monkeypatch.setattr(coll, "all_reduce",
                        lambda t, op=None, **kw: calls.append(1) or t)
    net.apply_collective_grads()

    trainable = [p for p in model.parameters() if not p.stop_gradient]
    total_mb = sum(p.size * np.dtype(p._value.dtype).itemsize
                   for p in trainable) / (1024 * 1024)
    dtype_groups = len({np.dtype(p._value.dtype) for p in trainable})
    bound = math.ceil(total_mb / net.comm_buffer_size) + dtype_groups + 1
    assert len(calls) <= bound, (len(calls), bound)
    assert len(calls) < n_params / 4, (len(calls), n_params)
    assert net._grad_comm.stats["n_params"] == n_params


def test_strategy_selects_codec_and_buffer(monkeypatch):
    wire = []
    monkeypatch.setattr(env_mod, "get_world_size", lambda: 2)
    monkeypatch.setattr(coll, "all_reduce",
                        _two_identical_rank_all_reduce(wire))

    net = nn.Linear(4, 2)
    loss = net(paddle.to_tensor(rng.rand(8, 4).astype(np.float32))).sum()
    loss.backward()

    st = fleet.DistributedStrategy()
    st.grad_comm = True
    st.grad_comm_configs = {"codec": "int8", "comm_buffer_size_MB": 13}
    dp = dist.DataParallel(net, strategy=st)
    dp.apply_collective_grads()
    assert [w[0] for w in wire] == ["float32", "int32"]  # scale + payload
    assert dp._grad_comm.config.comm_buffer_size == 13
    # unknown sub-keys still rejected (check_configs_key semantics)
    with pytest.raises(ValueError):
        st.grad_comm_configs = {"bogus": 1}
    # a bad codec configured via strategy fails loudly at sync time
    st2 = fleet.DistributedStrategy()
    st2.grad_comm = True
    st2.grad_comm_configs = {"codec": "fp8"}
    dp2 = dist.DataParallel(net, strategy=st2)
    with pytest.raises(ValueError):
        dp2.apply_collective_grads()


# --------------------------------------------------- sharding stage-2 path
def test_sharding_stage2_uses_reduce_scatter(monkeypatch):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                               "sharding_degree": 8}
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 2, "sharding_degree": 8}
    fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(5)
    net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
    wrapped = fleet.distributed_model(net)
    assert type(wrapped).__name__ == "ShardingParallel"
    assert wrapped._grad_comm is not None
    _set_grads(net)

    rs_calls, ag_calls = [], []
    monkeypatch.setattr(env_mod, "get_world_size", lambda: 2)
    monkeypatch.setattr(
        coll, "reduce_scatter",
        lambda t, tensor_list=None, op=None, group=None, **kw:
        rs_calls.append(str(t._value.dtype)) or t)
    monkeypatch.setattr(
        coll, "all_gather",
        lambda tl, t, group=None, **kw: ag_calls.append(1) or t)
    wrapped.apply_collective_grads()
    st = wrapped._grad_comm.stats
    assert st["n_buckets"] >= 1
    # each bucket goes reduce_scatter -> all_gather, never plain all_reduce
    assert len(rs_calls) == len(ag_calls) == st["n_buckets"]
    assert st["collectives"] == 2 * st["n_buckets"]
    # default wire codec for the sharded path is bf16
    assert all(d == "bfloat16" for d in rs_calls), rs_calls


def test_group_sharded_parallel_attaches_communicator():
    from paddle_tpu.distributed.sharding import group_sharded_parallel

    mesh_mod.set_mesh(mesh_mod.build_mesh({"sharding": 8}))
    net = nn.Linear(16, 8)
    opt = optim.Adam(learning_rate=0.01, parameters=net.parameters())
    model, opt, _ = group_sharded_parallel(net, opt, "os_g")
    assert isinstance(model._grad_comm, grad_comm.GradCommunicator)
    # buffer knobs come from the reference kwargs (bytes -> MB)
    assert model._grad_comm.config.comm_buffer_size == pytest.approx(8.0)
    # stage 1 attaches nothing (grads are not sharded there)
    net2 = nn.Linear(4, 2)
    opt2 = optim.Adam(learning_rate=0.01, parameters=net2.parameters())
    model2, _, _ = group_sharded_parallel(net2, opt2, "os")
    assert getattr(model2, "_grad_comm", None) is None


# ------------------------------------------------------- cost model + tools
def test_comm_cost_terms():
    from paddle_tpu.cost_model import comm_cost

    gb = 350e6  # ~GPT-125M fp32 grads
    fp32 = comm_cost(gb, world=8, codec="fp32")
    bf16 = comm_cost(gb, world=8, codec="bf16")
    int8 = comm_cost(gb, world=8, codec="int8")
    assert fp32["time_s"] > bf16["time_s"] > int8["time_s"]
    assert bf16["wire_bytes"] == gb // 2 and int8["wire_bytes"] == gb // 4
    # bucketing amortizes launch latency: per-param sync (~one collective
    # per tensor) costs strictly more than the bucketed plan
    per_param = comm_cost(gb, world=8, codec="bf16", collectives=150)
    assert per_param["time_s"] > bf16["time_s"]
    # reduce_scatter alone moves half of what all-reduce moves
    rs = comm_cost(gb, world=8, codec="bf16", reduce_scatter_only=True)
    assert rs["bytes_through_chip"] == pytest.approx(
        bf16["bytes_through_chip"] / 2)
    assert comm_cost(gb, world=1)["time_s"] == 0.0
    with pytest.raises(ValueError):
        comm_cost(gb, world=8, codec="fp8")


@pytest.mark.parametrize("codec", grad_comm.CODECS)
def test_sync_executes_what_comm_plan_counts(codec, monkeypatch):
    """On the test GPT config the eager sync issues exactly the
    collectives and wire bytes `comm_plan` counts for the codec, fewer
    collectives than parameters, and the codec's share of the fp32 wire."""
    from paddle_tpu.models import GPTForCausalLM, gpt_presets

    model = GPTForCausalLM(gpt_presets("gpt-test"), seed=0)
    n_params = _set_grads(model)
    params = [p for p in model.parameters() if not p.stop_gradient]
    calls = []
    monkeypatch.setattr(coll, "all_reduce",
                        lambda t, op=None, **kw: calls.append(1) or t)
    cfg = grad_comm.GradCommConfig(codec=codec)
    comm = grad_comm.GradCommunicator(cfg)
    comm.sync(params, world=2)
    plan = grad_comm.comm_plan(params, cfg)
    assert len(calls) == comm.stats["collectives"] \
        == plan["collectives_per_step"]
    assert comm.stats["comm_bytes"] == plan["comm_bytes_per_step"]
    assert plan["per_param_collectives"] == n_params
    assert len(calls) < n_params
    # wire bytes against fp32, from the counts alone: a byte or two an
    # element plus the scales (4 B a bucket, or 4 B a block of 1024)
    fp32 = grad_comm.comm_plan(params, grad_comm.GradCommConfig("fp32"))
    numel, n_buckets = plan["total_grad_numel"], plan["n_buckets"]
    assert fp32["comm_bytes_per_step"] == 4 * numel
    scales = sum(grad_comm.scale_bytes(b.size, cfg.block_size)
                 for b in comm.buckets_for(params))
    assert plan["comm_bytes_per_step"] == {
        "fp32": 4 * numel, "bf16": 2 * numel,
        "int8": numel + 4 * n_buckets,
        "int8_block": numel + scales, "fp8_block": numel + scales}[codec]

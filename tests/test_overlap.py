"""Overlapped gradient communication + fused flat-buffer optimizer update
(ISSUE 5: distributed/overlap.py, optimizer/fused.py).

Covers the tentpole contract: bucket collectives launch BEFORE backward
completes (span ordering in the step trace), results are bit-identical to
the serial sync for fp32/bf16/int8 (error-feedback residuals included),
the in-trace per-bucket-future path matches the serial psum values, the
fused flat update equals the per-param optimizer exactly (SGD/Adam/AdamW,
ZeRO-2 shard form), and the strategy/cost-model/bench wiring.
"""
import sys
import time

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
from paddle_tpu.distributed import fleet, grad_comm, overlap
from paddle_tpu.distributed.overlap import (
    BucketFuture, OverlappedGradCommunicator, communicator_for,
)
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.optimizer.fused import FusedFlatUpdater

rng = np.random.RandomState(0)


@pytest.fixture(autouse=True)
def reset_mesh(fresh_mesh):
    yield  # fresh_mesh (conftest) owns save/clear/restore


@pytest.fixture(autouse=True)
def reset_fleet_state():
    """fleet.init is process-global; a leaked strategy from one test would
    silently re-route another test's DataParallel communicator."""
    from paddle_tpu.distributed.fleet import _fleet_state

    saved = dict(_fleet_state)
    yield
    _fleet_state.clear()
    _fleet_state.update(saved)


def _two_rank_all_reduce(calls=None):
    """Two identical emulated ranks: AVG/MAX identity, integer SUM doubles
    (same fake as tests/test_grad_comm.py)."""
    def fake(t, op=None, group=None, **kw):
        if calls is not None:
            calls.append((str(t._value.dtype), op))
        if op == coll.ReduceOp.SUM and jnp.issubdtype(t._value.dtype,
                                                      jnp.integer):
            t._value = t._value * 2
        return t
    return fake


def _mlp(seed=7):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))
    return net


# tiny caps -> the MLP splits into 3 buckets, so "bucket-ready" ordering
# is observable
def _cfg(codec="fp32", overlapped=False):
    return grad_comm.GradCommConfig(codec, comm_buffer_size=0.0002,
                                    last_comm_buffer_size=0.0001,
                                    overlap=overlapped)


X = rng.standard_normal((16, 8)).astype(np.float32)
Y = rng.standard_normal((16, 1)).astype(np.float32)


# ------------------------------------------------------------ exact parity
@pytest.mark.parametrize("codec", grad_comm.CODECS)
def test_overlapped_sync_bit_identical_to_serial(codec, monkeypatch):
    """The acceptance bar: N training steps with bucket-ready overlapped
    sync produce EXACTLY the serial path's losses, grads, params — and for
    int8, exactly its cross-step error-feedback residuals."""
    monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())

    def train(overlapped, steps=5):
        net = _mlp()
        opt = optim.SGD(learning_rate=0.2, parameters=net.parameters())
        comm = communicator_for(_cfg(codec, overlapped))
        params = [p for p in net.parameters() if not p.stop_gradient]
        losses = []
        for _ in range(steps):
            if overlapped:
                comm.prepare(params, world=2)
            loss = F.mse_loss(net(paddle.to_tensor(X)), paddle.to_tensor(Y))
            loss.backward()
            comm.sync(params, world=2)
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        return losses, comm, net

    l_ser, c_ser, net_ser = train(False)
    l_ovl, c_ovl, net_ovl = train(True)
    assert type(c_ovl) is OverlappedGradCommunicator
    assert l_ser == l_ovl, (codec, l_ser, l_ovl)
    for a, b in zip(net_ser.parameters(), net_ovl.parameters()):
        assert np.array_equal(np.asarray(a._value), np.asarray(b._value))
    # int8 error feedback: the residual carried into the next step must be
    # the serial one, bit for bit, or a later step silently diverges
    assert sorted(c_ser._residuals) == sorted(c_ovl._residuals)
    for k in c_ser._residuals:
        assert np.array_equal(np.asarray(c_ser._residuals[k]),
                              np.asarray(c_ovl._residuals[k])), (codec, k)
    if codec == "int8":
        assert c_ser._residuals, "int8 run recorded no residuals"
    # the overlapped run actually overlapped
    assert c_ovl.stats["overlapped"] is True
    assert c_ovl.stats["n_buckets"] >= 3
    assert c_ovl.stats["buckets_launched_early"] == c_ovl.stats["n_buckets"]


def test_bucket_launches_before_backward_completes(monkeypatch):
    """Span-ordering proof (the step-trace acceptance check): every
    bucket's launch marker lands INSIDE the backward span — the collective
    was issued while backward was still running — and the lane's
    comm:bucket spans exist for each bucket."""
    from paddle_tpu import profiler as prof

    monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
    spans = []
    sink = lambda name, t0, t1, tid: spans.append((name, t0, t1, tid))
    prof.add_span_sink(sink)
    try:
        net = _mlp()
        comm = OverlappedGradCommunicator(_cfg("fp32", True))
        params = [p for p in net.parameters() if not p.stop_gradient]
        comm.prepare(params, world=2)
        with prof.RecordEvent("backward"):
            F.mse_loss(net(paddle.to_tensor(X)),
                       paddle.to_tensor(Y)).backward()
        comm.sync(params, world=2)
    finally:
        prof.remove_span_sink(sink)

    bwd = [s for s in spans if s[0] == "backward"]
    launches = [s for s in spans if s[0].startswith("comm_launch:bucket")]
    lane = [s for s in spans if s[0].startswith("comm:bucket")]
    assert len(bwd) == 1
    b0, b1 = bwd[0][1], bwd[0][2]
    n_buckets = comm.stats["n_buckets"]
    assert n_buckets >= 3
    assert len(launches) == n_buckets and len(lane) == n_buckets
    for name, t0, t1, _tid in launches:
        assert b0 <= t0 <= b1, \
            f"{name} launched outside the backward span"
    # the communicator's own timeline agrees (what flush() accounted)
    assert all(row["launched_early"] for row in comm.last_timeline)
    # and an exposed "comm" span exists for the flush barrier
    assert any(s[0] == "comm" for s in spans)


def test_gpt_test_overlap_parity_and_span_ordering(monkeypatch):
    """The gpt-test acceptance config: overlapped losses exactly equal
    serial losses, and every bucket launches mid-backward."""
    from paddle_tpu import profiler as prof
    from paddle_tpu.models import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
    )

    monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 256, (2, 16)).astype(np.int64)
    labels = rs.randint(0, 256, (2, 16)).astype(np.int64)

    def train(overlapped, steps=2):
        paddle.seed(1234)
        m = GPTForCausalLM(gpt_presets("gpt-test"), seed=7)
        crit = GPTPretrainingCriterion()
        o = optim.AdamW(learning_rate=1e-3, parameters=m.parameters())
        cfg = grad_comm.GradCommConfig("fp32", comm_buffer_size=0.05,
                                       last_comm_buffer_size=0.01,
                                       overlap=overlapped)
        comm = communicator_for(cfg)
        params = [p for p in m.parameters() if not p.stop_gradient]
        losses = []
        for _ in range(steps):
            if overlapped:
                comm.prepare(params, world=2)
            loss = crit(m(paddle.to_tensor(ids, dtype="int64")),
                        paddle.to_tensor(labels, dtype="int64"))
            with prof.RecordEvent("backward"):
                loss.backward()
            comm.sync(params, world=2)
            o.step()
            o.clear_grad()
            losses.append(float(loss.numpy()))
        return losses, comm

    l_ser, _ = train(False)
    spans = []
    sink = lambda name, t0, t1, tid: spans.append((name, t0, t1))
    prof.add_span_sink(sink)
    try:
        l_ovl, comm = train(True)
    finally:
        prof.remove_span_sink(sink)
    assert l_ser == l_ovl, (l_ser, l_ovl)
    assert comm.stats["n_buckets"] >= 2
    # every bucket of every step launched inside A backward span
    bwd = [(t0, t1) for n, t0, t1 in spans if n == "backward"]
    launches = [(n, t0) for n, t0, t1 in spans
                if n.startswith("comm_launch:bucket")]
    assert len(launches) == 2 * comm.stats["n_buckets"]
    for name, t0 in launches:
        assert any(b0 <= t0 <= b1 for b0, b1 in bwd), \
            f"{name} launched outside backward"


# --------------------------------------------------------------- lifecycle
def test_flush_handles_stragglers_and_unprepared_sync(monkeypatch):
    monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
    net = _mlp()
    params = [p for p in net.parameters() if not p.stop_gradient]
    comm = OverlappedGradCommunicator(_cfg("fp32", True))

    # unprepared sync falls back to the serial path (still correct)
    for p in params:
        p.grad = Tensor(rng.standard_normal(p.shape).astype(np.float32))
    before = [np.asarray(p.grad._value).copy() for p in params]
    comm.sync(params, world=2)
    for b, p in zip(before, params):
        assert np.array_equal(b, np.asarray(p.grad._value))  # AVG identity
    assert "overlapped" not in comm.stats

    # prepared, but NO backward ran: grads set manually -> all buckets are
    # stragglers launched at flush; still completes and accounts
    comm.prepare(params, world=2)
    for p in params:
        p.grad = Tensor(rng.standard_normal(p.shape).astype(np.float32))
    comm.sync(params, world=2)
    assert comm.stats["overlapped"] is True
    assert comm.stats["buckets_launched_early"] == 0

    # prepared with a missing grad -> loud error naming the contract
    comm.prepare(params, world=2)
    for p in params:
        p.grad = None
    with pytest.raises(RuntimeError, match="no gradient at flush"):
        comm.flush()


def test_abandon_disarms_without_syncing(monkeypatch):
    monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
    from paddle_tpu.framework import autograd as ag

    net = _mlp()
    params = [p for p in net.parameters() if not p.stop_gradient]
    comm = OverlappedGradCommunicator(_cfg("fp32", True))
    comm.prepare(params, world=2)
    assert ag._grad_ready_hook is not None
    comm.abandon()
    assert ag._grad_ready_hook is None
    # grads accumulate RAW afterwards (no hook, no launches)
    F.mse_loss(net(paddle.to_tensor(X)), paddle.to_tensor(Y)).backward()
    assert comm._step is None
    # re-arming twice doesn't leak the hook (prepare self-abandons)
    comm.prepare(params, world=2)
    comm.prepare(params, world=2)
    assert ag._grad_ready_hook == comm._on_grad_ready
    comm.abandon()
    assert ag._grad_ready_hook is None


def test_lane_error_surfaces_at_flush(monkeypatch):
    boom = RuntimeError("wire fell out")

    def bad_all_reduce(t, op=None, group=None, **kw):
        raise boom

    monkeypatch.setattr(coll, "all_reduce", bad_all_reduce)
    net = _mlp()
    params = [p for p in net.parameters() if not p.stop_gradient]
    comm = OverlappedGradCommunicator(_cfg("fp32", True))
    comm.prepare(params, world=2)
    F.mse_loss(net(paddle.to_tensor(X)), paddle.to_tensor(Y)).backward()
    with pytest.raises(RuntimeError, match="wire fell out"):
        comm.sync(params, world=2)
    # the failed step disarmed cleanly; the next serial sync still works
    from paddle_tpu.framework import autograd as ag

    assert ag._grad_ready_hook is None


# ----------------------------------------------------- in-trace / futures
def test_sync_async_matches_serial_in_trace():
    """Per-bucket futures inside a shard_map trace: each bucket's psum is
    its own op, and the resolved values match the serial sync's exactly."""
    from jax.sharding import PartitionSpec as P

    m = mesh_mod.set_mesh(
        mesh_mod.build_mesh({"data": 2}, devices=jax.devices()[:2]))
    shapes = [(3, 5), (7,), (2, 2, 4)]
    gs = [rng.standard_normal((2,) + s).astype(np.float32) for s in shapes]

    def make_params(vals):
        params = []
        for v in vals:
            p = Tensor(jnp.zeros(v.shape), _internal=True)
            p.stop_gradient = False
            p.grad = Tensor(v, _internal=True)
            params.append(p)
        return params

    def body(*rank_grads):
        vals = [g.reshape(s) for g, s in zip(rank_grads, shapes)]
        serial = make_params(vals)
        grad_comm.GradCommunicator(
            grad_comm.GradCommConfig("bf16")).sync(serial, world=2)
        asyncp = make_params(vals)
        comm = OverlappedGradCommunicator(grad_comm.GradCommConfig("bf16"))
        futs = comm.sync_async(asyncp, world=2)
        for f in futs:
            assert isinstance(f, BucketFuture) and f.done()
            f.scatter()   # write back per bucket, future by future
        return (tuple(p.grad._value for p in serial)
                + tuple(p.grad._value for p in asyncp))

    outs = mesh_mod.compat_shard_map(
        body, m, P("data"), tuple([P()] * (2 * len(shapes))))(*gs)
    ser, got = outs[:len(shapes)], outs[len(shapes):]
    for r, g in zip(ser, got):
        assert np.array_equal(np.asarray(r), np.asarray(g))


# -------------------------------------------------- fused flat-buffer step
@pytest.mark.parametrize("opt_cls", [optim.SGD, optim.Adam, optim.AdamW])
def test_fused_flat_update_exact_vs_per_param(opt_cls):
    def build():
        net = _mlp()
        return net, opt_cls(learning_rate=0.05,
                            parameters=net.parameters())

    net1, opt1 = build()
    for _ in range(4):
        F.mse_loss(net1(paddle.to_tensor(X)),
                   paddle.to_tensor(Y)).backward()
        opt1.step()
        opt1.clear_grad()

    net2, opt2 = build()
    params2 = [p for p in net2.parameters() if not p.stop_gradient]
    fused = FusedFlatUpdater(opt2, params2)
    for _ in range(4):
        F.mse_loss(net2(paddle.to_tensor(X)),
                   paddle.to_tensor(Y)).backward()
        fused.step()   # one kernel per bucket, no per-param unflatten
        opt2.clear_grad()

    for a, b in zip(net1.parameters(), net2.parameters()):
        assert np.array_equal(np.asarray(a._value), np.asarray(b._value)), \
            opt_cls.__name__


def test_fused_update_consumes_futures_without_grad_scatter(monkeypatch):
    """The overlap x fused composition: sync_async futures feed the flat
    update directly — the reduced buffer never unflattens into .grad."""
    monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
    net1, net2 = _mlp(), _mlp()
    opt1 = optim.Adam(learning_rate=0.05, parameters=net1.parameters())
    opt2 = optim.Adam(learning_rate=0.05, parameters=net2.parameters())
    p1 = [p for p in net1.parameters() if not p.stop_gradient]
    p2 = [p for p in net2.parameters() if not p.stop_gradient]
    comm1 = grad_comm.GradCommunicator(_cfg("fp32"))
    comm2 = OverlappedGradCommunicator(_cfg("fp32"))
    fused = FusedFlatUpdater(opt2, p2, communicator=comm2)
    for _ in range(3):
        F.mse_loss(net1(paddle.to_tensor(X)),
                   paddle.to_tensor(Y)).backward()
        F.mse_loss(net2(paddle.to_tensor(X)),
                   paddle.to_tensor(Y)).backward()
        comm1.sync(p1, world=2)
        opt1.step()
        opt1.clear_grad()
        futs = comm2.sync_async(p2, world=2)
        fused.step(futures=futs)
        opt2.clear_grad()
    for a, b in zip(p1, p2):
        assert np.array_equal(np.asarray(a._value), np.asarray(b._value))


def test_fused_rejects_nonelementwise_and_clip():
    net = _mlp()
    params = list(net.parameters())
    with pytest.raises(ValueError, match="cannot be fused"):
        FusedFlatUpdater(optim.Lamb(learning_rate=0.01, parameters=params),
                         params)
    with pytest.raises(ValueError, match="grad_clip"):
        FusedFlatUpdater(
            optim.SGD(learning_rate=0.01, parameters=params,
                      grad_clip=nn.ClipGradByGlobalNorm(1.0)), params)


def test_fused_sharded_update_matches_full(monkeypatch):
    """ZeRO stage-2 form: each rank updates only its owned shard of every
    flat bucket, shards all_gather back — and the result equals the full
    fused update exactly (the update rule is elementwise)."""
    # reference: full fused update
    net_ref = _mlp()
    opt_ref = optim.Adam(learning_rate=0.05,
                         parameters=net_ref.parameters())
    p_ref = [p for p in net_ref.parameters() if not p.stop_gradient]
    fused_ref = FusedFlatUpdater(opt_ref, p_ref)
    grads = [rng.standard_normal(p.shape).astype(np.float32) * 1e-2
             for p in p_ref]
    for p, g in zip(p_ref, grads):
        p.grad = Tensor(g)
    fused_ref.step()
    expected = {b.index: np.concatenate(
        [np.asarray(p_ref[pi]._value).reshape(-1)
         for pi in b.param_indices]) for b in fused_ref.buckets}

    world = 2
    for rank in range(world):
        net = _mlp()
        opt = optim.Adam(learning_rate=0.05, parameters=net.parameters())
        params = [p for p in net.parameters() if not p.stop_gradient]
        fused = FusedFlatUpdater(opt, params)
        for p, g in zip(params, grads):
            p.grad = Tensor(g)
        captured = {}

        def fake_all_gather(tl, t, group=None, **kw):
            # emulate the 2-rank gather: this rank's updated shard plus
            # the agreed full result for the peer's half
            i = len(captured)
            b = fused.buckets[i]
            captured[b.index] = np.asarray(t._value)
            pad = (-b.size) % world
            full = np.concatenate(
                [expected[b.index],
                 np.zeros(pad, expected[b.index].dtype)])
            return Tensor(full, _internal=True)

        monkeypatch.setattr(coll, "all_gather", fake_all_gather)
        fused.step_sharded(rank=rank, world=world)
        # the shard this rank computed IS the corresponding slice of the
        # full fused update, bit for bit
        for b in fused.buckets:
            pad = (-b.size) % world
            chunk = (b.size + pad) // world
            full = np.concatenate(
                [expected[b.index], np.zeros(pad, np.float32)])
            want = full[rank * chunk:(rank + 1) * chunk]
            assert np.array_equal(captured[b.index], want), \
                (rank, b.index)


def test_fused_slot_roundtrip_through_optimizer():
    net = _mlp()
    opt = optim.Adam(learning_rate=0.05, parameters=net.parameters())
    params = [p for p in net.parameters() if not p.stop_gradient]
    fused = FusedFlatUpdater(opt, params)
    F.mse_loss(net(paddle.to_tensor(X)), paddle.to_tensor(Y)).backward()
    fused.step()
    fused.sync_slots_to_optimizer()
    sd = opt.state_dict()
    assert any(k.endswith(".moment1") for k in sd)
    # re-import yields identical flat slots
    fused2 = FusedFlatUpdater(opt, params)
    fused2.load_slots_from_optimizer()
    for bi, slots in fused._slots.items():
        for k, v in slots.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(fused2._slots[bi][k]))


# ------------------------------------------------------------------ wiring
def test_strategy_overlap_knob_selects_overlapped_communicator(monkeypatch):
    monkeypatch.setattr(env_mod, "get_world_size", lambda: 2)
    monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
    net = nn.Linear(4, 2)
    st = fleet.DistributedStrategy()
    st.grad_comm = True
    st.grad_comm_configs = {"codec": "fp32", "overlap": True}
    dp = dist.DataParallel(net, strategy=st)
    comm = dp._grad_communicator()
    assert type(comm) is OverlappedGradCommunicator
    assert comm.config.overlap is True
    # forward arms the hook; backward launches; apply = flush
    from paddle_tpu.framework import autograd as ag

    loss = dp(paddle.to_tensor(rng.rand(8, 4).astype(np.float32))).sum()
    assert ag._grad_ready_hook is not None
    loss.backward()
    dp.apply_collective_grads()
    assert ag._grad_ready_hook is None
    assert comm.stats["overlapped"] is True
    assert comm.stats["buckets_launched_early"] == comm.stats["n_buckets"]
    # default stays serial
    st2 = fleet.DistributedStrategy()
    st2.grad_comm = True
    dp2 = dist.DataParallel(net, strategy=st2)
    assert type(dp2._grad_communicator()) is grad_comm.GradCommunicator


def test_sharding_stage2_overlap_uses_reduce_scatter(monkeypatch):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 8}
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 2, "sharding_degree": 8}
    strategy.grad_comm = True
    strategy.grad_comm_configs = {"codec": "bf16", "overlap": True}
    fleet.init(is_collective=True, strategy=strategy)
    net = _mlp(seed=5)
    wrapped = fleet.distributed_model(net)
    assert type(wrapped._grad_comm) is OverlappedGradCommunicator

    rs_calls, ag_calls = [], []
    monkeypatch.setattr(env_mod, "get_world_size", lambda: 2)
    monkeypatch.setattr(
        coll, "reduce_scatter",
        lambda t, tensor_list=None, op=None, group=None, **kw:
        rs_calls.append(str(t._value.dtype)) or t)
    monkeypatch.setattr(
        coll, "all_gather",
        lambda tl, t, group=None, **kw: ag_calls.append(1) or t)
    # forward arms, backward launches per completed bucket, apply flushes
    loss = wrapped(paddle.to_tensor(X)).sum()
    loss.backward()
    wrapped.apply_collective_grads()
    st = wrapped._grad_comm.stats
    assert st["overlapped"] is True
    assert len(rs_calls) == len(ag_calls) == st["n_buckets"]
    assert all(d == "bfloat16" for d in rs_calls)


def test_group_sharded_overlap_and_fused_knobs():
    from paddle_tpu.distributed.sharding import group_sharded_parallel

    mesh_mod.set_mesh(mesh_mod.build_mesh({"sharding": 8}))
    net = nn.Linear(16, 8)
    opt = optim.Adam(learning_rate=0.01, parameters=net.parameters())
    model, opt, _ = group_sharded_parallel(net, opt, "os_g",
                                           overlap_comm=True,
                                           fuse_update=True)
    assert type(model._grad_comm) is OverlappedGradCommunicator
    assert isinstance(model._fused_update, FusedFlatUpdater)


def test_hapi_fit_syncs_through_wrapper(monkeypatch):
    """Model.fit's eager path calls apply_collective_grads between
    backward and the optimizer (serial here: world emulated at 2), and the
    non-update micro-batches of gradient accumulation disarm overlap."""
    from paddle_tpu.hapi import Model

    monkeypatch.setattr(env_mod, "get_world_size", lambda: 2)
    synced = []
    real_sync = grad_comm.GradCommunicator.sync
    monkeypatch.setattr(
        grad_comm.GradCommunicator, "sync",
        lambda self, params, world=None, **kw:
        synced.append(world) or real_sync(self, params, world=1))
    net = dist.DataParallel(_mlp())
    model = Model(net)
    model.prepare(optimizer=optim.SGD(learning_rate=0.1,
                                      parameters=net.parameters()),
                  loss=F.mse_loss, jit_compile=False)
    data = [(X[i], Y[i]) for i in range(16)]
    model.fit(data, batch_size=4, shuffle=False, epochs=1, verbose=0)
    assert len(synced) == 4             # one sync per update step
    assert all(w == 2 for w in synced)
    # accumulation: 2 micro-batches per update -> half the syncs
    synced.clear()
    model.fit(data, batch_size=4, shuffle=False, epochs=1, verbose=0,
              accumulate_grad_batches=2)
    assert len(synced) == 2


# --------------------------------------------------- cost model + tooling
def test_comm_cost_overlap_terms():
    from paddle_tpu.cost_model import comm_cost

    gb = 350e6
    serial = comm_cost(gb, world=8, codec="bf16")
    assert serial["exposed_time_s"] == serial["time_s"]
    assert serial["overlap_efficiency"] == 0.0
    # a long backward hides everything but the last bucket
    ov = comm_cost(gb, world=8, codec="bf16", overlap=True, backward_s=1.0)
    assert ov["time_s"] == serial["time_s"]          # total work unchanged
    assert ov["exposed_time_s"] == pytest.approx(
        ov["time_s"] / ov["collectives"])            # last bucket exposed
    assert ov["exposed_time_s"] < serial["exposed_time_s"]
    assert 0.0 < ov["overlap_efficiency"] < 1.0
    # no backward window -> nothing hidden
    none = comm_cost(gb, world=8, codec="bf16", overlap=True, backward_s=0)
    assert none["exposed_time_s"] == none["time_s"]
    # a short window hides exactly that much
    short = comm_cost(gb, world=8, codec="bf16", overlap=True,
                      backward_s=serial["time_s"] / 10)
    assert short["hidden_time_s"] == pytest.approx(serial["time_s"] / 10)


def test_overlap_efficiency_gauge_exported(monkeypatch):
    from paddle_tpu.observability import get_registry

    monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
    net = _mlp()
    params = [p for p in net.parameters() if not p.stop_gradient]
    comm = OverlappedGradCommunicator(_cfg("fp32", True))
    comm.prepare(params, world=2)
    F.mse_loss(net(paddle.to_tensor(X)), paddle.to_tensor(Y)).backward()
    comm.sync(params, world=2)
    snap = get_registry().snapshot()
    assert snap["grad_comm_overlap_efficiency"] == pytest.approx(
        comm.stats["overlap_efficiency"], abs=1e-6)
    assert snap["grad_comm_overlapped_syncs_total"] >= 1
    assert snap["grad_comm_buckets_launched_early_total"] >= \
        comm.stats["buckets_launched_early"]

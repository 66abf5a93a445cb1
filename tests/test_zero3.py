"""ZeRO-3 parameter sharding at rest (ISSUE 9: distributed/sharding/stage3.py,
overlap.GatherFuture, fused.step_sharded(param_store=), memory watermark,
cost_model.zero3_cost, bench gates).

Covers the tentpole contract: parameters live as 1/world shards at rest
(live-bytes drop), per-bucket all_gathers prefetched one layer ahead on the
CollectiveLane (span-ordering proof), gathered params freed after use
(<= 2 buckets resident, LiveBytesWatermark proof), the owned-shard fused
update, and BIT-identical losses vs the replicated os_g path on gpt-test
for fp32/bf16/int8_block — plus the save/checkpoint/bench/cost wiring.
"""
import gc
import os

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
import paddle_tpu.optimizer as optim
import paddle_tpu.distributed.collective as coll
import paddle_tpu.distributed.env as env_mod
import paddle_tpu.distributed.mesh as mesh_mod
from paddle_tpu.distributed import grad_comm
from paddle_tpu.distributed.overlap import (
    GatherFuture, OverlappedGradCommunicator,
)
from paddle_tpu.distributed.sharding import (
    Stage3ParamShards, group_sharded_parallel, save_group_sharded_model,
)
from paddle_tpu.distributed.sharding.stage3 import FreedParamValue
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.observability import get_registry
from paddle_tpu.observability import memory as obs_mem
from paddle_tpu.optimizer.fused import FusedFlatUpdater

rng = np.random.RandomState(0)

X = rng.standard_normal((16, 8)).astype(np.float32)
Y = rng.standard_normal((16, 1)).astype(np.float32)


@pytest.fixture(autouse=True)
def reset_mesh(fresh_mesh):
    yield  # fresh_mesh (conftest) owns save/clear/restore


def _two_rank_all_reduce():
    """Two identical emulated ranks: AVG/MAX identity, integer SUM doubles
    (same fake as tests/test_overlap.py)."""
    def fake(t, op=None, group=None, **kw):
        if op == coll.ReduceOp.SUM and jnp.issubdtype(t._value.dtype,
                                                      jnp.integer):
            t._value = t._value * 2
        return t
    return fake


def _mlp(seed=7):
    paddle.seed(seed)
    return nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 1))


def _cfg(codec="fp32"):
    # tiny caps -> several buckets, so the prefetch pipeline has stages
    return grad_comm.GradCommConfig(codec, comm_buffer_size=0.0002,
                                    last_comm_buffer_size=0.0001,
                                    block_size=64)


# ------------------------------------------------------------ at-rest state
class TestAtRest:
    def test_shard_drops_live_bytes_to_one_over_world(self):
        paddle.seed(0)
        layers = []
        for _ in range(6):
            layers += [nn.Linear(256, 256), nn.Tanh()]
        net = nn.Sequential(*layers)
        params = [p for p in net.parameters() if not p.stop_gradient]
        full = sum(p._value.size * p._value.dtype.itemsize for p in params)
        store = Stage3ParamShards(
            params, grad_comm.GradCommConfig(
                "fp32", comm_buffer_size=0.3, last_comm_buffer_size=0.3
            ) and grad_comm.GradCommunicator(grad_comm.GradCommConfig(
                "fp32", comm_buffer_size=0.3, last_comm_buffer_size=0.3)),
            rank=0, world=4)
        gc.collect()
        before = obs_mem.live_tensor_bytes()
        store.shard_()
        gc.collect()
        after = obs_mem.live_tensor_bytes()
        # device set shrank by ~the 3/4 of param bytes now held as shards
        # elsewhere (host under emulation, peer HBM for real)
        assert before - after > 0.70 * full, (before, after, full)
        assert store.param_bytes_per_rank() <= full / 4 + 4096
        # every param is a placeholder carrying shape/dtype metadata
        for p in params:
            assert isinstance(p._value, FreedParamValue)
            assert tuple(p.shape) == tuple(p._value.shape)
            assert np.dtype(p.dtype) == p._value.dtype
        # the gauge agrees
        snap = get_registry().snapshot()
        assert snap["zero3_param_bytes_per_rank"] == \
            store.param_bytes_per_rank()

    def test_freed_placeholder_without_store_raises(self):
        ph = FreedParamValue((4, 4), np.float32, store=None, pname="w")
        with pytest.raises(RuntimeError, match="sharded at rest"):
            np.asarray(ph)

    def test_world_one_is_rejected(self):
        net = _mlp()
        with pytest.raises(ValueError, match="world > 1"):
            Stage3ParamShards([p for p in net.parameters()],
                              grad_comm.GradCommunicator(_cfg()),
                              rank=0, world=1)


# --------------------------------------------------------- prefetch schedule
class TestPrefetchScheduling:
    def test_layer_order_spans(self, monkeypatch):
        """The scheduling proof: every gather_launch:bucket{i} precedes
        that bucket's first forward use AND (for prefetched buckets)
        follows the PREVIOUS layer's pre-hook; the first bucket is
        gathered synchronously; lane-side gather spans exist."""
        from paddle_tpu import profiler as prof

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(32, 32), nn.Tanh(),
                            nn.Linear(32, 32), nn.Tanh(),
                            nn.Linear(32, 32))
        params = [p for p in net.parameters() if not p.stop_gradient]
        # 0.006 MB cap: one Linear's weight+bias (4224 B) per bucket
        comm = grad_comm.GradCommunicator(grad_comm.GradCommConfig(
            "fp32", comm_buffer_size=0.006, last_comm_buffer_size=0.006))
        store = Stage3ParamShards(params, comm, rank=0, world=2)
        store.shard_()
        store.install_hooks(net)
        # buckets are built in REVERSE traversal order, so earlier layers
        # consume higher-index buckets; buckets may straddle layers
        assert len(store.buckets) == 3
        layer_buckets = [need for _l, need in store._layer_order]
        assert len(layer_buckets) == 3
        first_use = {}
        for k, need in enumerate(layer_buckets):
            for bi in need:
                first_use.setdefault(bi, k)
        assert set(first_use) == {0, 1, 2}

        spans = []
        sink = lambda name, t0, t1, tid: spans.append((name, t0, t1))
        prof.add_span_sink(sink)
        try:
            with paddle.no_grad():
                net(paddle.to_tensor(
                    rng.standard_normal((2, 32)).astype(np.float32)))
        finally:
            prof.remove_span_sink(sink)

        t_pre = {int(n.split("layer")[1]): t0 for n, t0, _ in spans
                 if n.startswith("zero3_prehook:layer")}
        t_ready = {int(n.split("layer")[1]): t0 for n, t0, _ in spans
                   if n.startswith("zero3_ready:layer")}
        t_launch = {int(n.split("bucket")[1]): t0 for n, t0, _ in spans
                    if n.startswith("gather_launch:bucket")}
        lane = {int(n.split("bucket")[1]) for n, _t0, _t1 in spans
                if n.startswith("gather:bucket")
                or n.startswith("gather_sync:bucket")}
        assert len(t_pre) == 3 and len(t_ready) == 3
        assert set(t_launch) == {0, 1, 2} and lane == {0, 1, 2}
        for bi, k in first_use.items():
            # the launch PRECEDES the bucket's first forward use (the
            # layer's forward starts only after its ready marker) ...
            assert t_launch[bi] <= t_ready[k], (bi, k, t_launch, t_ready)
            # ... and FOLLOWS the previous layer's pre-hook (the
            # layer-ahead prefetch window, or this layer's own sync path)
            assert t_launch[bi] >= t_pre[max(k - 1, 0)], \
                (bi, k, t_launch, t_pre)
        # at least one bucket was prefetched from the PREVIOUS layer's
        # pre-hook window (launched before its first-use pre-hook fired)
        assert any(t_launch[bi] <= t_pre[k]
                   for bi, k in first_use.items() if k > 0)
        # first bucket had no layer to hide under -> synchronous gather
        snap = get_registry().snapshot()
        assert snap["zero3_gathers_total"].get("mode=sync", 0) >= 1
        assert snap["zero3_gathers_total"].get("mode=prefetched", 0) >= 1

    def test_free_after_use_watermark(self):
        """The <= 2-buckets-resident proof: during a forward over a
        param-dominated net, live bytes never exceed the at-rest baseline
        by more than two full buckets (current + prefetched next)."""
        paddle.seed(0)
        layers = []
        for _ in range(6):
            layers += [nn.Linear(256, 256), nn.Tanh()]
        net = nn.Sequential(*layers)
        params = [p for p in net.parameters() if not p.stop_gradient]
        comm = grad_comm.GradCommunicator(grad_comm.GradCommConfig(
            "fp32", comm_buffer_size=0.3, last_comm_buffer_size=0.3))
        store = Stage3ParamShards(params, comm, rank=0, world=4)
        store.shard_()
        store.install_hooks(net)
        bucket_bytes = max(b.nbytes for b in store.buckets)
        x = paddle.to_tensor(
            rng.standard_normal((1, 256)).astype(np.float32))
        gc.collect()
        with paddle.no_grad():
            with obs_mem.LiveBytesWatermark() as wm:
                net(x)
        assert wm.n_samples >= 2 * len(store.buckets)
        # activations for batch 1 are ~1KB; 64KB of slack is generous
        assert wm.delta <= 2 * bucket_bytes + 64 * 1024, \
            (wm.delta, bucket_bytes)
        # everything back at rest afterwards
        assert store.resident_buckets() == []
        assert all(isinstance(p._value, FreedParamValue) for p in params)

    def test_failed_prefetch_surfaces_and_recovers(self, monkeypatch):
        net = _mlp()
        params = [p for p in net.parameters() if not p.stop_gradient]
        comm = grad_comm.GradCommunicator(_cfg())
        store = Stage3ParamShards(params, comm, rank=0, world=2)
        store.shard_()
        boom = RuntimeError("gather wire fell out")

        def bad_all_gather(tl, t, group=None, **kw):
            raise boom

        monkeypatch.setattr(coll, "all_gather", bad_all_gather)
        fut = store.prefetch_bucket(0)
        assert isinstance(fut, GatherFuture)
        with pytest.raises(RuntimeError, match="wire fell out"):
            store.ensure_gathered(0)
        # the failure disarmed cleanly; a healthy gather retries fine
        monkeypatch.undo()
        store.ensure_gathered(0)
        assert store._state[0] == "gathered"
        store.free_bucket(0)


# ------------------------------------------------------------- exact parity
class TestParity:
    @pytest.mark.parametrize("codec", ["fp32", "bf16", "int8_block"])
    def test_gpt_test_bit_identical_to_replicated(self, codec, monkeypatch):
        """The acceptance bar: gpt-test under true at-rest sharding trains
        to EXACTLY the replicated os_g path's losses (and params, and
        error-feedback residuals) — exercising prefetch, free-after-use,
        the tied-embedding fallback gather, and the owned-shard update."""
        from paddle_tpu.models import (
            GPTForCausalLM, GPTPretrainingCriterion, gpt_presets,
        )

        monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 256, (2, 16)).astype(np.int64)
        labels = rs.randint(0, 256, (2, 16)).astype(np.int64)

        def train(stage3, steps=3):
            paddle.seed(1234)
            m = GPTForCausalLM(gpt_presets("gpt-test"), seed=7)
            crit = GPTPretrainingCriterion()
            o = optim.AdamW(learning_rate=1e-3, parameters=m.parameters())
            cfg = grad_comm.GradCommConfig(
                codec, comm_buffer_size=0.05, last_comm_buffer_size=0.01,
                block_size=64)
            comm = grad_comm.GradCommunicator(cfg)
            params = [p for p in m.parameters() if not p.stop_gradient]
            fused = FusedFlatUpdater(o, params, communicator=comm)
            store = None
            if stage3:
                store = Stage3ParamShards(params, comm, rank=0, world=2)
                store.shard_()
                store.install_hooks(m)
            losses = []
            for _ in range(steps):
                loss = crit(m(paddle.to_tensor(ids, dtype="int64")),
                            paddle.to_tensor(labels, dtype="int64"))
                loss.backward()
                comm.sync(params, world=2, use_reduce_scatter=True)
                if stage3:
                    fused.step_sharded(rank=0, world=2, param_store=store)
                else:
                    fused.step()
                o.clear_grad()
                losses.append(float(loss.numpy()))
            return losses, m, comm, store

        l_ref, m_ref, c_ref, _ = train(False)
        l_z3, m_z3, c_z3, store = train(True)
        assert l_ref == l_z3, (codec, l_ref, l_z3)
        # error-feedback residuals (blockwise codec) match bit for bit
        assert sorted(c_ref._residuals) == sorted(c_z3._residuals)
        for k in c_ref._residuals:
            assert np.array_equal(np.asarray(c_ref._residuals[k]),
                                  np.asarray(c_z3._residuals[k])), (codec, k)
        if codec == "int8_block":
            assert c_ref._residuals, "blockwise run recorded no residuals"
        # final parameters match bit for bit (materialize gathers, then
        # frees on exit — the S001 all-paths release scope)
        with store.materialize():
            for a, b in zip(m_ref.parameters(), m_z3.parameters()):
                assert np.array_equal(np.asarray(a._value),
                                      np.asarray(b._value)), (codec, a.name)
        assert store.resident_buckets() == []
        # the tied embedding (read by the LM head OUTSIDE its owning
        # layer's forward) went through the self-healing fallback gather
        snap = get_registry().snapshot()
        assert snap["zero3_gathers_total"].get("mode=fallback", 0) >= 1

    def test_overlapped_comm_and_grad_accumulation_abandon(self,
                                                           monkeypatch):
        """Interplay with PR-5 overlap: the store's gather lane and the
        grad lane coexist; non-update micro-batches disarm the overlapped
        sync via abandon() while the stage-3 hooks keep gathering/freeing
        — losses and params stay bit-identical to the serial-accumulation
        replicated run."""
        monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
        micro = [(X[i::2], Y[i::2]) for i in range(2)]

        def train(stage3, steps=2):
            net = _mlp()
            o = optim.SGD(learning_rate=0.2, parameters=net.parameters())
            cfg = grad_comm.GradCommConfig(
                "fp32", comm_buffer_size=0.0002,
                last_comm_buffer_size=0.0001, overlap=True)
            comm = OverlappedGradCommunicator(cfg)
            params = [p for p in net.parameters() if not p.stop_gradient]
            fused = FusedFlatUpdater(o, params, communicator=comm)
            store = None
            if stage3:
                store = Stage3ParamShards(params, comm, rank=0, world=2)
                store.shard_()
                store.install_hooks(net)
            losses = []
            for _ in range(steps):
                for k, (xm, ym) in enumerate(micro):
                    update = k == len(micro) - 1
                    if update:
                        comm.prepare(params, world=2,
                                     use_reduce_scatter=True)
                    else:
                        comm.abandon()   # raw accumulation micro-batch
                    loss = F.mse_loss(net(paddle.to_tensor(xm)),
                                      paddle.to_tensor(ym))
                    loss.backward()
                    if update:
                        comm.sync(params, world=2,
                                  use_reduce_scatter=True)
                        if stage3:
                            fused.step_sharded(rank=0, world=2,
                                               param_store=store)
                        else:
                            fused.step()
                        o.clear_grad()
                    losses.append(float(loss.numpy()))
            return losses, net, store

        l_ref, net_ref, _ = train(False)
        l_z3, net_z3, store = train(True)
        assert l_ref == l_z3, (l_ref, l_z3)
        with store.materialize():
            for a, b in zip(net_ref.parameters(), net_z3.parameters()):
                assert np.array_equal(np.asarray(a._value),
                                      np.asarray(b._value))


# ----------------------------------------------------------- save / restore
class TestSaveRestore:
    def test_save_group_sharded_model_loads_unsharded_bit_identical(
            self, tmp_path, monkeypatch):
        """Satellite 1: a stage-3 save must write FULL weights —
        loading model.pdparams into a plain unsharded model reproduces
        the sharded model's parameters bit for bit."""
        monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
        net = _mlp(seed=11)
        want = [np.asarray(p._value).copy() for p in net.parameters()]
        params = [p for p in net.parameters() if not p.stop_gradient]
        comm = grad_comm.GradCommunicator(_cfg())
        store = Stage3ParamShards(params, comm, rank=0, world=2)
        store.shard_()
        store.install_hooks(net)
        net._zero3 = store
        out = str(tmp_path / "saved")
        save_group_sharded_model(net, out)
        # the save window freed everything again
        assert store.resident_buckets() == []
        assert all(isinstance(p._value, FreedParamValue) for p in params)

        fresh = _mlp(seed=99)   # different init — the load must win
        state = paddle.load(os.path.join(out, "model.pdparams"))
        fresh.set_state_dict(state)
        for w, p in zip(want, fresh.parameters()):
            assert np.array_equal(w, np.asarray(p._value))

    def test_state_dict_roundtrip_and_geometry_guards(self):
        net = _mlp(seed=3)
        params = [p for p in net.parameters() if not p.stop_gradient]
        want = [np.asarray(p._value).copy() for p in params]
        comm = grad_comm.GradCommunicator(_cfg())
        store = Stage3ParamShards(params, comm, rank=0, world=2)
        store.shard_()
        state = store.state_dict()
        assert set(state["shards"]) == {b.index for b in store.buckets}

        # fresh model, different entropy: load must restore exactly
        net2 = _mlp(seed=55)
        params2 = [p for p in net2.parameters() if not p.stop_gradient]
        comm2 = grad_comm.GradCommunicator(_cfg())
        store2 = Stage3ParamShards(params2, comm2, rank=0, world=2)
        store2.shard_()
        store2.load_state_dict(state)
        with store2.materialize():
            for w, p in zip(want, params2):
                assert np.array_equal(w, np.asarray(p._value))

        # geometry guards refuse a drifted resume
        with pytest.raises(ValueError, match="world mismatch"):
            store2.load_state_dict({**state, "world": 4})
        meta = store2.meta_state()
        store2.check_meta(meta)   # self-consistent
        with pytest.raises(ValueError, match="geometry mismatch"):
            store2.check_meta({**meta, "world": 8})

    def test_fused_shard_slots_roundtrip(self, monkeypatch):
        monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
        net = _mlp()
        o = optim.Adam(learning_rate=0.05, parameters=net.parameters())
        params = [p for p in net.parameters() if not p.stop_gradient]
        comm = grad_comm.GradCommunicator(_cfg())
        fused = FusedFlatUpdater(o, params, communicator=comm)
        store = Stage3ParamShards(params, comm, rank=0, world=2)
        store.shard_()
        store.install_hooks(net)
        loss = F.mse_loss(net(paddle.to_tensor(X)), paddle.to_tensor(Y))
        loss.backward()
        comm.sync(params, world=2, use_reduce_scatter=True)
        fused.step_sharded(rank=0, world=2, param_store=store)
        state = fused.shard_slots_state()
        assert state["own"] and state["peer"]
        fused2 = FusedFlatUpdater(
            optim.Adam(learning_rate=0.05, parameters=net.parameters()),
            params, communicator=comm)
        fused2.load_shard_slots_state(state)
        for i, slots in fused._shard_slots.items():
            for k, v in slots.items():
                np.testing.assert_array_equal(
                    np.asarray(v), np.asarray(fused2._shard_slots[i][k]))


# ------------------------------------------------------------------ wiring
class TestWiring:
    def test_group_sharded_parallel_attaches_store(self, monkeypatch):
        monkeypatch.setattr(env_mod, "get_world_size", lambda: 2)
        monkeypatch.setattr(coll, "all_reduce", _two_rank_all_reduce())
        net = _mlp()
        o = optim.Adam(learning_rate=0.01, parameters=net.parameters())
        model, o, _ = group_sharded_parallel(net, o, "p_g_os")
        store = model._zero3
        assert isinstance(store, Stage3ParamShards)
        assert store.sharded and store.world == 2
        assert store.comm is model._grad_comm
        # params are at rest; a forward gathers + frees through the hooks
        params = [p for p in model.parameters() if not p.stop_gradient]
        assert all(isinstance(p._value, FreedParamValue) for p in params)
        with paddle.no_grad():
            model(paddle.to_tensor(X))
        assert store.resident_buckets() == []

    def test_group_sharded_parallel_world_one_stays_unsharded(self):
        net = _mlp()
        o = optim.Adam(learning_rate=0.01, parameters=net.parameters())
        model, o, _ = group_sharded_parallel(net, o, "p_g_os")
        assert getattr(model, "_zero3", None) is None
        assert not any(isinstance(p._value, FreedParamValue)
                       for p in model.parameters())

    def test_register_external_use_prefetches_tied_weight(self,
                                                          monkeypatch):
        """A declared external use is served by the hooks (no fallback
        gather) — the tied-weight fast path."""

        class Tied(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(8, 8)
                self.head = nn.Linear(8, 8)

            def forward(self, x):
                h = self.head(self.fc(x))
                # reads fc.weight OUTSIDE fc's forward
                from paddle_tpu.framework.autograd import call_op

                return call_op(lambda a, w: a @ w, h, self.fc.weight,
                               op_name="tied_use")

        paddle.seed(0)
        net = Tied()
        params = [p for p in net.parameters() if not p.stop_gradient]
        comm = grad_comm.GradCommunicator(grad_comm.GradCommConfig(
            "fp32", comm_buffer_size=0.0002, last_comm_buffer_size=0.0001))
        store = Stage3ParamShards(params, comm, rank=0, world=2)
        store.register_external_use(net, net.fc.weight)
        store.shard_()
        store.install_hooks(net)
        before = get_registry().snapshot()["zero3_gathers_total"]
        fallback0 = before.get("mode=fallback", 0)
        with paddle.no_grad():
            net(paddle.to_tensor(X[:, :8]))
        after = get_registry().snapshot()["zero3_gathers_total"]
        assert after.get("mode=fallback", 0) == fallback0
        assert store.resident_buckets() == []


# --------------------------------------------------- cost model + tooling
class TestCostAndTooling:
    def test_zero3_cost_terms(self):
        from paddle_tpu.cost_model import zero3_cost

        pb = 1.4e9
        sync = zero3_cost(pb, world=8, prefetch=False)
        assert sync["param_bytes_per_rank"] == int(np.ceil(pb / 8))
        assert sync["exposed_gather_s_prefetched"] == \
            sync["exposed_gather_s_sync"] == sync["gather_time_s"]
        # a long forward hides everything but the first bucket
        pf = zero3_cost(pb, world=8, forward_s=10.0)
        assert pf["gather_time_s"] == sync["gather_time_s"]
        per_bucket = pf["gather_time_s"] / pf["n_buckets"]
        assert pf["exposed_gather_s_prefetched"] == \
            pytest.approx(per_bucket)
        # a short window hides exactly that much
        short = zero3_cost(pb, world=8,
                           forward_s=sync["gather_time_s"] / 10)
        assert short["hidden_gather_s"] == \
            pytest.approx(sync["gather_time_s"] / 10)
        # re-gather for backward doubles the work
        back = zero3_cost(pb, world=8, regather_backward=True,
                          forward_s=0.0)
        assert back["gather_time_s"] == \
            pytest.approx(2 * sync["gather_time_s"])
        # degenerate world
        one = zero3_cost(pb, world=1)
        assert one["gather_time_s"] == 0.0
        assert one["param_bytes_per_rank"] == int(pb)

    @pytest.mark.parametrize("fault", ["clean", "raises_mid_walk"])
    def test_layer_ahead_walk_gathers_once_and_frees_all(self, fault):
        """The layer-ahead discipline by its counts: walking the buckets
        with the next one prefetched gathers each exactly once (the first
        synchronously, the rest on the lane), holds half the parameter
        bytes per rank at world=2, and leaves nothing resident and every
        parameter a FreedParamValue, also when the walk raises with a
        prefetch in flight."""
        net = _mlp()
        params = [p for p in net.parameters() if not p.stop_gradient]
        full = sum(p._value.size * p._value.dtype.itemsize for p in params)
        store = Stage3ParamShards(params, grad_comm.GradCommunicator(_cfg()),
                                  rank=0, world=2)
        store.shard_()
        n = len(store.buckets)
        assert n >= 3
        assert store.param_bytes_per_rank() <= full / 2 + 2048

        def gathers():
            by_mode = get_registry().snapshot()["zero3_gathers_total"]
            return (by_mode.get("mode=sync", 0),
                    by_mode.get("mode=prefetched", 0))

        def walk(raise_at=None):
            for i, b in enumerate(store.buckets):
                try:
                    store.ensure_gathered(b.index)
                    if i + 1 < n:
                        store.prefetch_bucket(store.buckets[i + 1].index)
                    if i == raise_at:
                        raise RuntimeError("layer failed")
                    assert store.resident_buckets() == [b.index]
                finally:
                    store.free_bucket(b.index)

        sync0, pre0 = gathers()
        if fault == "clean":
            walk()
            launched = n - 1
        else:
            with pytest.raises(RuntimeError, match="layer failed"):
                try:
                    walk(raise_at=1)
                finally:
                    store.free_bucket(store.buckets[2].index)
            launched = 2
        sync1, pre1 = gathers()
        assert (sync1 - sync0, pre1 - pre0) == (1, launched)
        assert store.resident_buckets() == []
        assert all(isinstance(p._value, FreedParamValue) for p in params)
        assert get_registry().snapshot()["zero3_gathered_buckets"] == 0
        # and the store is whole: the next walk gathers every bucket again
        walk()
        assert gathers() == (sync1 + 1, pre1 + n - 1)
        assert all(isinstance(p._value, FreedParamValue) for p in params)

    def test_exposed_gather_gauge_exported(self):
        net = _mlp()
        params = [p for p in net.parameters() if not p.stop_gradient]
        comm = grad_comm.GradCommunicator(_cfg())
        store = Stage3ParamShards(params, comm, rank=0, world=2)
        store.shard_()
        store.install_hooks(net)
        with paddle.no_grad():
            net(paddle.to_tensor(X))
        snap = get_registry().snapshot()
        assert snap["zero3_exposed_gather_ms"] == pytest.approx(
            store.stats["exposed_gather_s_last_pass"] * 1e3, abs=1e-3)
        assert snap["zero3_gathered_buckets"] == 0

"""PS hot path (ISSUE 20): compiled dense step + async sharded embedding
pipeline.

Covers the tentpole contracts end to end:
- wire codec bit-parity with the PR-8 grad_comm blockwise transforms
  (the numpy wire pair must produce grad_comm's exact bits);
- key-hash shard routing + full pull/push parity vs a single LocalPs;
- duplicate-id gradient SUM through the sharded client (merge_sparse)
  and in-trace through PsTrainStep's scatter-add transpose;
- depth-1 pipeline == hand-rolled serial reference, bit-identical;
- depth-2 double buffering converges and hides pull latency;
- quantized wire: int8_block <= ~0.3x fp32 bytes at dim 32, loss parity
  band, error-feedback residuals carried per (table, shard);
- PR-4 failure model: timeout/retry -> typed DeadShardError naming the
  shard host; FLAGS_ps_degraded_ok serves zeros / drops-and-counts;
- tracing spans per step (pull_launch/pull_wait/step/push_commit);
- FLAGS_ps_* declared; wire-byte + cache-hit counters registered;
- the compiled step against the eager lookup step on the same batches.
"""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.ps import LocalPs
from paddle_tpu.distributed.ps.pipeline import (
    BusShardedClient, DeadShardError, PsPipeline, PsShardService,
    PsTrainStep, decode_rows, encode_rows, make_sharded_ps, wire_nbytes)
from paddle_tpu.models import WideDeep, ctr_batches, wide_deep_loss

DIM = 8
SLOTS = 4
BATCH = 16


def _model_step(pad_rows=128, seed=0, lr=1e-3):
    paddle.seed(seed)
    model = WideDeep(SLOTS, DIM)
    opt = paddle.optimizer.Adam(learning_rate=lr,
                                parameters=model.parameters())
    return PsTrainStep(model, opt, wide_deep_loss, dim=DIM,
                       pad_rows=pad_rows)


@pytest.fixture
def sharded():
    client, services, bus = make_sharded_ps(3, base_task=9100)
    client.create_table(0, DIM)
    yield client
    client.close()
    for s in services:
        s.stop()
    bus.close()


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

class TestWireCodec:
    def test_fp32_round_trip_and_bytes(self):
        rows = np.random.RandomState(0).randn(11, DIM).astype(np.float32)
        payload, resid = encode_rows(rows, "fp32")
        assert resid is None
        np.testing.assert_array_equal(decode_rows(payload), rows)
        keys = np.arange(11, dtype=np.uint64)
        assert wire_nbytes(payload, keys) == rows.nbytes + keys.nbytes

    @pytest.mark.parametrize("codec", ["int8_block", "fp8_block"])
    def test_bit_parity_with_grad_comm(self, codec):
        """The numpy wire pair must emit grad_comm's EXACT bits — scales,
        quantized payload, and EF residual (the PR-8 proof surface)."""
        import jax.numpy as jnp

        from paddle_tpu.distributed import grad_comm as G

        rs = np.random.RandomState(3)
        rows = (rs.randn(37, 16) * np.exp(rs.randn(37, 16))) \
            .astype(np.float32)
        payload, resid = encode_rows(rows, codec, block=64)
        flat = jnp.asarray(rows.reshape(-1))
        scales = G.block_scales(G.block_absmax(flat, 64), codec)
        q = G.block_encode(flat, scales, 64, codec)
        ref_wire = (np.asarray(q, np.int8) if codec == "int8_block"
                    else np.asarray(jnp.asarray(q).astype(
                        jnp.float8_e4m3fn)).view(np.uint8))
        ref_resid = np.asarray(
            G.block_residual(flat, q, scales, rows.size)).reshape(rows.shape)
        np.testing.assert_array_equal(payload["s"], np.asarray(scales))
        # the PS wire truncates block padding; parity on the real elements
        np.testing.assert_array_equal(payload["q"],
                                      ref_wire.reshape(-1)[:rows.size])
        np.testing.assert_array_equal(resid, ref_resid)

    def test_int8_decode_matches_dequant_and_counts_scale_bytes(self):
        rows = np.random.RandomState(1).randn(9, DIM).astype(np.float32)
        payload, resid = encode_rows(rows, "int8_block", block=16)
        deq = decode_rows(payload)
        # encode + residual reconstructs the input exactly
        np.testing.assert_allclose(deq + resid, rows, rtol=0, atol=1e-6)
        nb = wire_nbytes(payload)
        assert nb == payload["q"].nbytes + payload["s"].nbytes
        assert payload["q"].dtype == np.int8

    def test_unknown_codec_raises(self):
        with pytest.raises(ValueError, match="unknown PS wire codec"):
            encode_rows(np.zeros((2, 2), np.float32), "int4_block")


# ---------------------------------------------------------------------------
# sharded transport
# ---------------------------------------------------------------------------

class TestShardedClient:
    def test_pull_push_parity_vs_local(self, sharded):
        """Sharded pull/push must equal one LocalPs doing the same ops."""
        ref = LocalPs()
        ref.create_table(0, DIM)
        keys = np.random.RandomState(0).randint(
            0, 10_000, 40).astype(np.uint64)
        a = sharded.pull(0, keys)
        b = ref.pull(0, keys)
        np.testing.assert_array_equal(a, b)  # deterministic key-hash init
        g = np.random.RandomState(1).randn(40, DIM).astype(np.float32)
        sharded.push(0, keys, g, lr=0.5)
        ref.push(0, keys, g, lr=0.5)
        np.testing.assert_allclose(sharded.pull(0, keys), ref.pull(0, keys),
                                   rtol=0, atol=1e-6)

    def test_duplicate_ids_sum_not_last_write_win(self, sharded):
        """One push with the SAME id 3x must apply the SUMMED grad.
        SGD table so the update is exactly -lr * sum (adagrad would
        normalize the magnitude away)."""
        sharded.create_table(1, DIM, optimizer="sgd", lr=1.0,
                             init_range=0.0)
        keys = np.asarray([7, 7, 7], np.uint64)
        g = np.ones((3, DIM), np.float32)
        sharded.push(1, keys, g, lr=1.0)
        got = sharded.pull(1, keys[:1])
        np.testing.assert_allclose(got, np.full((1, DIM), -3.0),
                                   rtol=0, atol=1e-6)

    def test_routing_is_total_and_deterministic(self, sharded):
        keys = np.arange(1000, dtype=np.uint64)
        parts = sharded._route(keys)
        covered = np.concatenate([idx for _, idx, _ in parts])
        assert sorted(covered.tolist()) == list(range(1000))
        assert len(parts) == 3  # splitmix64 spreads a range over all shards
        again = sharded._route(keys)
        for (s1, i1, k1), (s2, i2, k2) in zip(parts, again):
            assert s1 == s2
            np.testing.assert_array_equal(k1, k2)

    def test_wire_byte_counters_by_codec(self):
        client, services, bus = make_sharded_ps(
            2, base_task=9200, codec="int8_block")
        try:
            client.create_table(0, DIM)
            keys = np.arange(64, dtype=np.uint64)
            client.pull(0, keys)
            client.push(0, keys, np.ones((64, DIM), np.float32), lr=0.1)
            assert client.pull_bytes > 0
            # int8 wire: q bytes ~= numel, far under fp32's 4*numel
            assert client.push_bytes < 64 * DIM * 4
            from paddle_tpu.observability.metrics import get_registry

            fam = get_registry().counter("ps_push_bytes_total",
                                         labels=("codec",))
            assert fam.labels(codec="int8_block").get() > 0
        finally:
            client.close()
            for s in services:
                s.stop()
            bus.close()

    def test_error_feedback_pushes_rounded_away_bits_eventually(self):
        """A grad with one dominant and one tiny component: each int8 push
        rounds the tiny one away, the EF residual re-adds it next push, so
        the accumulated server value converges near the true sum instead
        of dropping the tiny coordinate entirely."""
        client, services, bus = make_sharded_ps(
            1, base_task=9300, codec="int8_block")
        try:
            # SGD table: server value is exactly -lr * (sum of applied
            # grads), so the EF accounting is directly visible
            client.create_table(0, dim=4, optimizer="sgd", lr=1.0,
                                init_range=0.0)
            key = np.asarray([5], np.uint64)
            g = np.asarray([[100.0, 0.12, 0.0, 0.0]], np.float32)
            n = 50
            for _ in range(n):
                client.push(0, key, g, lr=1.0)
            # read the shard BACKEND directly: the client pull would come
            # back through the quantized wire too, hiding the tiny coord
            # again (pulls are stateless reads, no residual)
            got = services[0].backend.pull(0, key)
            want = -n * g[0]
            # the dominant coord is near-exact; the tiny one must be within
            # a few quantization steps of the truth (one step ~ 100/127)
            assert abs(got[0, 0] - want[0]) < 1.0
            assert abs(got[0, 1] - want[1]) < 2 * (100.0 / 127)
            assert client._resid  # residual store carries per-shard state
        finally:
            client.close()
            for s in services:
                s.stop()
            bus.close()


class TestFailureModel:
    def _dead_shard_setup(self, degraded_ok):
        from paddle_tpu.distributed import fleet_executor as fx

        bus = fx.MessageBus(rank=0)
        alive = PsShardService(bus, 9400, name="alive")
        dead = PsShardService(bus, 9401, name="dead")
        client = BusShardedClient(
            bus, [alive.task_id, dead.task_id], client_task=9402,
            timeout_s=0.05, retries=1, degraded_ok=degraded_ok,
            shard_names=["alive", "dead"])
        client.create_table(0, DIM)
        dead.stop()  # inbox stays registered; nothing drains -> timeouts
        return bus, alive, client

    def test_dead_shard_raises_typed_error_naming_host(self):
        bus, alive, client = self._dead_shard_setup(degraded_ok=False)
        try:
            keys = np.arange(64, dtype=np.uint64)  # hits both shards
            with pytest.raises(DeadShardError) as ei:
                client.pull(0, keys)
            assert ei.value.shard == 1
            assert ei.value.task_id == 9401
            assert "dead" in str(ei.value)
            from paddle_tpu.observability import get_event_log

            evs = get_event_log().events(kind="ps_shard_dead")
            assert evs and evs[-1]["host"] == "dead"
        finally:
            client.close()
            alive.stop()
            bus.close()

    def test_degraded_mode_zeros_pulls_and_drops_pushes(self):
        bus, alive, client = self._dead_shard_setup(degraded_ok=True)
        try:
            keys = np.arange(64, dtype=np.uint64)
            rows = client.pull(0, keys)  # no raise
            assert rows.shape == (64, DIM)
            from paddle_tpu.distributed.ps.pipeline import _shard_of

            dead_keys = _shard_of(keys, 2) == 1
            assert dead_keys.any() and (~dead_keys).any()
            assert np.all(rows[dead_keys] == 0.0)     # zeros for the dead
            assert np.any(rows[~dead_keys] != 0.0)    # live shard served
            before = client.dropped_pushes
            client.push(0, keys, np.ones((64, DIM), np.float32), lr=0.1)
            assert client.dropped_pushes > before     # counted, not raised
        finally:
            client.close()
            alive.stop()
            bus.close()


# ---------------------------------------------------------------------------
# compiled step + pipeline semantics
# ---------------------------------------------------------------------------

class TestPsTrainStep:
    def test_duplicate_ids_in_batch_sum_into_row_grad(self, sharded):
        """The gather transpose is a scatter-add: a row referenced by k
        slots gets k summed contributions in the EMITTED row grads."""
        import jax.numpy as jnp

        step = _model_step()
        # batch of 2: row 0 appears 3x, row 1 once in example 0, etc.
        slots = np.asarray([[0, 0, 0, 1], [2, 3, 3, 2]], np.int32)
        rows = jnp.asarray(np.random.RandomState(0).randn(
            step.pad_rows, DIM).astype(np.float32))
        labels = np.asarray([1.0, 0.0], np.float32)
        _, g_rows = step(rows, slots, labels)
        g = np.asarray(g_rows)
        assert np.any(g[0] != 0) and np.any(g[3] != 0)
        assert np.all(g[4:] == 0)  # untouched pad rows get zero grad

    def test_warm_map_reuses_compiled_step_across_instances(self):
        s1 = _model_step(seed=0)
        import jax.numpy as jnp

        rows = jnp.zeros((s1.pad_rows, DIM), jnp.float32)
        slots = np.zeros((BATCH, SLOTS), np.int32)
        labels = np.zeros(BATCH, np.float32)
        s1(rows, slots, labels)
        assert not s1.cache_hit  # first build compiled
        s2 = _model_step(seed=1)
        s2(jnp.zeros((s2.pad_rows, DIM), jnp.float32), slots, labels)
        assert s2.cache_hit  # same fingerprint+geometry -> warm map hit


class TestPipeline:
    def _serial_reference(self, client, batches, pad_rows=128, seed=0):
        """Hand-rolled pull -> compiled step -> merged push per batch —
        the semantics depth=1 must reproduce bit-for-bit."""
        import jax.numpy as jnp

        step = _model_step(pad_rows=pad_rows, seed=seed)
        losses = []
        for ids, labels in batches:
            uniq, inv = np.unique(
                np.asarray(ids, np.uint64).reshape(-1), return_inverse=True)
            rows = np.asarray(client.pull(0, uniq), np.float32)
            rows = np.pad(rows, ((0, pad_rows - rows.shape[0]), (0, 0)))
            slots = inv.astype(np.int32).reshape(ids.shape)
            loss, g_rows = step(jnp.asarray(rows), slots, labels)
            g = np.asarray(g_rows)[:uniq.size]
            nz = np.any(g != 0, axis=1)
            if nz.any():
                client.push(0, uniq[nz], g[nz], lr=0.1)
            losses.append(float(loss))
        return losses

    @pytest.mark.parametrize("shards", [1, 2])
    def test_compiled_step_matches_eager_lookup_step(self, shards):
        """The compiled PsTrainStep under the pipeline against the eager
        path it replaced (distributed_lookup_table per batch, dense step
        on the tape, row gradients pushed by the lookup's backward), on
        the same batches and SGD tables: the same losses, the same rows in
        the table, the same dense weights."""
        from paddle_tpu.distributed.ps import distributed_lookup_table

        batches = ctr_batches(6, BATCH, SLOTS, 500, alpha=1.0, seed=0)
        ref = LocalPs()
        ref.create_table(0, DIM, optimizer="sgd")
        paddle.seed(0)
        model = WideDeep(SLOTS, DIM)
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=model.parameters())
        eager = []
        for ids, labels in batches:
            rows = distributed_lookup_table(
                paddle.to_tensor(ids.astype(np.int64)), table_id=0,
                client=ref, lr=0.1)
            loss = wide_deep_loss(model(rows.reshape([ids.shape[0], -1])),
                                  paddle.to_tensor(labels))
            loss.backward()
            opt.step()
            opt.clear_grad()
            eager.append(float(loss))

        client, services, bus = make_sharded_ps(shards, base_task=9550)
        try:
            client.create_table(0, DIM, optimizer="sgd")
            step = _model_step()
            pipe = PsPipeline(client, 0, step, depth=1, lr_sparse=0.1)
            stats = pipe.run(batches)
            pipe.close()
            np.testing.assert_allclose(stats["losses"], eager, rtol=0,
                                       atol=1e-6)
            keys = np.unique(np.concatenate(
                [b[0].reshape(-1) for b in batches]).astype(np.uint64))
            np.testing.assert_allclose(client.pull(0, keys),
                                       ref.pull(0, keys), rtol=0, atol=1e-6)
            for a, b in zip(model.parameters(), step.model.parameters()):
                np.testing.assert_allclose(np.asarray(a._value),
                                           np.asarray(b._value),
                                           rtol=0, atol=1e-6)
        finally:
            client.close()
            for s in services:
                s.stop()
            bus.close()

    def test_depth1_bit_identical_to_serial_reference(self):
        batches = ctr_batches(6, BATCH, SLOTS, 500, alpha=1.0, seed=0)
        ref = LocalPs()
        ref.create_table(0, DIM)
        ref_losses = self._serial_reference(ref, batches)

        client, services, bus = make_sharded_ps(2, base_task=9500)
        try:
            client.create_table(0, DIM)
            step = _model_step()
            pipe = PsPipeline(client, 0, step, depth=1, lr_sparse=0.1)
            stats = pipe.run(batches)
            pipe.close()
            assert stats["losses"] == ref_losses  # BIT-identical
            # and the table state agrees exactly too
            keys = np.unique(np.concatenate(
                [b[0].reshape(-1) for b in batches]).astype(np.uint64))
            np.testing.assert_array_equal(client.pull(0, keys),
                                          ref.pull(0, keys))
        finally:
            client.close()
            for s in services:
                s.stop()
            bus.close()

    def _traced_pass(self, name, depth, n_batches, base_task):
        """One pipeline pass with request tracing on; returns its stats
        and its trace document (the spans in the order they were
        recorded)."""
        from paddle_tpu.framework.flags import _FLAGS
        from paddle_tpu.observability.tracing import get_tracer

        batches = ctr_batches(n_batches, BATCH, SLOTS, 500, alpha=1.0,
                              seed=0)
        client, services, bus = make_sharded_ps(2, base_task=base_task)
        old = _FLAGS.get("FLAGS_serving_tracing", True)
        _FLAGS["FLAGS_serving_tracing"] = True
        try:
            client.create_table(0, DIM)
            pipe = PsPipeline(client, 0, _model_step(), depth=depth,
                              lr_sparse=0.1, name=name)
            stats = pipe.run(batches)
            pipe.close()
            store = get_tracer().store
            doc = next(d for d in (store.get(t["trace_id"])
                                   for t in store.index()["traces"])
                       if d and d["name"] == name)
            return stats, doc
        finally:
            _FLAGS["FLAGS_serving_tracing"] = old
            client.close()
            for s in services:
                s.stop()
            bus.close()

    @pytest.mark.parametrize("depth", [1, 2])
    def test_pull_is_launched_ahead_of_the_step_only_at_depth2(self, depth):
        """Double buffering by the order of the pass's own spans: at depth
        2 the pull of batch k+1 is launched before step k runs (so the
        step hides it), at depth 1 only after step k and its push; both
        train. No clock is read."""
        stats, doc = self._traced_pass(f"ps_pass_depth{depth}", depth, 12,
                                       base_task=9600)
        losses = stats["losses"]
        assert len(losses) == 12 and losses[-1] < losses[0]
        order = {(sp["name"], sp["fields"]["step"]): i
                 for i, sp in enumerate(doc["spans"])
                 if sp["name"] in ("pull_launch", "step")}
        assert sum(1 for n, _ in order if n == "pull_launch") == 12
        ahead = [order[("pull_launch", k + 1)] < order[("step", k)]
                 for k in range(11)]
        assert ahead == [depth == 2] * 11

    @pytest.mark.parametrize("codec", ["int8_block", "fp8_block"])
    def test_quantized_wire_loss_parity_and_byte_ratio(self, codec):
        """A one-byte blockwise wire at dim 32: <= ~0.3x fp32 bytes, loss
        within a parity band of the fp32 wire (EF residuals at work). At
        depth 1, where a pass is deterministic: at depth 2 a push races
        the next pull, one codec's last loss moves by 0.03 from run to run
        and the band would judge the threads, not the wire."""
        dim, slots, pad = 32, 8, 512
        batches = ctr_batches(8, 32, slots, 2000, alpha=1.1, seed=0)

        def run(codec):
            client, services, bus = make_sharded_ps(
                2, base_task=9700, codec=codec)
            try:
                client.create_table(0, dim)
                paddle.seed(0)
                model = WideDeep(slots, dim)
                opt = paddle.optimizer.Adam(
                    learning_rate=1e-3, parameters=model.parameters())
                step = PsTrainStep(model, opt, wide_deep_loss, dim=dim,
                                   pad_rows=pad)
                pipe = PsPipeline(client, 0, step, depth=1, lr_sparse=0.1)
                stats = pipe.run(batches)
                pipe.close()
                return stats, client.pull_bytes + client.push_bytes
            finally:
                client.close()
                for s in services:
                    s.stop()
                bus.close()

        s32, b32 = run("fp32")
        s8, b8 = run(codec)
        assert b8 <= 0.31 * b32
        assert abs(s8["losses"][-1] - s32["losses"][-1]) < 0.02

    def test_pipeline_through_heter_cache(self):
        from paddle_tpu.distributed.ps.heter_cache import HeterCache

        batches = ctr_batches(6, BATCH, SLOTS, 200, alpha=1.2, seed=0)
        client, services, bus = make_sharded_ps(2, base_task=9800)
        try:
            client.create_table(0, DIM)
            cache = HeterCache(client, 0, DIM, capacity=128, lr=0.1,
                               fault_window_s=0.0)
            step = _model_step()
            pipe = PsPipeline(client, 0, step, depth=2, lr_sparse=0.1,
                              cache=cache)
            stats = pipe.run(batches)
            pipe.close()
            assert stats["losses"][-1] < stats["losses"][0]
            assert cache.hits > 0          # hot Zipf keys stayed resident
            assert cache.writeback_pushes + len(cache._wb_keys) == 0 or \
                cache.writeback_pushes >= 0  # flush() ran in finally
            # after flush, the PS holds every grad (no stranded dirty rows)
            assert not any(cache._dirty)
        finally:
            client.close()
            for s in services:
                s.stop()
            bus.close()

    def test_tracing_spans_name_each_stage(self):
        _, doc = self._traced_pass("ps_pass_test", 2, 3, base_task=9900)
        names = {s["name"] for s in doc["spans"]}
        assert {"pull_launch", "pull_wait", "step", "push_commit"} <= names
        # a span names its step and buffer -> a stall is attributable
        sp = next(s for s in doc["spans"] if s["name"] == "pull_wait")
        assert "step" in sp["fields"] and "buf" in sp["fields"]


# ---------------------------------------------------------------------------
# flags / metrics
# ---------------------------------------------------------------------------

class TestKnobs:
    def test_ps_flags_declared(self):
        from paddle_tpu.framework.flags import flag

        assert flag("FLAGS_ps_pipeline_depth") == 2
        assert flag("FLAGS_ps_wire_codec") == "fp32"
        assert flag("FLAGS_ps_wire_block") == 1024
        assert flag("FLAGS_ps_shards") == 1
        assert flag("FLAGS_ps_pull_timeout_s") == 10.0
        assert flag("FLAGS_ps_pull_retries") == 2
        assert flag("FLAGS_ps_degraded_ok") is False

    def test_metric_families_one_label_schema(self):
        from paddle_tpu.observability.metrics import get_registry

        reg = get_registry()
        assert reg.counter("ps_pull_bytes_total",
                           labels=("codec",)).label_names == ("codec",)
        assert reg.counter("ps_push_bytes_total",
                           labels=("codec",)).label_names == ("codec",)
        assert reg.counter("ps_cache_hits_total",
                           labels=("table",)).label_names == ("table",)

    def test_cache_hit_counter_increments_per_table(self):
        from paddle_tpu.distributed.ps.heter_cache import HeterCache
        from paddle_tpu.observability.metrics import get_registry

        ps = LocalPs()
        ps.create_table(3, DIM)
        cache = HeterCache(ps, 3, DIM, capacity=8, fault_window_s=0.0)
        child = get_registry().counter(
            "ps_cache_hits_total", labels=("table",)).labels(table="3")
        before = child.get()
        cache.lookup([1, 2])      # misses
        cache.lookup([1, 2])      # hits
        assert child.get() == before + 2


class TestCostModel:
    def test_ps_pipeline_cost_wire_and_overlap_math(self):
        from paddle_tpu.cost_model import ps_pipeline_cost

        fp32 = ps_pipeline_cost(batch=256, uniq_keys=1500, dim=32,
                                step_s=6e-3, depth=2, codec="fp32")
        int8 = ps_pipeline_cost(batch=256, uniq_keys=1500, dim=32,
                                step_s=6e-3, depth=2, codec="int8_block")
        # quantized wire moves ~1/4 the bytes (+ scales + keys overhead)
        assert int8["wire_bytes_per_step"] < 0.35 * fp32["wire_bytes_per_step"]
        # at depth 2 the steady step is the max of legs, not the sum
        serial = ps_pipeline_cost(batch=256, uniq_keys=1500, dim=32,
                                  step_s=6e-3, depth=1, codec="fp32")
        assert serial["steady_step_s"] > fp32["steady_step_s"]
        assert fp32["examples_per_s"] > serial["examples_per_s"]
        # compute-bound at this geometry on a 1 GB/s wire model
        assert not fp32["wire_bound"]

"""Serving runtime (ISSUE 14): paged quantized KV cache + continuous
batching + multi-replica eviction.

Contracts pinned here:
- KV block pool: fp32 codec bit-identical, int8/fp8 blockwise round-trip
  inside the codec error bound, append read-back == gather (the engine's
  incremental mirror IS the at-rest cache), free-list reuse, OOM typing,
  int8 at-rest bytes <= ~1/4 of fp32, flag-on (pallas seam) parity.
- Decode model: teacher-forced prefill+decode logits == the full forward
  (the training model's math, incrementally).
- Engine: paged generation == dense-cache reference generation exactly
  (fp32), no head-of-line blocking, blocks returned on completion,
  admission rejects at queue depth, int8 KV parity bound end to end.
- Replica set: hang/crash/corrupt replicas are evicted with their
  in-flight requests drained and re-dispatched — ZERO accepted requests
  lost (the acceptance-criteria chaos phase), zombie threads fenced.
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from paddle_tpu.models import GPTForCausalLM, gpt_presets
from paddle_tpu.serving import (
    GPTDecodeModel, KVBlockPool, KVCacheOOM, ReplicaSet, RequestQueue,
    ServeRequest, ServingEngine, bucket_pow2,
)
from paddle_tpu.serving.scheduler import _m_queue_depth, _m_requests


@pytest.fixture(autouse=True)
def reset_mesh(fresh_mesh):
    """Serving is mesh-independent, but the parity tests run the
    TRAINING model's forward, whose sharding constraints reject a
    leftover ambient mesh (e.g. data=8 vs batch 2) from earlier suites
    — the shared conftest fixture clears and restores it."""


def _mini_cfg(**over):
    kw = dict(hidden_size=32, num_heads=2, num_layers=2, vocab_size=64,
              max_position_embeddings=64)
    kw.update(over)
    return gpt_presets("gpt-test", **kw)


@pytest.fixture(scope="module")
def dm():
    return GPTDecodeModel(GPTForCausalLM(_mini_cfg(), seed=0))


def _pool(dm, codec="fp32", n_blocks=32, block_tokens=8):
    return KVBlockPool(n_blocks=n_blocks, block_tokens=block_tokens,
                       elems_per_token=dm.elems_per_token, codec=codec)


def _drive(engine, max_steps=200):
    """Step an engine until idle (queue drained, batch empty)."""
    for _ in range(max_steps):
        worked = engine.step()
        if not worked and not engine.running and not engine.queue.depth:
            return
    raise AssertionError("engine did not drain")


def _reqs(rs, n, prompt_len=5, max_new=4, vocab=64, **kw):
    return [ServeRequest(prompt_ids=rs.randint(0, vocab, (prompt_len,)),
                         max_new_tokens=max_new, **kw) for _ in range(n)]


# ---------------------------------------------------------------------------
# KV block pool + codecs
# ---------------------------------------------------------------------------

class TestKVBlockPool:
    def test_fp32_round_trip_bit_identical(self):
        pool = KVBlockPool(8, 4, 16, codec="fp32")
        rs = np.random.RandomState(0)
        kv = rs.randn(10, 16).astype(np.float32)
        t = pool.alloc_table(10)
        back = pool.append(t, kv)
        np.testing.assert_array_equal(back, kv)
        np.testing.assert_array_equal(pool.gather(t), kv)

    @pytest.mark.parametrize("codec", ["int8_block", "fp8_block"])
    def test_quantized_round_trip_error_bound(self, codec):
        pool = KVBlockPool(8, 4, 256, codec=codec)
        rs = np.random.RandomState(1)
        kv = (rs.randn(11, 256) * 3).astype(np.float32)
        t = pool.alloc_table(11)
        back = pool.append(t, kv)
        got = pool.gather(t)
        # append read-back IS the at-rest value
        np.testing.assert_array_equal(back, got)
        # per-scale-block error bound: int8 is a uniform grid (half a
        # step = absmax/127/2); fp8 e4m3 is a float format whose error is
        # RELATIVE to each value (3 mantissa bits -> half-ulp = |v|/16),
        # plus the shared-scale grid for subnormal-small values
        qmax = 127.0 if codec == "int8_block" else 448.0
        qb = pool.quant_block
        flat_in = kv.reshape(-1, qb)
        flat_out = got.reshape(-1, qb)
        step = np.abs(flat_in).max(axis=1, keepdims=True) / qmax
        if codec == "int8_block":
            tol = 0.5 * step + 1e-7
        else:
            tol = np.abs(flat_in) / 8.0 + step + 1e-7
        assert (np.abs(flat_in - flat_out) <= tol).all()

    def test_incremental_append_equals_gather(self):
        """Token-by-token appends (the decode path) must read back
        bit-identically to a fresh gather — quantize-once alignment."""
        pool = KVBlockPool(8, 4, 128, codec="int8_block")
        rs = np.random.RandomState(2)
        t = pool.alloc_table(9)
        rows = []
        for _ in range(9):
            row = rs.randn(1, 128).astype(np.float32)
            rows.append(pool.append(t, row))
        mirror = np.concatenate(rows)
        np.testing.assert_array_equal(mirror, pool.gather(t))

    def test_free_list_reuse_and_oom(self):
        pool = KVBlockPool(4, 4, 8, codec="fp32")
        t1 = pool.alloc_table(16)          # all 4 blocks
        assert pool.free_blocks == 0
        with pytest.raises(KVCacheOOM):
            pool.alloc_table(1)
        pool.free_table(t1)
        assert pool.free_blocks == 4
        t2 = pool.alloc_table(5)           # 2 blocks
        assert pool.free_blocks == 2 and len(t2.block_ids) == 2
        with pytest.raises(KVCacheOOM):
            pool.append(t2, np.zeros((9, 8), np.float32))  # > reservation

    def test_int8_bytes_le_quarter_of_fp32(self):
        pool = KVBlockPool(8, 16, 256, codec="int8_block")
        t = pool.alloc_table(40)
        pool.append(t, np.ones((40, 256), np.float32))
        ratio = pool.bytes_in_use() / pool.fp32_equiv_bytes()
        assert ratio <= 0.28, ratio   # 1/4 payload + 4/quant_block scales
        fp = KVBlockPool(8, 16, 256, codec="fp32")
        tf = fp.alloc_table(40)
        assert fp.block_bytes() * len(tf.block_ids) == fp.fp32_equiv_bytes()

    def test_quant_block_alignment_enforced(self):
        with pytest.raises(ValueError, match="must divide"):
            KVBlockPool(4, 4, 96, codec="int8_block", quant_block=64)

    def test_kernel_autotune_flag_path_identical(self):
        """The codec rides grad_comm._block_kernel_ops: with
        FLAGS_kernel_autotune on (CPU target -> jnp pair retained) the
        at-rest bits must be identical to the flag-off path."""
        from paddle_tpu.framework import flags

        rs = np.random.RandomState(3)
        kv = rs.randn(7, 128).astype(np.float32)
        pool_off = KVBlockPool(8, 4, 128, codec="int8_block")
        t_off = pool_off.alloc_table(7)
        pool_off.append(t_off, kv)
        flags.set_flags({"FLAGS_kernel_autotune": True})
        try:
            pool_on = KVBlockPool(8, 4, 128, codec="int8_block")
            t_on = pool_on.alloc_table(7)
            pool_on.append(t_on, kv)
            np.testing.assert_array_equal(pool_on._payload, pool_off._payload)
            np.testing.assert_array_equal(pool_on._scales, pool_off._scales)
            np.testing.assert_array_equal(pool_on.gather(t_on),
                                          pool_off.gather(t_off))
        finally:
            flags.set_flags({"FLAGS_kernel_autotune": False})

    def test_pallas_codec_kernels_match_jnp_pair(self):
        """The pallas codec kernels themselves (interpret mode on CPU)
        must produce the exact payload/decode the pool stores — the TPU
        flag-on path is bit-for-bit the tested one."""
        from paddle_tpu.distributed import grad_comm
        from paddle_tpu.ops.pallas import codec as pcodec

        rs = np.random.RandomState(4)
        flat = rs.randn(512).astype(np.float32)
        qb = 128
        absmax = grad_comm.block_absmax(flat, qb)
        scales = grad_comm.block_scales(absmax, "int8_block")
        q_ref = grad_comm.block_encode(flat, scales, qb, "int8_block")
        q_ker = pcodec.block_encode(flat, scales, qb, "int8_block")
        np.testing.assert_array_equal(np.asarray(q_ref), np.asarray(q_ker))
        d_ref = grad_comm.block_decode(q_ref, scales, 1, np.float32, 512)
        d_ker = pcodec.block_decode(q_ref, scales, 1, np.float32, 512)
        np.testing.assert_array_equal(np.asarray(d_ref), np.asarray(d_ker))


# ---------------------------------------------------------------------------
# decode-model adapter
# ---------------------------------------------------------------------------

class TestDecodeModel:
    def test_bucket_pow2(self):
        assert bucket_pow2(1) == 1
        assert bucket_pow2(3) == 4
        assert bucket_pow2(9, minimum=16) == 16
        assert bucket_pow2(900, minimum=16, maximum=64) == 64

    def test_prefill_matches_full_forward(self, dm):
        import paddle_tpu as paddle

        model = GPTForCausalLM(_mini_cfg(), seed=0)
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 64, (2, 9)).astype(np.int64)
        ref = model(paddle.to_tensor(ids)).numpy()
        got = dm.forced_logits(ids)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype,atol", [("float32", 1e-5),
                                            ("bfloat16", 0.05)])
    def test_teacher_forced_decode_parity(self, dm, dtype, atol):
        """Incremental prefill+decode logits == full-forward logits at
        every position (fp32 dense cache; under bf16 weights the step
        casts the cache down instead of promoting the residual stream)."""
        if dtype != "float32":
            dm = GPTDecodeModel(GPTForCausalLM(_mini_cfg(dtype=dtype),
                                               seed=0))
        rs = np.random.RandomState(1)
        seq = rs.randint(0, 64, (10,)).astype(np.int32)
        ref = dm.forced_logits(seq[None])[0].astype(np.float32)  # [s, V]
        last, kvs = dm.prefill([seq[:4]])
        np.testing.assert_allclose(last[0], ref[3], atol=atol)
        past = np.zeros((1, 16, dm.elems_per_token), np.float32)
        past[0, :4] = kvs[0]
        n = 4
        for t in range(4, 10):
            lg, kv = dm.decode(np.array([seq[t]]), np.array([n]), past,
                               np.array([n]))
            np.testing.assert_allclose(lg[0], ref[t], atol=atol)
            past[0, n] = kv[0]
            n += 1

    def test_prefill_batch_padding_inert(self, dm):
        """Ragged prompts prefilled together == prefilled alone (padding
        rows/positions must not leak into real rows)."""
        rs = np.random.RandomState(2)
        a, b_ = rs.randint(0, 64, (9,)), rs.randint(0, 64, (3,))
        last2, kv2 = dm.prefill([a, b_])
        la, kva = dm.prefill([a])
        lb, kvb = dm.prefill([b_])
        np.testing.assert_allclose(last2[0], la[0], atol=1e-5)
        np.testing.assert_allclose(last2[1], lb[0], atol=1e-5)
        np.testing.assert_allclose(kv2[0], kva[0], atol=1e-5)
        np.testing.assert_allclose(kv2[1], kvb[0], atol=1e-5)

    def test_prompt_bounds(self, dm):
        with pytest.raises(ValueError, match="empty"):
            dm.prefill([np.zeros((0,), np.int32)])
        with pytest.raises(ValueError, match="max_context"):
            dm.prefill([np.zeros((65,), np.int32)])

    def test_int8_kv_logits_parity_bound(self, dm):
        """Decode against an int8-at-rest cache stays within the codec
        error bound of the fp32-cache logits (the 'pinned output parity'
        of the acceptance criteria)."""
        rs = np.random.RandomState(3)
        seq = rs.randint(0, 64, (12,)).astype(np.int32)
        _, kvs = dm.prefill([seq])
        kv = kvs[0]
        pool = _pool(dm, codec="int8_block")
        t = pool.alloc_table(12)
        kv_q = pool.append(t, kv)
        S = 16
        past = np.zeros((1, S, dm.elems_per_token), np.float32)
        past_q = past.copy()
        past[0, :12], past_q[0, :12] = kv, kv_q
        lg, _ = dm.decode(np.array([5]), np.array([12]), past,
                          np.array([12]))
        lg_q, _ = dm.decode(np.array([5]), np.array([12]), past_q,
                            np.array([12]))
        # logits drift bounded; loose bound, tight enough to catch a
        # broken codec (which lands O(1) off) while allowing ~1% KV error
        assert np.abs(lg - lg_q).max() < 0.15


# ---------------------------------------------------------------------------
# continuous-batching engine
# ---------------------------------------------------------------------------

class TestServingEngine:
    def _engine(self, dm, codec="fp32", **kw):
        q = RequestQueue(max_depth=kw.pop("queue_depth", 64))
        pool = _pool(dm, codec=codec,
                     n_blocks=kw.pop("n_blocks", 32))
        return ServingEngine(dm, pool, q, max_batch=kw.pop("max_batch", 4),
                             **kw)

    def _reference_greedy(self, dm, prompt, max_new):
        last, kvs = dm.prefill([prompt])
        toks = [int(np.argmax(last[0]))]
        cap = len(prompt) + max_new
        S = bucket_pow2(cap, minimum=16)
        past = np.zeros((1, S, dm.elems_per_token), np.float32)
        past[0, :len(prompt)] = kvs[0]
        n = len(prompt)
        while len(toks) < max_new:
            lg, kv = dm.decode(np.array([toks[-1]]), np.array([n]), past,
                               np.array([n]))
            past[0, n] = kv[0]
            n += 1
            toks.append(int(np.argmax(lg[0])))
        return toks

    def test_paged_generation_matches_dense_reference(self, dm):
        """fp32 paged engine == dense-cache greedy reference, exactly,
        for a batch of ragged requests served concurrently."""
        eng = self._engine(dm)
        rs = np.random.RandomState(0)
        reqs = [ServeRequest(prompt_ids=rs.randint(0, 64, (3 + i,)),
                             max_new_tokens=3 + i) for i in range(4)]
        for r in reqs:
            assert eng.queue.submit(r)
        _drive(eng)
        for r in reqs:
            assert r.outcome == "completed"
            assert r.generated == self._reference_greedy(
                dm, r.prompt_ids, r.max_new_tokens), r.request_id

    def test_no_head_of_line_blocking(self, dm):
        """A short request admitted behind a long one finishes first —
        the decode batch is re-formed every step."""
        eng = self._engine(dm, max_batch=2)
        rs = np.random.RandomState(1)
        long = ServeRequest(prompt_ids=rs.randint(0, 64, (4,)),
                            max_new_tokens=24)
        short = ServeRequest(prompt_ids=rs.randint(0, 64, (4,)),
                             max_new_tokens=2)
        eng.queue.submit(long)
        eng.queue.submit(short)
        order = []
        for _ in range(60):
            eng.step()
            for r in (short, long):
                if r.outcome == "completed" and r.request_id not in order:
                    order.append(r.request_id)
            if len(order) == 2:
                break
        assert order == [short.request_id, long.request_id]

    def test_blocks_freed_on_completion_and_batch_reforms(self, dm):
        eng = self._engine(dm, max_batch=2, n_blocks=8)
        rs = np.random.RandomState(2)
        reqs = _reqs(rs, 5, prompt_len=4, max_new=3)
        for r in reqs:
            eng.queue.submit(r)
        _drive(eng)
        assert all(r.outcome == "completed" for r in reqs)
        assert eng.pool.blocks_in_use == 0
        assert eng.pool.free_blocks == 8
        assert eng.completed == 5

    def test_admission_rejects_at_depth(self, dm):
        before = _m_requests.labels(outcome="rejected").get()
        q = RequestQueue(max_depth=2)
        rs = np.random.RandomState(3)
        rr = _reqs(rs, 3)
        assert q.submit(rr[0]) and q.submit(rr[1])
        assert not q.submit(rr[2])
        assert _m_requests.labels(outcome="rejected").get() == before + 1
        assert _m_queue_depth.get() == 2

    def test_oversized_request_fails_cleanly(self, dm):
        eng = self._engine(dm)
        r = ServeRequest(prompt_ids=np.zeros((40,), np.int64),
                         max_new_tokens=60)   # budget 99 > max_context 64
        eng.queue.submit(r)
        _drive(eng)
        assert r.outcome == "failed" and "context" in r.error

    def test_put_back_when_pool_full_then_served(self, dm):
        """Admission defers (front put-back, not drop) while the pool
        has no room, and serves the request once blocks free up."""
        eng = self._engine(dm, n_blocks=4, max_batch=4)
        rs = np.random.RandomState(4)
        r1, r2 = _reqs(rs, 2, prompt_len=8, max_new=17)  # 3 blocks each
        eng.queue.submit(r1)
        eng.queue.submit(r2)
        _drive(eng)
        assert r1.outcome == "completed" and r2.outcome == "completed"

    @pytest.mark.parametrize("codec", ["int8_block", "fp8_block"])
    def test_quantized_engine_quarter_bytes_at_token_parity(self, dm, codec):
        """The same ragged requests through an fp32 and a quantized paged
        cache: the quantized pool peaks at <= 0.28 of the fp32 bytes
        (1/4 payload + scales) and serves >= 95 % of the same tokens."""
        rs = np.random.RandomState(5)
        specs = [(rs.randint(0, 64, (int(rs.randint(6, 14)),)),
                  int(rs.randint(6, 12))) for _ in range(8)]
        gen, peak = {}, {}
        for c in ("fp32", codec):
            eng = self._engine(dm, codec=c)
            reqs = [ServeRequest(prompt_ids=p, max_new_tokens=m)
                    for p, m in specs]
            for r in reqs:
                assert eng.queue.submit(r)
            peak[c] = 0
            while eng.step() or eng.running or eng.queue.depth:
                peak[c] = max(peak[c], eng.pool.bytes_in_use())
            assert all(r.outcome == "completed" for r in reqs)
            assert [len(r.generated) for r in reqs] == [m for _, m in specs]
            assert eng.pool.blocks_in_use == 0
            gen[c] = [r.generated for r in reqs]
        assert peak[codec] <= 0.28 * peak["fp32"], peak
        match = np.mean([np.mean([x == y for x, y in zip(a, b)])
                         for a, b in zip(gen["fp32"], gen[codec])])
        assert match >= 0.95, match

    def test_mirror_equals_pool_gather_mid_flight(self, dm):
        """The engine's incremental fp32 mirror must be bit-identical to
        a fresh dequantizing gather of the paged cache at every step —
        attention consumes exactly the at-rest bits."""
        eng = self._engine(dm, codec="int8_block", max_batch=2)
        rs = np.random.RandomState(6)
        for r in _reqs(rs, 2, prompt_len=5, max_new=8):
            eng.queue.submit(r)
        for _ in range(12):
            eng.step()
            for s in eng.running:
                np.testing.assert_array_equal(
                    s.mirror[:s.n_past], eng.pool.gather(s.table))
        _drive(eng)


# ---------------------------------------------------------------------------
# replica set: dispatch, chaos, eviction (the acceptance chaos phase)
# ---------------------------------------------------------------------------

class TestReplicaSet:
    def _submit_n(self, rset, rs, n, max_new=5):
        ids = []
        for r in _reqs(rs, n, prompt_len=5, max_new=max_new):
            assert rset.submit(r)
            ids.append(r.request_id)
        return ids

    def test_two_replicas_complete_everything(self, dm):
        rset = ReplicaSet(dm, n_replicas=2, n_blocks=32, block_tokens=8,
                          max_batch=4)
        rs = np.random.RandomState(0)
        with rset:
            ids = self._submit_n(rset, rs, 8)
            res = rset.wait(ids, timeout=60)
        assert len(res) == 8
        assert all(r.outcome == "completed" for r in res.values())
        # outputs equal the single-engine reference (shared zero-copy
        # weights; per-replica state must not leak into results)
        for r in res.values():
            q = RequestQueue(8)
            ref_eng = ServingEngine(dm, _pool(dm), q, max_batch=1)
            ref = ServeRequest(prompt_ids=r.prompt_ids,
                               max_new_tokens=r.max_new_tokens)
            q.submit(ref)
            _drive(ref_eng)
            assert r.generated == ref.generated

    @pytest.mark.parametrize("n_replicas", [2, 3])
    def test_hang_eviction_loses_zero_requests(self, dm, n_replicas):
        """CHAOS: replica 0 hangs mid-run holding live sequences; the
        watchdog evicts it, its requests drain + re-dispatch, and every
        accepted request still completes on the survivors."""
        gate = threading.Event()
        hung = threading.Event()

        def hang_hook(eng):
            if eng.running and not gate.is_set():
                hung.set()
                gate.wait(30)   # "stuck inside a step"

        rset = ReplicaSet(dm, n_replicas=n_replicas, n_blocks=32,
                          block_tokens=8, max_batch=2, watchdog_timeout=0.3,
                          pre_step_hooks={0: hang_hook})
        rs = np.random.RandomState(1)
        try:
            with rset:
                ids = self._submit_n(rset, rs, 10, max_new=6)
                assert hung.wait(20), "replica 0 never picked up work"
                res = rset.wait(ids, timeout=60)
                assert len(res) == 10, \
                    f"lost requests: {set(ids) - set(res)}"
                assert all(r.outcome == "completed" for r in res.values())
                deadline = time.monotonic() + 10
                while not rset.evictions and time.monotonic() < deadline:
                    time.sleep(0.02)
        finally:
            gate.set()      # release the zombie thread
        assert [e["reason"] for e in rset.evictions] == ["hang"]
        assert rset.evictions[0]["drained"] >= 1
        assert [e.alive for e in rset.engines] == \
            [False] + [True] * (n_replicas - 1)
        # drained requests were re-run from scratch on a survivor
        redone = [r for r in res.values() if r.attempts > 0]
        assert len(redone) >= 1
        assert all(len(r.generated) == 6 for r in res.values())

    @pytest.mark.parametrize("n_replicas", [1, 2])
    def test_open_loop_burst_answers_every_accepted_request(self, dm,
                                                            n_replicas):
        """Arrivals that do not wait for completions, more than the queue
        holds: the full queue refuses at the door, and everything it
        accepted is answered in full, with no KV block left behind."""
        rset = ReplicaSet(dm, n_replicas=n_replicas, n_blocks=32,
                          block_tokens=8, max_batch=2,
                          queue=RequestQueue(max_depth=6))
        rs = np.random.RandomState(3)
        offered = _reqs(rs, 40, prompt_len=5, max_new=5)
        refused0 = _m_requests.labels(outcome="rejected").get()
        # ten land before the workers start (six fit), thirty while they run
        accepted = [r for r in offered[:10] if rset.submit(r)]
        assert len(accepted) == 6
        with rset:
            accepted += [r for r in offered[10:] if rset.submit(r)]
            res = rset.wait([r.request_id for r in accepted], timeout=120)
        assert _m_requests.labels(outcome="rejected").get() - refused0 == \
            len(offered) - len(accepted)
        assert sorted(res) == sorted(r.request_id for r in accepted)
        assert all(r.outcome == "completed" and len(r.generated) == 5
                   for r in res.values())
        assert all(e.pool.blocks_in_use == 0 for e in rset.engines)

    def test_crash_eviction_loses_zero_requests(self, dm):
        """CHAOS: a replica whose step RAISES is evicted and drained."""
        state = {"armed": True}

        def crash_hook(eng):
            if eng.running and state["armed"]:
                state["armed"] = False
                raise RuntimeError("injected replica crash")

        rset = ReplicaSet(dm, n_replicas=2, n_blocks=32, block_tokens=8,
                          max_batch=2, pre_step_hooks={0: crash_hook})
        rs = np.random.RandomState(2)
        with rset:
            ids = self._submit_n(rset, rs, 8)
            res = rset.wait(ids, timeout=60)
        assert len(res) == 8
        assert all(r.outcome == "completed" for r in res.values())
        assert [e["reason"] for e in rset.evictions] == ["error"]

    def test_corrupt_replica_evicted_by_guard(self, dm):
        """CHAOS: a replica serving from corrupted weights diverges from
        the boot-time ReplicaGuard digest and is evicted."""
        import jax.numpy as jnp

        bad = GPTDecodeModel.__new__(GPTDecodeModel)
        bad.__dict__.update(dm.__dict__)
        bad.params = dict(dm.params)
        w = np.array(bad.params["fc1_w"])
        w[0, 0, 0] += 1.0   # SDC: one flipped weight
        bad.params["fc1_w"] = jnp.asarray(w)
        rset = ReplicaSet(dm, n_replicas=2, n_blocks=32, block_tokens=8,
                          max_batch=2, guard_every=1, models=[bad, dm])
        rs = np.random.RandomState(3)
        with rset:
            ids = self._submit_n(rset, rs, 6)
            res = rset.wait(ids, timeout=60)
        assert len(res) == 6
        assert all(r.outcome == "completed" for r in res.values())
        assert [e["reason"] for e in rset.evictions] == ["corrupt"]
        assert not rset.engines[0].alive

    def test_serving_exposition_section(self, dm):
        from paddle_tpu.observability.exposition import TelemetryServer

        rset = ReplicaSet(dm, n_replicas=1, n_blocks=16, block_tokens=8,
                          max_batch=2)
        rs = np.random.RandomState(4)
        with rset, TelemetryServer(port=0) as srv:
            ids = self._submit_n(rset, rs, 3)
            rset.wait(ids, timeout=60)
            with urllib.request.urlopen(srv.url + "/serving",
                                        timeout=5) as resp:
                doc = json.loads(resp.read())
        assert doc["alive_replicas"] == 1
        assert doc["replicas"][0]["name"] == "replica-0"
        assert doc["replicas"][0]["kv"]["codec"] == "fp32"
        assert doc["latency_ms"]["count"] >= 3
        assert doc["latency_ms"]["p99"] is not None
        # unregistered after stop: the route 404s again
        with TelemetryServer(port=0) as srv2:
            req = urllib.request.Request(srv2.url + "/serving")
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(req, timeout=5)

    def test_outcome_accounting(self, dm):
        done0 = _m_requests.labels(outcome="completed").get()
        rset = ReplicaSet(dm, n_replicas=1, n_blocks=16, block_tokens=8,
                          max_batch=2)
        rs = np.random.RandomState(5)
        with rset:
            ids = self._submit_n(rset, rs, 4)
            res = rset.wait(ids, timeout=60)
        assert len(res) == 4
        assert _m_requests.labels(outcome="completed").get() == done0 + 4

    def test_flags_defaults_wired(self, dm):
        from paddle_tpu.framework.flags import get_flags

        f = get_flags(["FLAGS_serving_block_tokens",
                       "FLAGS_serving_max_batch",
                       "FLAGS_serving_queue_depth",
                       "FLAGS_serving_kv_codec",
                       "FLAGS_serving_watchdog_s"])
        assert f["FLAGS_serving_kv_codec"] == "fp32"
        rset = ReplicaSet(dm, n_replicas=1, n_blocks=4)
        assert rset.queue.max_depth == f["FLAGS_serving_queue_depth"]
        assert rset.engines[0].max_batch == f["FLAGS_serving_max_batch"]
        assert rset.engines[0].pool.block_tokens == \
            f["FLAGS_serving_block_tokens"]
        assert rset.codec == "fp32"


# ---------------------------------------------------------------------------
# request-scoped tracing (ISSUE 18)
# ---------------------------------------------------------------------------

class TestRequestTracing:
    def _submit_n(self, rset, rs, n, max_new=5):
        ids = []
        for r in _reqs(rs, n, prompt_len=5, max_new=max_new):
            assert rset.submit(r)
            ids.append(r.request_id)
        return ids

    def test_traces_section_lifecycle(self, dm):
        """/traces (index) and /traces/<id> serve the trace store while
        the ReplicaSet runs; unknown ids 404; after stop the whole route
        404s again (satellite 3)."""
        from paddle_tpu.observability.exposition import TelemetryServer

        rset = ReplicaSet(dm, n_replicas=1, n_blocks=16, block_tokens=8,
                          max_batch=2)
        rs = np.random.RandomState(6)
        with rset, TelemetryServer(port=0) as srv:
            ids = self._submit_n(rset, rs, 3)
            res = rset.wait(ids, timeout=60)
            assert all(r.trace is not None for r in res.values())
            with urllib.request.urlopen(srv.url + "/traces",
                                        timeout=5) as resp:
                idx = json.loads(resp.read())
            listed = {t["trace_id"]: t for t in idx["traces"]}
            r0 = res[ids[0]]
            assert r0.trace.trace_id in listed
            assert listed[r0.trace.trace_id]["request_id"] == ids[0]
            with urllib.request.urlopen(
                    srv.url + "/traces/" + r0.trace.trace_id,
                    timeout=5) as resp:
                doc = json.loads(resp.read())
            names = [s["name"] for s in doc["spans"]]
            assert names[0] == "queue_wait" and names[-1] == "retire"
            assert "prefill" in names and "decode_step" in names
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(srv.url + "/traces/t0-nope",
                                       timeout=5)
            assert e.value.code == 404
        # unregistered after stop: the route 404s again
        with TelemetryServer(port=0) as srv2:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(srv2.url + "/traces", timeout=5)
            assert e.value.code == 404

    def test_chaos_trace_names_every_hop(self, dm):
        """Acceptance (ISSUE 18): replica hangs mid-decode -> watchdog
        eviction -> requeue-at-head -> completion on the survivor yields
        ONE trace whose spans name every hop, retrievable over /traces/
        <id> starting from an exemplar on the latency histogram."""
        from paddle_tpu.observability.exposition import TelemetryServer
        from paddle_tpu.observability.tracing import get_tracer
        from paddle_tpu.serving.engine import _m_latency

        gate = threading.Event()
        hung = threading.Event()

        def hang_hook(eng):
            if eng.running and not gate.is_set():
                hung.set()
                gate.wait(30)

        rset = ReplicaSet(dm, n_replicas=2, n_blocks=32, block_tokens=8,
                          max_batch=2, watchdog_timeout=0.3,
                          pre_step_hooks={0: hang_hook})
        rs = np.random.RandomState(7)
        try:
            with rset, TelemetryServer(port=0) as srv:
                ids = self._submit_n(rset, rs, 6, max_new=6)
                assert hung.wait(20), "replica 0 never picked up work"
                res = rset.wait(ids, timeout=60)
                assert len(res) == 6
                assert all(r.outcome == "completed"
                           for r in res.values())
                redone = [r for r in res.values() if r.attempts > 0]
                assert redone, "no request survived an eviction"
                tid = redone[0].trace.trace_id
                with urllib.request.urlopen(srv.url + "/traces/" + tid,
                                            timeout=5) as resp:
                    doc = json.loads(resp.read())
        finally:
            gate.set()
        names = [s["name"] for s in doc["spans"]]
        # every hop of the journey, in causal order: admitted, started on
        # the doomed replica, evicted, requeued at head, re-admitted and
        # finished on the survivor
        for hop in ("queue_wait", "prefill", "eviction", "requeue_front",
                    "retire"):
            assert hop in names, f"missing hop {hop!r} in {names}"
        assert names.count("queue_wait") == 2      # two admissions
        assert names.index("eviction") < names.index("requeue_front") \
            < names.index("retire")
        retire = [s for s in doc["spans"] if s["name"] == "retire"][-1]
        assert retire["fields"]["outcome"] == "completed"
        assert retire["fields"]["attempt"] >= 1
        evicted = [s for s in doc["spans"] if s["name"] == "eviction"]
        assert evicted[0]["fields"]["reason"] == "hang"
        assert evicted[0]["fields"]["replica"] == "replica-0"
        # the trace is reachable FROM the telemetry: some latency-bucket
        # exemplar resolves to a trace that names the eviction hop
        exemplars = (_m_latency.get().get("exemplars") or {}).values()
        store = get_tracer().store
        traced = [store.get(e["trace_id"]) for e in exemplars]
        assert any(t and any(s["name"] == "eviction" for s in t["spans"])
                   for t in traced), \
            "no exemplar led to a trace naming the eviction"


class TestFleetScaling:
    """Policy-driven replica scaling (ISSUE 17): the drain + re-admit
    path loses zero requests, sync pump() drives a set deterministically,
    and the compile-aware watchdog grace keeps a slow-compiling replica
    alive where a stalled serving replica is evicted."""

    def test_caller_queue_is_shared_even_when_empty(self, dm):
        """Regression: RequestQueue defines __len__, so an EMPTY queue is
        FALSY — `queue or RequestQueue(...)` silently replaced the
        caller's queue and every externally-submitted request vanished.
        The fleet harness submits through exactly this shape."""
        q = RequestQueue(max_depth=8)
        assert len(q) == 0 and not q      # the trap: empty == falsy
        rset = ReplicaSet(dm, n_replicas=2, queue=q, n_blocks=32,
                          block_tokens=8, max_batch=2)
        assert rset.queue is q
        assert all(e.queue is q for e in rset.engines)
        req = ServeRequest(prompt_ids=np.array([1, 2, 3]),
                           max_new_tokens=2)
        q.submit(req)
        rset.pump(ticks=8)
        assert req.outcome == "completed"

    def test_engine_state_boot_compiling_serving(self, dm):
        q = RequestQueue(8)
        eng = ServingEngine(dm, _pool(dm), q, max_batch=2)
        assert eng.state == "boot"
        # idle ticks before the first request must NOT leave "compiling"
        # — the first real admission is what triggers the jit compile,
        # and the watchdog grace has to still be covering it then
        eng.step()
        assert eng.state == "compiling"
        seen = {}

        def spy(e):
            seen["during"] = e.state

        eng.pre_step = spy
        q.submit(ServeRequest(prompt_ids=np.array([1, 2, 3]),
                              max_new_tokens=2))
        eng.step()                  # first REAL step: admits + compiles
        assert seen["during"] == "compiling"
        assert eng.state == "serving"
        assert eng.stats()["state"] == "serving"

    def test_compile_guard_covers_first_shape_bucket(self, dm):
        """A model call on a never-executed shape bucket runs under
        state="compiling" (the first call per bucket may XLA-compile for
        ~seconds; a watchdog sized for a decode tick would read that as
        a hang and evict the survivor). A repeat bucket stays covered by
        whatever state the step is in."""
        q = RequestQueue(8)
        eng = ServingEngine(dm, _pool(dm), q, max_batch=2)
        eng._warm = True
        eng.state = "serving"
        with eng._compile_guard("decode", 2, 16):
            assert eng.state == "compiling"
        assert ("decode", 2, 16) in eng._seen_buckets
        # second encounter: no state flip, the bucket is warm
        eng.state = "serving"
        with eng._compile_guard("decode", 2, 16):
            assert eng.state == "serving"
        # a failed first call does NOT mark the bucket — the retry must
        # still run under grace
        try:
            with eng._compile_guard("extend", 4, 32, 3):
                raise RuntimeError("interrupted compile")
        except RuntimeError:
            pass
        assert ("extend", 4, 32, 3) not in eng._seen_buckets
        # a served request leaves its real buckets behind
        q.submit(ServeRequest(prompt_ids=np.array([1, 2, 3]),
                              max_new_tokens=2))
        _drive(eng)
        assert any(k[0] == "prefill" for k in eng._seen_buckets)
        assert any(k[0] == "decode" for k in eng._seen_buckets)

    def test_drain_recovers_mid_admission_intake(self, dm):
        """Requests popped from the queue but not yet landed in
        ``running`` (mid-prefill) must be visible to drain() — a
        scale-down racing _admit() on a HEALTHY replica would otherwise
        silently lose the batch being built."""
        q = RequestQueue(8)
        eng = ServingEngine(dm, _pool(dm), q, max_batch=2)
        r = ServeRequest(prompt_ids=np.array([4, 5, 6]), max_new_tokens=2)
        eng._intake.append(r)       # as _admit() holds it mid-prefill
        drained = eng.drain()
        assert not eng.alive
        assert [d.request_id for d in drained] == [r.request_id]
        assert drained[0].attempts == r.attempts + 1
        assert eng._intake == []
        # the worker's release is told the reincarnated copy is now
        # authoritative (so it won't also finish/requeue the original)
        assert eng._intake_discard(r) is False

    def test_intake_discard_is_identity_based(self, dm):
        """dataclass == on ServeRequest trips numpy's ambiguous-truth
        error (prompt_ids is an array); _intake_discard must match by
        identity, releasing exactly the object it was handed."""
        q = RequestQueue(8)
        eng = ServingEngine(dm, _pool(dm), q, max_batch=2)
        a = ServeRequest(prompt_ids=np.array([1, 2, 3]), max_new_tokens=2)
        b = ServeRequest(prompt_ids=np.array([9, 8, 7]), max_new_tokens=2)
        eng._intake.extend([a, b])
        assert eng._intake_discard(b) is True
        assert eng._intake == [a]
        assert eng._intake_discard(b) is False
        assert eng._intake == [a]

    def test_sync_scale_down_drains_and_readmits(self, dm):
        """The controller's serve_to_train path: retire a BUSY replica
        mid-flight; its running requests re-enter at the queue head and
        every accepted request still completes. Zero lost."""
        q = RequestQueue(max_depth=16)
        rset = ReplicaSet(dm, n_replicas=2, queue=q, n_blocks=32,
                          block_tokens=8, max_batch=2)
        rs = np.random.RandomState(7)
        reqs = _reqs(rs, 6, max_new=4)
        for r in reqs:
            assert rset.submit(r)
        rset.pump(ticks=2)          # both replicas pick up work
        assert rset.engines[1].running, "replica 1 never got in-flight work"
        ev = rset.scale_down(reason="fleet_policy")
        assert ev is not None and ev["direction"] == "down"
        assert ev["reason"] == "fleet_policy" and ev["drained"] >= 1
        assert rset.alive_replicas == 1
        rset.pump(ticks=60)         # the survivor absorbs the re-admits
        # drained requests finish as REINCARNATED objects (same
        # request_id, attempts+1) — judge by the result table, the same
        # identity the fleet ledger counts
        assert len(rset.results) == 6
        assert {r.request_id for r in reqs} == set(rset.results)
        assert all(r.outcome == "completed" for r in rset.results.values())
        assert any(r.attempts > 0 for r in rset.results.values())
        assert rset.stats()["scale_events"] == [ev]

    def test_sync_scale_up_adds_serving_capacity(self, dm):
        rset = ReplicaSet(dm, n_replicas=1, n_blocks=32, block_tokens=8,
                          max_batch=2)
        idx = rset.scale_up(reason="fleet_policy")
        assert idx == 1 and rset.alive_replicas == 2
        assert rset.scale_events[-1]["direction"] == "up"
        rs = np.random.RandomState(8)
        reqs = _reqs(rs, 4, max_new=3)
        for r in reqs:
            assert rset.submit(r)
        rset.pump(ticks=40)
        assert all(r.outcome == "completed" for r in reqs)
        # both engines did real work — the new replica is not a stub
        assert all(e.steps > 0 for e in rset.engines)

    def test_slow_compile_survives_watchdog_grace(self, dm):
        """Satellite 1: a replica stuck in its first (compiling) step for
        longer than watchdog_timeout is NOT evicted while compile_grace
        covers it, and serves normally once warm."""
        def slow_compile(eng):
            if eng.steps == 0:
                time.sleep(0.9)     # 3x the watchdog timeout

        rset = ReplicaSet(dm, n_replicas=2, n_blocks=32, block_tokens=8,
                          max_batch=2, watchdog_timeout=0.3,
                          compile_grace=30.0,
                          pre_step_hooks={0: slow_compile})
        rs = np.random.RandomState(9)
        with rset:
            ids = [r.request_id for r in _reqs(rs, 6, max_new=3)
                   if rset.submit(r) or True]
            res = rset.wait(ids, timeout=60)
        assert len(res) == 6
        assert rset.evictions == []
        assert all(e.alive for e in rset.engines)

    def test_stall_without_grace_is_still_evicted(self, dm):
        """Control for the grace test: the same stall AFTER the first
        step (state == serving) fires the watchdog — compile grace must
        not blind it to real hangs."""
        gate = threading.Event()

        def hang_warm(eng):
            if eng.running and not gate.is_set():
                gate.wait(20)       # stuck while state == "serving"

        rset = ReplicaSet(dm, n_replicas=2, n_blocks=32, block_tokens=8,
                          max_batch=2, watchdog_timeout=0.3,
                          compile_grace=30.0,
                          pre_step_hooks={0: hang_warm})
        rs = np.random.RandomState(10)
        try:
            with rset:
                ids = [r.request_id for r in _reqs(rs, 8, max_new=4)
                       if rset.submit(r) or True]
                res = rset.wait(ids, timeout=60)
                assert len(res) == 8
                deadline = time.monotonic() + 10
                while not rset.evictions and time.monotonic() < deadline:
                    time.sleep(0.02)
        finally:
            gate.set()
        assert [e["reason"] for e in rset.evictions] == ["hang"]
        assert not rset.engines[0].alive and rset.engines[1].alive

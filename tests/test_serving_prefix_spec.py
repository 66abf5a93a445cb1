"""Prefix-cached, sampled, speculative serving (ISSUE 16).

Contracts pinned here:
- CounterKeyStream / BatchSampler: per-request counter-based RNG streams
  — a request's token at position i depends only on (sampler seed,
  request identity, i), never on batch placement; temperature=0 IS
  np.argmax (the pre-ISSUE-16 greedy, token-for-token).
- Pool prefix cache: chain-keyed block sharing with refcounts, LRU over
  refcount-0 blocks (evictions counted), copy-on-write before any append
  into a shared block (the sharer's bytes never move), reserve/rollback
  scratch leak-free.
- Engine: cache on/off greedy parity + hit/miss accounting; appending
  past a shared prefix never mutates bytes another live sequence reads
  (mirror == pool.gather bit-exact for BOTH, mid-flight); replica
  eviction + requeue replays top-p sampled requests bit-identically.
- Speculative decode: draft-proposed tokens are verified losslessly —
  outputs are token-for-token the non-speculative sampler's, accepted
  tokens/step > 1 with a self-draft, and zero KV blocks leak even under
  replica chaos.
"""
import threading

import numpy as np
import pytest

from paddle_tpu.framework.random import CounterKeyStream
from paddle_tpu.models import GPTForCausalLM, gpt_presets
from paddle_tpu.serving import (
    BatchSampler, GPTDecodeModel, KVBlockPool, ReplicaSet, RequestQueue,
    SamplingParams, ServeRequest, ServingEngine,
)


@pytest.fixture(autouse=True)
def reset_mesh(fresh_mesh):
    """Same rationale as tests/test_serving.py: clear any ambient mesh a
    prior suite left behind."""


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


def _drive(engine, max_steps=300):
    for _ in range(max_steps):
        worked = engine.step()
        if not worked and not engine.running and not engine.queue.depth:
            return
    raise AssertionError("engine did not drain")


def _run(dm, prompts, max_new=6, sampling=None, **ekw):
    q = RequestQueue()
    eng = ServingEngine(dm, _pool(dm), q, **ekw)
    reqs = [ServeRequest(prompt_ids=np.asarray(p), max_new_tokens=max_new,
                         request_id=f"r{i}",
                         **({"sampling": sampling} if sampling else {}))
            for i, p in enumerate(prompts)]
    for r in reqs:
        q.submit(r)
    _drive(eng)
    assert all(r.outcome == "completed" for r in reqs)
    return eng, reqs


# ---------------------------------------------------------------------------
# RNG streams + sampler
# ---------------------------------------------------------------------------

class TestCounterKeyStream:
    def test_keys_depend_only_on_identity_and_counter(self):
        import jax.random

        a, b = CounterKeyStream(seed=7), CounterKeyStream(seed=7)
        # query in different orders: same (identity, counter) -> same key
        k1 = a.key("req-x", 3)
        a.key("req-y", 0)
        b.key("req-y", 9)
        k2 = b.key("req-x", 3)
        np.testing.assert_array_equal(jax.random.key_data(k1),
                                      jax.random.key_data(k2))
        # distinct counters and identities give distinct keys
        assert not np.array_equal(jax.random.key_data(a.key("req-x", 4)),
                                  jax.random.key_data(k1))
        assert not np.array_equal(jax.random.key_data(a.key("req-z", 3)),
                                  jax.random.key_data(k1))

    def test_seed_separates_streams(self):
        import jax.random

        assert not np.array_equal(
            jax.random.key_data(CounterKeyStream(0).key("r", 0)),
            jax.random.key_data(CounterKeyStream(1).key("r", 0)))


class TestBatchSampler:
    def _logits(self, rs, n, vocab=64):
        return (rs.randn(n, vocab) * 3).astype(np.float32)

    def test_temperature_zero_is_argmax(self):
        rs = np.random.RandomState(0)
        logits = self._logits(rs, 5)
        s = BatchSampler(seed=0)
        toks = s.sample(logits, [SamplingParams()] * 5,
                        [f"r{i}" for i in range(5)], [0] * 5)
        np.testing.assert_array_equal(toks, np.argmax(logits, axis=-1))

    def test_batch_placement_invariance(self):
        """The token sampled for (request, position) must not depend on
        which other rows share the batch — the eviction/requeue replay
        contract at the sampler level."""
        rs = np.random.RandomState(1)
        logits = self._logits(rs, 4)
        sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.9)
        s = BatchSampler(seed=3)
        full = s.sample(logits, [sp] * 4,
                        ["a", "b", "c", "d"], [5, 6, 7, 8])
        solo = s.sample(logits[2:3], [sp], ["c"], [7])
        assert full[2] == solo[0]
        # and reversed batch order
        rev = s.sample(logits[::-1].copy(), [sp] * 4,
                       ["d", "c", "b", "a"], [8, 7, 6, 5])
        np.testing.assert_array_equal(rev[::-1], full)

    def test_top_k_one_is_argmax(self):
        rs = np.random.RandomState(2)
        logits = self._logits(rs, 3)
        s = BatchSampler(seed=0)
        sp = SamplingParams(temperature=1.5, top_k=1)
        toks = s.sample(logits, [sp] * 3, ["x", "y", "z"], [0, 1, 2])
        np.testing.assert_array_equal(toks, np.argmax(logits, axis=-1))

    def test_top_p_keeps_nucleus_only(self):
        """With one token holding ~all probability mass, any top_p keeps
        exactly that token."""
        logits = np.full((2, 64), -10.0, np.float32)
        logits[0, 17] = 10.0
        logits[1, 42] = 10.0
        s = BatchSampler(seed=5)
        sp = SamplingParams(temperature=1.0, top_p=0.5)
        toks = s.sample(logits, [sp] * 2, ["p", "q"], [0, 0])
        np.testing.assert_array_equal(toks, [17, 42])

    def test_explicit_seed_overrides_request_identity(self):
        rs = np.random.RandomState(3)
        logits = self._logits(rs, 1)
        s = BatchSampler(seed=0)
        sp = SamplingParams(temperature=0.9, seed=123)
        a = s.sample(logits, [sp], ["first-id"], [4])
        b = s.sample(logits, [sp], ["other-id"], [4])
        assert a[0] == b[0]


# ---------------------------------------------------------------------------
# Pool-level prefix cache
# ---------------------------------------------------------------------------

class TestPrefixCachePool:
    def _fill(self, pool, table, n, seed=0):
        rs = np.random.RandomState(seed)
        kv = rs.randn(n, pool.elems_per_token).astype(np.float32)
        pool.append(table, kv)
        return kv

    def test_full_block_sharing_and_refcount(self, dm):
        pool = _pool(dm)
        prompt = np.arange(20, dtype=np.int32)  # 2 full blocks + 4 rows
        a = pool.alloc_table(24, prefix_tokens=prompt)
        assert a.n_tokens == 0 and a.n_shared == 0
        kv = self._fill(pool, a, 20)
        pool.register_prefix(a, prompt)
        assert pool.probe_prefix(prompt) == 20
        b = pool.alloc_table(24, prefix_tokens=prompt)
        # b shares a's blocks: full blocks by id, partial via COW spare
        assert b.n_tokens == 20 and b.n_shared == 3
        assert b.block_ids[:2] == a.block_ids[:2]
        assert b.block_ids[2] == a.block_ids[2] and b.cow_spare is not None
        np.testing.assert_array_equal(pool.gather(b), kv)
        # releasing one sharer must not free the other's data
        pool.free_table(b)
        np.testing.assert_array_equal(pool.gather(a), kv)
        pool.free_table(a)
        assert pool.blocks_in_use == 0
        assert pool.cached_blocks >= 2  # indexed blocks parked in LRU

    def test_lru_eviction_recycles_cold_blocks(self, dm):
        pool = _pool(dm, n_blocks=8)
        prompts = [np.full((8,), i, np.int32) for i in range(7)]
        for i, p in enumerate(prompts):
            t = pool.alloc_table(8, prefix_tokens=p)
            self._fill(pool, t, 8, seed=i)
            pool.register_prefix(t, p)
            pool.free_table(t)
        # 7 distinct one-block prefixes through an 8-block pool: the
        # oldest entries were evicted from the LRU to make room
        assert pool.blocks_in_use == 0
        assert pool.cached_blocks <= 8
        # hottest (= most recent) prefix still resident, coldest gone
        assert pool.probe_prefix(prompts[-1]) == 8

    def test_cow_before_append_preserves_sharer_bytes(self, dm):
        pool = _pool(dm)
        prompt = np.arange(12, dtype=np.int32)  # block0 full, block1: 4 rows
        a = pool.alloc_table(20, prefix_tokens=prompt)
        kv_a = self._fill(pool, a, 12)
        pool.register_prefix(a, prompt)
        b = pool.alloc_table(20, prefix_tokens=prompt)
        assert b.n_shared == 2 and b.cow_spare is not None
        shared_block = b.block_ids[1]
        # b appends past the shared prefix -> COW must fire
        rs = np.random.RandomState(9)
        kv_b_new = rs.randn(3, pool.elems_per_token).astype(np.float32)
        pool.append(b, kv_b_new)
        assert b.block_ids[1] != shared_block  # b moved to its copy
        assert b.n_shared == 1 and b.cow_spare is None
        # a's bytes never moved; b reads prefix + its own suffix
        np.testing.assert_array_equal(pool.gather(a), kv_a)
        np.testing.assert_array_equal(pool.gather(b)[:12], kv_a)
        got_b = pool.gather(b)[12:]
        np.testing.assert_array_equal(
            got_b, kv_b_new)  # fp32 codec: bit-exact
        pool.free_table(a)
        pool.free_table(b)
        assert pool.blocks_in_use == 0

    def test_reserve_rollback_leak_free(self, dm):
        pool = _pool(dm)
        t = pool.alloc_table(10)
        self._fill(pool, t, 10)
        base_blocks = len(t.block_ids)
        pool.reserve(t, 9)  # spec scratch: k+1 lookahead
        assert len(t.block_ids) > base_blocks
        rs = np.random.RandomState(4)
        pool.append(t, rs.randn(9, pool.elems_per_token).astype(np.float32))
        pool.rollback(t, 7)  # reject 7 of the 9 drafted rows
        assert t.n_tokens == 12
        assert len(t.block_ids) == max(base_blocks,
                                       pool.blocks_needed(12))
        pool.free_table(t)
        assert pool.blocks_in_use == 0


# ---------------------------------------------------------------------------
# Engine: cached admission + COW + sampling replay
# ---------------------------------------------------------------------------

class TestEnginePrefixCache:
    @pytest.mark.parametrize("traffic", ["one_prompt_thrice",
                                         "zipf_system_prompts"])
    def test_cache_on_off_greedy_parity_and_hit_accounting(self, dm,
                                                           traffic):
        """Shared prefixes prefill once: the cache hits, computes fewer
        prefill tokens than the uncached engine, and serves the same
        tokens. The second traffic is a chat endpoint's: a few long system
        prompts drawn Zipf(1.1), each with a short suffix of its own."""
        from paddle_tpu.serving.engine import (
            _m_prefill_tok, _m_prefix_hit, _m_prefix_miss,
        )

        rs = np.random.RandomState(0)
        if traffic == "one_prompt_thrice":
            shared = rs.randint(0, 64, (20,))
            prompts = [shared, shared, shared]
        else:
            system = [rs.randint(0, 64, (24,)) for _ in range(3)]
            w = 1.0 / np.arange(1, 4) ** 1.1
            prompts = [np.concatenate([system[int(rs.choice(3, p=w / w.sum()))],
                                       rs.randint(0, 64, (3,))])
                       for _ in range(8)]
        hit0, miss0 = _m_prefix_hit.get(), _m_prefix_miss.get()
        pre0 = _m_prefill_tok.get()
        eng_on, r_on = _run(dm, prompts, max_new=4)
        computed_on = _m_prefill_tok.get() - pre0
        hits = _m_prefix_hit.get() - hit0
        assert hits > 0
        assert _m_prefix_miss.get() - miss0 > 0
        pre0 = _m_prefill_tok.get()
        _, r_off = _run(dm, prompts, max_new=4, prefix_cache=False)
        computed_off = _m_prefill_tok.get() - pre0
        assert [r.generated for r in r_on] == [r.generated for r in r_off]
        # every hit token is a prefill token the cached engine did not compute
        assert computed_off == sum(len(p) for p in prompts)
        assert computed_on == computed_off - hits
        assert eng_on.pool.blocks_in_use == 0

    def test_cow_pinned_mid_flight_mirror_equals_gather(self, dm):
        """Two live sequences share a prompt prefix; each samples a
        DIFFERENT continuation (distinct request ids). At every step both
        sequences' incremental mirrors must equal pool.gather bit-exactly
        — i.e. appending past the shared prefix copied, never mutated,
        bytes the other sequence still reads."""
        rs = np.random.RandomState(1)
        shared = rs.randint(0, 64, (17,))  # partial tail block: COW fires
        sp = SamplingParams(temperature=1.2, top_k=0, top_p=1.0)
        q = RequestQueue()
        eng = ServingEngine(dm, _pool(dm), q)
        reqs = [ServeRequest(prompt_ids=shared.copy(), max_new_tokens=6,
                             request_id=f"cow{i}", sampling=sp)
                for i in range(2)]
        for r in reqs:
            q.submit(r)
        checked = 0
        for _ in range(300):
            worked = eng.step()
            for s in eng.running:
                np.testing.assert_array_equal(
                    s.mirror[:s.n_past], eng.pool.gather(s.table))
                checked += 1
            if not worked and not eng.running and not q.depth:
                break
        assert checked > 0
        assert all(r.outcome == "completed" for r in reqs)
        # distinct ids -> distinct streams -> the continuations diverged
        # (shared-prefix COW actually exercised divergent appends)
        assert reqs[0].generated != reqs[1].generated
        assert eng.pool.blocks_in_use == 0

    def test_eviction_requeue_replays_bit_identical(self, dm):
        """CHAOS + sampling: a hung replica's top-p requests re-run on
        the survivor and must land the SAME sampled tokens — position-
        keyed streams make replay independent of replica and batch."""
        sp = SamplingParams(temperature=0.9, top_k=16, top_p=0.9)
        rs = np.random.RandomState(2)
        prompts = [rs.randint(0, 64, (6,)) for _ in range(6)]
        # reference: clean single-replica run
        _, ref = _run(dm, prompts, max_new=6, sampling=sp)
        expect = {r.request_id: r.generated for r in ref}

        gate, hung = threading.Event(), threading.Event()

        def hang_hook(eng):
            if eng.running and not gate.is_set():
                hung.set()
                gate.wait(30)

        rset = ReplicaSet(dm, n_replicas=2, n_blocks=32, block_tokens=8,
                          max_batch=2, watchdog_timeout=0.3,
                          pre_step_hooks={0: hang_hook})
        try:
            with rset:
                ids = []
                for i, p in enumerate(prompts):
                    r = ServeRequest(prompt_ids=p, max_new_tokens=6,
                                     request_id=f"r{i}", sampling=sp)
                    assert rset.submit(r)
                    ids.append(r.request_id)
                assert hung.wait(20)
                res = rset.wait(ids, timeout=60)
        finally:
            gate.set()
        assert len(res) == 6
        assert [e["reason"] for e in rset.evictions] == ["hang"]
        replayed = [r for r in res.values() if r.attempts > 0]
        assert replayed, "chaos run must actually replay something"
        for rid, r in res.items():
            assert r.generated == expect[rid], \
                f"{rid} replay diverged (attempts={r.attempts})"


# ---------------------------------------------------------------------------
# Speculative decoding
# ---------------------------------------------------------------------------

class TestSpeculative:
    @pytest.mark.parametrize("spec_k", [2, 4])
    @pytest.mark.parametrize("sampling", [
        None, SamplingParams(temperature=0.8, top_k=20, top_p=0.95)],
        ids=["greedy", "top_p"])
    def test_lossless_vs_non_speculative(self, dm, sampling, spec_k):
        rs = np.random.RandomState(3)
        prompts = [rs.randint(0, 64, (6,)) for _ in range(3)]
        _, ref = _run(dm, prompts, max_new=10, sampling=sampling)
        eng, got = _run(dm, prompts, max_new=10, sampling=sampling,
                        draft_model=dm.truncated(1), spec_k=spec_k)
        for a, b in zip(ref, got):
            assert a.generated == b.generated
        # a verify step commits at least the target's own token and at
        # most the k proposed plus one: accepted <= proposed
        assert eng.spec_steps > 0
        assert eng.spec_steps <= eng.spec_emitted \
            <= (spec_k + 1) * eng.spec_steps
        assert eng.pool.blocks_in_use == 0

    def test_self_draft_accepts_everything(self, dm):
        """Draft == target: every proposal verifies, so each spec step
        commits k+1 tokens (up to the max_new_tokens tail)."""
        rs = np.random.RandomState(4)
        eng, _ = _run(dm, [rs.randint(0, 64, (6,))], max_new=10,
                      draft_model=dm, spec_k=4)
        aps = eng.spec_emitted / max(1, eng.spec_steps)
        assert aps > 4.0
        assert eng.pool.blocks_in_use == 0

    def test_chaos_with_spec_zero_lost_zero_leaked(self, dm):
        """A crashing replica mid-speculation: every request completes on
        the survivor, outputs match the clean run, and no LIVE replica
        leaks KV blocks (reserve/rollback unwound; the dead replica's
        pool is abandoned with it by design — see engine.drain)."""
        rs = np.random.RandomState(5)
        prompts = [rs.randint(0, 64, (5,)) for _ in range(6)]
        draft = dm.truncated(1)
        _, ref = _run(dm, prompts, max_new=8, draft_model=draft, spec_k=4)
        expect = {r.request_id: r.generated for r in ref}

        state = {"armed": True}

        def crash_hook(eng):
            if eng.running and state["armed"]:
                state["armed"] = False
                raise RuntimeError("injected replica crash")

        rset = ReplicaSet(dm, n_replicas=2, n_blocks=32, block_tokens=8,
                          max_batch=2, pre_step_hooks={0: crash_hook},
                          draft_model=draft, spec_k=4)
        with rset:
            ids = []
            for i, p in enumerate(prompts):
                r = ServeRequest(prompt_ids=p, max_new_tokens=8,
                                 request_id=f"r{i}")
                assert rset.submit(r)
                ids.append(r.request_id)
            res = rset.wait(ids, timeout=60)
        assert len(res) == 6
        assert [e["reason"] for e in rset.evictions] == ["error"]
        for rid, r in res.items():
            assert r.generated == expect[rid]
        live = [e for e in rset.engines if e.alive]
        assert live
        for eng in live:
            assert eng.pool.blocks_in_use == 0, eng.pool.stats()

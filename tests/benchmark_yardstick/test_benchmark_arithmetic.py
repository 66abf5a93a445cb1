"""The yardstick's arithmetic and its traffic generator."""
import collections
import json
import os
import statistics

import numpy as np
import pytest

from benchmark import flops, manifest as mf, stats, traffic_gen
from benchmark.kinds import train


def _cfg(name):
    with open(os.path.join(mf.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def test_chunk_median_ignores_a_few_slow_chunks_and_stall_share_shows_them():
    even = [0.5] * 11
    slow = [0.5] * 9 + [0.9, 1.1]
    assert stats.chunk_rate(even, 4096) == pytest.approx(8192.0)
    assert stats.chunk_rate(slow, 4096) == pytest.approx(8192.0)
    assert stats.stall_share(even) == pytest.approx(0.0)
    # 6.5 s of wall for 11 chunks the median puts at 5.5 s
    assert stats.stall_share(slow) == pytest.approx(100 * (1 - 5.5 / 6.5))
    # faster outliers give a negative share, as PR 22's -0.0008 was
    assert stats.stall_share([0.5] * 9 + [0.4, 0.4]) < 0


def test_the_end_to_end_rate_is_all_the_work_over_all_the_wall():
    assert stats.rate(12 * 1024 * 80, 10.0) == pytest.approx(98304.0)
    # 80 steps, one of which stalled for a second: the median chunk does
    # not see it, the rate does
    assert stats.rate(12 * 1024 * 80, 11.0) < 0.92 * 98304.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_a_segment_counts_every_step_and_a_stall_is_in_its_wall():
    import time

    calls = []

    def step_fn():
        time.sleep(0.05 if len(calls) == 7 else 0.001)   # one stall
        calls.append(time.monotonic())
        return len(calls)

    waited = []
    seg = train._segment(step_fn, waited.append, k=2, n_chunks=6)
    assert seg["steps"] == len(calls) == 12
    assert waited == seg["losses"] == [2, 4, 6, 8, 10, 12]
    wall = train.segment_wall(seg)
    # from the segment's start to its last completion: the stall is inside
    assert wall >= 0.05 + 11 * 0.001
    assert seg["t_start"] <= calls[0] and seg["done_t"][-1] >= calls[-1]
    # stopping by the clock: no new chunk once `until` has passed
    seg = train._segment(step_fn, waited.append, k=2,
                         until=time.monotonic() - 1)
    assert seg["steps"] == 2


def test_chunk_seconds_are_intervals_between_completions():
    seg = {"t_start": 10.0, "done_t": [11.5, 12.5, 13.5, 14.6, 15.1]}
    # the first two (ramp-up) and the last (the drained tail) are left out
    assert train.chunk_seconds(seg, 2) == pytest.approx([1.0, 1.1])
    assert train.chunk_seconds(seg, 0)[0] == pytest.approx(1.5)
    assert len(train.chunk_seconds(seg, 0)) == 4


@pytest.mark.parametrize("q,want", [(50, 50.5), (90, 90.1), (0, 1), (100, 100)])
def test_percentile_interpolates_like_numpy(q, want):
    v = list(range(1, 101))
    assert stats.percentile(v, q) == pytest.approx(want)
    assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_spread_is_the_drivers():
    v = [100.0, 101.0, 99.0, 100.5, 99.5, 100.2]
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / statistics.median(v))


@pytest.mark.parametrize("name,seq,mflop", [("gpt-125m", 1024, 803),
                                            ("gpt-125m-ctx2048", 2048, 864)])
def test_gpt_125m_parameters_and_flops_per_token(name, seq, mflop):
    cfg = _cfg(name)
    assert cfg["max_position_embeddings"] == seq
    n = flops.gpt_param_count(cfg)
    # 124.4M: 38.6M token + 0.8M position embeddings (1.6M at 2048
    # positions), 12 x 7.09M blocks
    assert n == 50304 * 768 + seq * 768 + 12 * 7087872 + 2 * 768
    assert n == pytest.approx(124.4e6, rel=8e-3)
    # 6N + 6 L s h: 803 MFLOP per token at s = 1024 (the issue's figure);
    # at s = 2048 the attention term doubles, 57 to 113 MFLOP
    assert flops.gpt_train_flops_per_token(cfg, seq) == pytest.approx(
        mflop * 1e6, rel=2e-3)


def test_param_count_counts_the_programs_arrays():
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    cfg = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
           "intermediate_size": 256, "max_position_embeddings": 96}
    model = GPTForCausalLM(GPTConfig(
        vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
        max_position_embeddings=96), seed=0)
    assert flops.gpt_param_count(cfg) == sum(
        int(np.prod(p.shape)) for p in model.parameters())


def test_flash_cost():
    c = flops.flash_causal_train_cost(batch=16, seq=1024, heads=12,
                                      head_dim=64, layers=12)
    # per head forward: QK^T + PV = 2 * 2 s^2 d, half of it causal; x3 with
    # the backward; 12 tensors of b s n d bf16 elements moved once
    assert c["flops"] == pytest.approx(
        12 * 16 * 12 * (2 * 2 * 1024 * 1024 * 64 / 2) * 3)
    assert c["bytes"] == 12 * 12 * (16 * 1024 * 12 * 64 * 2)
    # the attention term of the per-token formula is the same count
    assert c["flops"] / (16 * 1024) == pytest.approx(6 * 12 * 1024 * 768)
    least, bound = flops.roofline_seconds(c, flops.peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(
        c["flops"] / 197e12)


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    for kind in ("cpu", "TPU v9", "_source"):
        with pytest.raises(KeyError):
            flops.peaks(kind)


# ------------------------------------------------------------------ traffic
def test_zipf_batches_are_seeded_and_labels_are_the_next_token():
    a = traffic_gen.ZipfTokens(2 ** 31 + 5, 50304, 1.1)
    b = traffic_gen.ZipfTokens(2 ** 31 + 5, 50304, 1.1)
    x, y = a.batch(3, 4, 128)
    x2, y2 = b.batch(3, 4, 128)
    assert x.shape == y.shape == (4, 128) and x.dtype == np.int32
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    assert np.array_equal(x[:, 1:], y[:, :-1])
    assert 0 <= x.min() and x.max() < 50304
    assert not np.array_equal(x, a.batch(4, 4, 128)[0])     # fresh per step
    assert not np.array_equal(
        x, traffic_gen.ZipfTokens(6, 50304, 1.1).batch(3, 4, 128)[0])
    # something to learn: the commonest token is far above uniform
    big = a.batch(0, 64, 1024)[0].ravel()
    top = collections.Counter(big.tolist()).most_common(1)[0][1]
    assert top / big.size > 100 / 50304

"""The reduction from a trace to numbers: on hand-made events with known
answers, and on a small trace recorded on the chip."""
import os

import pytest

from benchmark import manifest as mf, trace_reduce as tr

MS = 1e6   # ns


# names as the TPU profiler writes them: the instruction's whole HLO text
FLASH = ("%jvp__.19 = (bf16[16,12,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}, "
         "f32[16,12,1024,1]{3,2,1,0:T(8,128)}) custom-call(bf16[16,12,1024,"
         "64]{3,2,1,0:T(8,128)(2,1)S(1)} %bitcast.4), "
         "custom_call_target=\"tpu_custom_call\"")
FUSION_OF_A_COLLECTIVE = (
    "%fusion.2 = bf16[16,1024]{1,0:T(8,128)(2,1)} fusion(bf16[16,1024]"
    "{1,0:T(8,128)(2,1)} %all-reduce.7, f32[8]{0} %custom-call.3), "
    "kind=kLoop, calls=%fused_computation.2")


def _trace():
    """Two chips, window 0..100 ms.
    chip 0: fusion 0-40, all-reduce 30-60 (30-40 under the fusion, so
            20 ms exposed), flash custom call 60-70, idle 70-100
            except a fusion 90-95.
    chip 1: fusion 0-50, all-gather 50-60 (all exposed), idle after."""
    return {
        "devices": {
            0: {"ops": [("fusion.1", 0, 40 * MS),
                        ("all-reduce.7", 30 * MS, 30 * MS), (FLASH, 60 * MS,
                                                             10 * MS),
                        (FUSION_OF_A_COLLECTIVE, 90 * MS, 5 * MS)],
                "async": []},
            # an asynchronous pair: start and done are short ops, the
            # transfer is the pair's span on the async line
            1: {"ops": [("fusion.1", 0, 50 * MS),
                        ("all-gather-start.2", 50 * MS, 1 * MS),
                        ("all-gather-done.2", 59 * MS, 1 * MS)],
                "async": [("all-gather-start.2", 50 * MS, 10 * MS)]},
        },
        "host": [("bench.window", 0, 100 * MS),
                 ("bench.wait_chunk", 65 * MS, 30 * MS),
                 ("bench.enqueue_chunk", 96 * MS, 4 * MS)],
        "lines": {},
    }


def test_interval_arithmetic():
    assert tr.merge([(5, 7), (0, 3), (2, 4), (7, 8), (9, 9)]) == \
        [[0, 4], [5, 8]]
    assert tr.total(tr.merge([(0, 3), (2, 4), (5, 7)])) == 6
    assert tr.intersect([[0, 4], [5, 8]], [[3, 6], [7, 10]]) == \
        [[3, 4], [5, 6], [7, 8]]
    assert tr.clip([[0, 4], [5, 8]], 2, 6) == [[2, 4], [5, 6]]
    assert tr.op_family("%fusion.123") == "fusion"
    assert tr.op_family("all-reduce-start.7") == "all-reduce-start"


def test_an_op_is_classified_by_its_opcode_not_by_its_operands():
    op = tr.parse_op(FLASH)
    assert op["name"] == "jvp__.19" and op["opcode"] == "custom-call"
    assert op["type"].startswith("(bf16[16,12,1024,64]")
    assert tr.is_mosaic(op, FLASH) and not tr.is_collective(op)
    op = tr.parse_op(FUSION_OF_A_COLLECTIVE)
    assert op["opcode"] == "fusion"
    assert not tr.is_collective(op)
    assert not tr.is_mosaic(op, FUSION_OF_A_COLLECTIVE)
    for text in ("%all-reduce-start.3 = f32[8]{0} all-reduce-start(f32[8]{0} "
                 "%p), replica_groups={}", "all-gather-done.2",
                 "%rs = bf16[4]{0} reduce-scatter(bf16[8]{0} %x)"):
        assert tr.is_collective(tr.parse_op(text)), text
    assert tr.parse_op("%w = (s32[]{:T(128)}) while((s32[]) %t), body=%b")[
        "opcode"] == "while"


def test_busy_union_collectives_exposed_and_mosaic():
    r = tr.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.100)
    assert r["chips"] == 2
    # chip 0 busy 0-70 and 90-95 = 75 ms; chip 1 busy 0-51 and 59-60 =
    # 52 ms: busy is the ops line (the core), not a transfer in flight
    assert r["busy_s"] == pytest.approx((0.075 + 0.052) / 2)
    assert r["collective_s"] == pytest.approx((0.030 + 0.010) / 2)
    assert r["collective_exposed_s"] == pytest.approx((0.020 + 0.010) / 2)
    assert r["mosaic_s"] == pytest.approx(0.010 / 2)
    assert r["mosaic_calls"] == pytest.approx(0.5)
    ops = dict((n, s) for n, s in r["breakdown"]["device_ops"])
    assert ops["fusion x1"] == pytest.approx((0.040 + 0.050) / 2)
    assert any(k.startswith("custom-call:jvp__ (bf16[16,12,1024,64]")
               for k in ops)
    assert len(r["breakdown"]["device_ops"]) <= 10


def test_idle_gaps_are_named_by_the_host_span_over_their_middle():
    r = tr.reduce(_trace())
    gaps = dict(r["breakdown"]["idle_gaps"])
    # chip 0 idles 70-90 (middle 80: in wait_chunk) and 95-100 (middle
    # 97.5: in enqueue_chunk, the shorter of the spans that cover it)
    assert gaps["host_in_bench.wait_chunk"] == pytest.approx(0.020)
    assert gaps["host_in_bench.enqueue_chunk"] == pytest.approx(0.005)
    assert gaps["longest_single_gap"] == pytest.approx(0.020)


def test_events_outside_the_window_do_not_count():
    t = _trace()
    t["host"][0] = ("bench.window", 10 * MS, 60 * MS)    # 10..70 ms
    r = tr.reduce(t)
    assert r["window_s"] == pytest.approx(0.060)
    assert r["busy_s"] == pytest.approx((0.060 + 0.042) / 2)
    assert r["busy_s"] <= r["window_s"]


def test_without_a_window_span_the_extent_of_the_ops_is_the_window():
    t = _trace()
    t["host"] = []
    assert tr.window_of(t) == (0, 95 * MS)
    assert tr.reduce(t)["breakdown"]["idle_gaps"][0][0] == \
        "host_outside_bench_spans"


def test_a_trace_with_no_device_plane_reduces_to_nothing():
    assert tr.reduce({"devices": {}, "host": [], "lines": {}}) == {}
    assert tr.reduce({"devices": {0: {"ops": [], "async": []}}, "host": [],
                      "lines": {}}) == {}


FIXTURE = os.path.join(mf.BENCH_DIR, "fixtures", "tiny_tpu.xplane.pb")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no trace recorded on the chip yet")
def test_recorded_chip_trace():
    """benchmark/fixtures/record_fixture.py on a v5e: three calls of a step
    with two matmuls and one Mosaic flash-attention call each."""
    trace = tr.load(FIXTURE)
    assert list(trace["devices"]) == [0]
    assert len(trace["devices"][0]["ops"]) == 18
    assert len(trace["devices"][0]["async"]) == 3
    names = [n for n, _, _ in trace["host"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.enqueue_chunk") == 3
    r = tr.reduce(trace)
    # the device's clock runs 0.05-0.35 ms ahead of the host's in this
    # trace: the first call's ops are stamped before `bench.window` opens
    assert r["mosaic_calls"] == 2
    assert 0 < r["mosaic_s"] < r["busy_s"] < r["window_s"]
    assert r["collective_s"] == 0
    # the window holds three 2 ms sleeps: the chip idles most of it
    assert r["window_s"] > 0.006 and r["busy_s"] / r["window_s"] < 0.5
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["longest_single_gap"] > 0.001

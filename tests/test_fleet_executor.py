"""Fleet executor actor runtime (distributed/fleet_executor.py).

Reference: paddle/fluid/distributed/fleet_executor/ — Carrier/Interceptor/
MessageBus task-graph orchestration for multi-stage inference.
"""
import threading
import time

import numpy as np
import pytest

from paddle_tpu.distributed.fleet_executor import FleetExecutor, TaskNode


def test_three_stage_pipeline_order_and_results():
    exe = FleetExecutor([
        TaskNode(0, fn=lambda x: x + 1, downstream=[1]),
        TaskNode(1, fn=lambda x: x * 2, downstream=[2]),
        TaskNode(2, fn=lambda x: x - 3),
    ])
    outs = exe.run([1, 2, 3, 4])
    assert sorted(outs) == [(v + 1) * 2 - 3 for v in [1, 2, 3, 4]]
    exe.shutdown()


def test_stages_overlap_in_time():
    """Real concurrency, shown by a rendezvous and not by a clock: stage b
    holds item 0 until stage a has taken up item 1. An executor that ran
    one item through both stages before admitting the next would leave b
    waiting out its timeout."""
    a_has_item_1 = threading.Event()
    overlapped = []

    def a(x):
        if x == 1:
            a_has_item_1.set()
        return x

    def b(x):
        if x == 0:
            overlapped.append(a_has_item_1.wait(30))
        return x

    exe = FleetExecutor([
        TaskNode(0, fn=a, downstream=[1]),
        TaskNode(1, fn=b),
    ])
    outs = exe.run(list(range(8)))
    exe.shutdown()
    assert sorted(outs) == list(range(8))
    assert overlapped == [True]


def test_fanout_graph():
    """One source feeding two sinks (branching task graph)."""
    exe = FleetExecutor([
        TaskNode(0, fn=lambda x: x * 10, downstream=[1, 2]),
        TaskNode(1, fn=lambda x: x + 1),
        TaskNode(2, fn=lambda x: x + 2),
    ])
    outs = exe.run([1, 2], timeout=30)
    assert len(outs) == 2  # run() waits for len(microbatches) results
    assert set(outs) <= {11, 12, 21, 22}
    exe.shutdown()


def test_stage_error_propagates():
    def boom(x):
        raise RuntimeError("stage exploded")

    exe = FleetExecutor([
        TaskNode(0, fn=boom, downstream=[1]),
        TaskNode(1, fn=lambda x: x),
    ])
    with pytest.raises((RuntimeError, Exception)):
        exe.run([1], timeout=5)


def test_with_compiled_predictor_stage():
    """The intended composition: host pre/post stages around a jitted
    program."""
    import jax
    import jax.numpy as jnp

    predict = jax.jit(lambda v: jnp.tanh(v).sum())
    exe = FleetExecutor([
        TaskNode(0, fn=lambda x: np.asarray(x, np.float32) / 10.0,
                 downstream=[1]),
        TaskNode(1, fn=lambda v: float(predict(v))),
    ])
    outs = exe.run([np.ones(4), np.zeros(4)])
    assert sorted(round(o, 4) for o in outs) == sorted(
        [round(float(np.tanh(0.1) * 4), 4), 0.0])
    exe.shutdown()


def test_dist_model_sharded_inference_matches_single_device(tmp_path):
    """DistModel (reference dist_model.cc): artifact load + batch sharded
    over the mesh produces the same logits as plain single-device run."""
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.static as static
    import paddle_tpu.distributed.mesh as mesh_mod
    from paddle_tpu.distributed.fleet_executor import (
        DistModel, DistModelConfig,
    )

    rs = np.random.RandomState(0)
    prefix = str(tmp_path / "distm")
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = static.data("x", shape=[None, 8], dtype="float32")
        h = static.nn.fc(x, 16, activation="relu")
        out = static.nn.fc(h, 4)
    exe = static.Executor()
    exe.run(startup)
    static.save_inference_model(prefix, [x], [out], exe, program=main)

    feed = rs.rand(16, 8).astype("float32")
    (ref,) = exe.run(main, feed={"x": feed}, fetch_list=[out])

    try:
        mesh_mod.set_mesh(mesh_mod.build_mesh({"data": 8}))
        cfg = DistModelConfig(model_prefix=prefix)
        dm = DistModel(cfg)
        assert dm.init()
        (got,) = dm.run([feed])
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5,
                                   atol=1e-6)
        # the fed batch really was sharded over the 8 devices
        assert dm._batch_sharding.mesh.size == 8
    finally:
        mesh_mod._current[0] = None


def test_dist_model_mesh_set_after_init(tmp_path):
    """A mesh installed AFTER init() must be honored at run() (the
    sharding decision follows the current mesh, not a stale snapshot)."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.static as static
    import paddle_tpu.distributed.mesh as mesh_mod
    from paddle_tpu.distributed.fleet_executor import (
        DistModel, DistModelConfig,
    )

    rs = np.random.RandomState(1)
    prefix = str(tmp_path / "dm2")
    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        x = static.data("x", shape=[None, 4], dtype="float32")
        out = static.nn.fc(x, 2)
    exe = static.Executor()
    exe.run(startup)
    static.save_inference_model(prefix, [x], [out], exe, program=main)
    feed = rs.rand(8, 4).astype("float32")
    (ref,) = exe.run(main, feed={"x": feed}, fetch_list=[out])

    dm = DistModel(DistModelConfig(model_prefix=prefix))
    dm.init()  # no mesh yet
    try:
        (got0,) = dm.run([feed])  # meshless run works
        np.testing.assert_allclose(got0, np.asarray(ref), rtol=1e-5)
        mesh_mod.set_mesh(mesh_mod.build_mesh({"data": 8}))
        (got,) = dm.run([feed])  # mesh appeared afterwards: no crash
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-5)
        assert dm._batch_sharding is not None
    finally:
        mesh_mod._current[0] = None


def test_backpressure_bounds_inflight_work():
    """Credit gating must propagate hop-by-hop (reference
    compute_interceptor.cc ready = input AND output-buffer space): a fast
    middle stage may run at most its downstream credit ahead of a slow
    sink, not absorb the whole feed into memory."""
    import threading

    processed_mid = []
    first_sink = threading.Event()
    mid_at_first_sink = []

    def mid(x):
        processed_mid.append(x)
        return x

    def sink(x):
        if not first_sink.is_set():
            time.sleep(0.3)
            mid_at_first_sink.append(len(processed_mid))
            first_sink.set()
        return x

    exe = FleetExecutor([
        TaskNode(0, downstream=[1], max_run_times=1),
        TaskNode(1, fn=mid, downstream=[2], max_run_times=1),
        TaskNode(2, fn=sink, max_run_times=1),
    ])
    outs = exe.run(list(range(8)))
    exe.shutdown()
    assert len(outs) == 8
    assert mid_at_first_sink[0] <= 2, mid_at_first_sink


def test_many_microbatches_fanout_stress():
    """200 micro-batches through a diamond graph (source -> 2 branches ->
    join): credit flow must neither deadlock nor drop/duplicate work."""
    import numpy as np

    joined = []

    exe = FleetExecutor([
        TaskNode(0, fn=lambda x: x, downstream=[1, 2], max_run_times=3),
        TaskNode(1, fn=lambda x: x * 2, downstream=[3], max_run_times=2),
        TaskNode(2, fn=lambda x: x * 3, downstream=[3], max_run_times=1),
        TaskNode(3, fn=lambda x: joined.append(int(x)) or x,
                 max_run_times=2),
    ])
    outs = exe.run(list(range(200)), timeout=60)
    exe.shutdown()
    # join sees each micro-batch TWICE (once per branch)
    assert len(outs) == 200 and len(joined) == 400
    got = sorted(joined)
    want = sorted([i * 2 for i in range(200)] + [i * 3 for i in range(200)])
    assert got == want

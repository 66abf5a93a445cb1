"""Eager per-op dispatch regression guard (SURVEY §7 hard-part 1).

The steady-state eager path is an op-cache HIT; the regression this guard
exists for (a cache-key bug that recompiles per call, an op that stops
being cacheable) shows in the op cache's own counters, whatever the
machine's load: N repeated calls of one op at one shape are 1 miss and
N - 1 hits, and a fresh shape is a miss. What a dispatch costs on the chip
is not measured.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework.autograd import clear_op_cache
from paddle_tpu.observability import get_registry

N = 150


def _counts():
    reg = get_registry()
    return {k: reg.counter(f"trace_cache_{k}_total").value
            for k in ("hits", "misses", "uncacheable")}


def _delta(before):
    return {k: v - before[k] for k, v in _counts().items()}


@pytest.mark.parametrize("grad", [False, True], ids=["no_grad", "grad"])
def test_repeated_calls_are_one_miss_then_hits(grad):
    """N times `t * a + b` at one shape: each of the two ops misses once
    and hits N - 1 times, with the tape recording or not."""
    x = paddle.to_tensor(np.ones((16, 16), "float32"))
    x.stop_gradient = not grad
    clear_op_cache()
    before = _counts()
    t = x
    for _ in range(N):
        t = t * 1.0001 + 0.1
    assert np.isfinite(t.numpy()).all()
    assert _delta(before) == {"hits": 2 * (N - 1), "misses": 2,
                              "uncacheable": 0}


def test_a_fresh_shape_is_a_miss():
    """The other side: the miss path really is one (or the count above
    would say nothing), once per new shape and not again."""
    clear_op_cache()
    before = _counts()
    for i in range(3):
        t = paddle.to_tensor(np.ones((8, 8 + i), "float32"))
        _ = t * 2.0
    assert _delta(before) == {"hits": 0, "misses": 3, "uncacheable": 0}
    _ = paddle.to_tensor(np.ones((8, 8), "float32")) * 2.0
    assert _delta(before) == {"hits": 1, "misses": 3, "uncacheable": 0}

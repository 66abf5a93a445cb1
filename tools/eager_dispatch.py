"""Eager per-op dispatch latency, per op-cache hit/miss (VERDICT r4 #7).

SURVEY §7 hard-part 1: eager op dispatch must stay usable on TPU. This
measures, against the backend JAX finds (the JSON names it):

  hit_us        op-cache HIT dispatch (the steady-state eager path)
  miss_us       op-cache MISS (fresh trace+compile per op: new shapes)
  train_hit_us  grad-enabled loop: dispatch + tape build + cached bwd

Prints one JSON line. tests/test_eager_dispatch.py is the regression
guard over the hit path.
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure(n_hit: int = 400, n_miss: int = 5) -> dict:
    # n_miss stays SMALL: every miss op pays a real compile and the mean
    # stabilizes quickly
    import jax

    import paddle_tpu as paddle

    dev = jax.devices()[0]

    # ---- hit path: repeated same-shape ops ride the op cache ----
    x = paddle.ones([256, 256])

    def chain(t, k):
        for _ in range(k):
            t = t * 1.0001 + 0.1
        return t

    _ = float(chain(x, 20).sum())  # warm
    t0 = time.perf_counter()
    y = chain(x, n_hit)
    _ = float(y.sum())
    hit_us = (time.perf_counter() - t0) / (2 * n_hit) * 1e6

    # ---- miss path: a fresh shape per op defeats the cache, so every
    # dispatch pays trace + compile (the first-touch cost a user sees) ----
    t0 = time.perf_counter()
    for i in range(n_miss):
        t = paddle.ones([8, 8 + i])
        _ = float((t * 2.0 + float(i)).sum())
    miss_us = (time.perf_counter() - t0) / (2 * n_miss) * 1e6

    # ---- grad-enabled hit path (the eager TRAINING shape) ----
    xs = paddle.ones([16, 16])
    w = paddle.ones([16, 16])
    w.stop_gradient = False
    k = 20

    def train_iter():
        t = xs
        for _ in range(k):
            t = (t @ w) * 0.5
        loss = t.sum()
        loss.backward()
        g = w.grad
        w.clear_grad()
        return g

    _ = train_iter()
    iters = max(1, n_hit // (2 * k))
    t0 = time.perf_counter()
    for _ in range(iters):
        g = train_iter()
    _ = float(g.sum().numpy())
    train_hit_us = (time.perf_counter() - t0) / (iters * 2 * k) * 1e6

    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "hit_us": round(hit_us, 2),
        "miss_us": round(miss_us, 2),
        "train_hit_us": round(train_hit_us, 2),
        "miss_over_hit": round(miss_us / hit_us, 1) if hit_us else None,
        "n_hit_ops": 2 * n_hit,
        "n_miss_ops": 2 * n_miss,
        "note": ("miss pays trace+compile (first touch of a shape); hit "
                 "is the steady-state dispatch SURVEY §7 risk #1 tracks; "
                 "100us/op is the usability target on TPU"),
    }


def main():
    print(json.dumps(measure()))


if __name__ == "__main__":
    main()

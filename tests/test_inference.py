"""Inference subsystem: save → fresh-process load → identical outputs.

Reference capability: AnalysisPredictor (inference/api/analysis_predictor.h:
load model → optimize → zero-copy run) and static save/load_inference_model
(python/paddle/static/io.py). The fresh-process test is the deployment
contract: nothing from the training process may be needed to serve.
"""
import os
import subprocess
import sys
import textwrap

import pytest

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.static as static

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build_and_train(tmp):
    """Tiny static-mode MLP trained a few steps; returns feeds/logits/prefix."""
    paddle.enable_static()
    try:
        main = static.Program()
        startup = static.Program()
        with static.program_guard(main, startup):
            x = static.data("x", shape=(4, 8), dtype="float32")
            lbl = static.data("lbl", shape=(4, 1), dtype="int64")
            h = static.nn.fc(x, size=16, activation="relu")
            logits = static.nn.fc(h, size=3)
            loss = paddle.nn.functional.cross_entropy(
                logits, lbl, reduction="mean")
            opt = paddle.optimizer.SGD(learning_rate=0.1)
            opt.minimize(loss)

        exe = static.Executor()
        exe.run(startup)
        rs = np.random.RandomState(0)
        xs = rs.randn(4, 8).astype("float32")
        ys = rs.randint(0, 3, (4, 1)).astype("int64")
        for _ in range(3):
            exe.run(main, feed={"x": xs, "lbl": ys}, fetch_list=[loss])

        infer_prog = main.clone(for_test=True)
        prefix = os.path.join(tmp, "mlp")
        static.save_inference_model(prefix, [x], [logits],
                                    executor=exe, program=infer_prog)
        expect = exe.run(infer_prog, feed={"x": xs, "lbl": ys},
                         fetch_list=[logits])[0]
        return xs, np.asarray(expect), prefix
    finally:
        paddle.disable_static()


def test_save_load_inference_model_same_process(tmp_path):
    xs, expect, prefix = _build_and_train(str(tmp_path))
    paddle.enable_static()
    try:
        exe = static.Executor()
        prog, feed_names, fetch_targets = static.load_inference_model(
            prefix, exe)
        assert feed_names == ["x"]
        out = exe.run(prog, feed={"x": xs}, fetch_list=fetch_targets)[0]
        np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)
    finally:
        paddle.disable_static()


def test_predictor_zero_copy_api(tmp_path):
    xs, expect, prefix = _build_and_train(str(tmp_path))
    from paddle_tpu import inference

    cfg = inference.Config(prefix + ".pdmodel", prefix + ".pdiparams")
    pred = inference.create_predictor(cfg)
    assert pred.get_input_names() == ["x"]
    h = pred.get_input_handle("x")
    h.copy_from_cpu(xs)
    pred.run()
    out = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)
    # convenience run(list) form + clone sharing weights
    out2 = pred.clone().run([xs])[0]
    np.testing.assert_allclose(out2, expect, rtol=1e-5, atol=1e-6)


def test_fresh_process_load_identical_logits(tmp_path):
    """THE deployment contract: train → save → load in a NEW process →
    bit-identical logits."""
    xs, expect, prefix = _build_and_train(str(tmp_path))
    np.save(tmp_path / "xs.npy", xs)
    np.save(tmp_path / "expect.npy", expect)
    script = textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, {REPO!r})
        import numpy as np
        from paddle_tpu import inference
        xs = np.load({str(tmp_path / 'xs.npy')!r})
        expect = np.load({str(tmp_path / 'expect.npy')!r})
        cfg = inference.Config({prefix + '.pdmodel'!r})
        pred = inference.create_predictor(cfg)
        out = pred.run([xs])[0]
        np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)
        print("FRESH_PROCESS_OK")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FRESH_PROCESS_OK" in r.stdout


def test_jit_save_produces_servable_artifact(tmp_path):
    """Dygraph flow: jit.save(layer, input_spec=...) → create_predictor."""
    import paddle_tpu.nn as nn

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(8, 4)

        def forward(self, x):
            return paddle.nn.functional.relu(self.fc(x))

    net = Net()
    net.eval()
    xs = np.random.RandomState(1).randn(2, 8).astype("float32")
    expect = net(paddle.to_tensor(xs)).numpy()

    prefix = str(tmp_path / "net")
    paddle.jit.save(net, prefix,
                    input_spec=[static.InputSpec([2, 8], "float32", "x")])
    from paddle_tpu import inference

    pred = inference.create_predictor(inference.Config(prefix + ".pdmodel"))
    out = pred.run([xs])[0]
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


def test_export_multi_feed_shared_batch_dim(tmp_path):
    """Two dynamic-batch feeds combined in one op must export: all leading
    -1 dims share ONE symbolic 'batch' (independent symbols would make
    ids + mask style models inconclusive at trace time)."""
    from paddle_tpu.inference.io import (
        InferenceArtifact, export_inference_artifact,
    )

    w = np.random.RandomState(0).randn(8, 4).astype("float32")

    def fn(ws, fs):
        x, mask = fs
        return [(x * mask) @ ws[0]]

    prefix = str(tmp_path / "mf")
    export_inference_artifact(
        fn, [w],
        [("x", [-1, 8], "float32"), ("mask", [-1, 8], "float32")],
        prefix)
    art = InferenceArtifact.load(prefix)
    for b in (2, 5):
        rs = np.random.RandomState(b)
        x = rs.randn(b, 8).astype("float32")
        m = (rs.rand(b, 8) > 0.5).astype("float32")
        (out,) = art.run([x, m])
        np.testing.assert_allclose(np.asarray(out), (x * m) @ w,
                                   rtol=1e-5, atol=1e-6)


class _JitArtifact:
    """Minimal real-jit artifact for Predictor-surface tests that must
    not depend on the StableHLO export path (jax.export is absent in
    some CI environments; the full save->load contract is covered by the
    tests above when it exists). The compute is a genuinely compiled XLA
    executable, so clone-concurrency exercises the real thread path."""

    def __init__(self, w):
        import jax
        import jax.numpy as jnp

        self.feed_names = ["x"]
        self.feed_specs = {"x": ([2, 8], "float32")}
        self.n_fetches = 1
        self._w = jnp.asarray(w)
        self._fn = jax.jit(lambda wv, x: [jnp.maximum(x @ wv, 0.0)])

    def run(self, feed_vals):
        return self._fn(self._w, feed_vals[0])


def _stub_predictor(monkeypatch, w):
    from paddle_tpu import inference

    art = _JitArtifact(w)
    monkeypatch.setattr(inference, "_load_artifact",
                        lambda *a, **k: art)
    return inference.create_predictor(inference.Config("stub.pdmodel"))


def test_run_inputs_does_not_leak_into_handle_runs(monkeypatch):
    """ISSUE 14 satellite bugfix: values staged by run(inputs=...) are
    transient to that call. A later handle-style run() that forgot to
    re-stage must raise, not silently reuse the convenience call's
    arrays (the old behavior served stale inputs)."""
    import pytest

    rs = np.random.RandomState(0)
    w = rs.randn(8, 4).astype("float32")
    xs = rs.randn(2, 8).astype("float32")
    expect = np.maximum(xs @ w, 0.0)
    pred = _stub_predictor(monkeypatch, w)
    out = pred.run([xs])[0]
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)
    # the bug: this used to reuse xs from the run(inputs=...) above
    with pytest.raises(RuntimeError, match="was not set"):
        pred.run()
    # handle staging still works per call, and a convenience run in
    # between clears it again
    h = pred.get_input_handle("x")
    h.copy_from_cpu(xs)
    pred.run()
    out2 = pred.get_output_handle(pred.get_output_names()[0]).copy_to_cpu()
    np.testing.assert_allclose(out2, expect, rtol=1e-5, atol=1e-6)
    pred.run([xs])
    with pytest.raises(RuntimeError, match="was not set"):
        pred.run()


def test_clone_concurrent_runs_share_artifact_without_interference(
        monkeypatch):
    """ISSUE 14 satellite: the serving replica pool depends on
    Predictor.clone() zero-copy weight sharing being safe under
    concurrent run() from separate threads — each clone has its own
    handles, so simultaneous runs must not cross inputs/outputs."""
    import threading

    rs = np.random.RandomState(7)
    w = rs.randn(8, 4).astype("float32")
    xs = rs.randn(2, 8).astype("float32")
    base = _stub_predictor(monkeypatch, w)
    clones = [base.clone() for _ in range(2)]
    assert all(c._artifact is base._artifact for c in clones)
    feeds = [xs, rs.randn(*xs.shape).astype("float32")]
    expects = [np.asarray(base.run([f])[0]) for f in feeds]
    n_iters, errors, outs = 30, [], [[], []]
    barrier = threading.Barrier(2)

    def worker(i):
        try:
            barrier.wait(timeout=30)
            for _ in range(n_iters):
                outs[i].append(np.asarray(clones[i].run([feeds[i]])[0]))
        except Exception as e:  # surfaced below; a thread must not die silently
            errors.append((i, repr(e)))

    ts = [threading.Thread(target=worker, args=(i,), daemon=True)
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errors, errors
    for i in range(2):
        assert len(outs[i]) == n_iters
        for o in outs[i]:
            np.testing.assert_allclose(o, expects[i], rtol=1e-5, atol=1e-6)

"""Tests for paddle.autograd (PyLayer), device, incubate auto-checkpoint,
onnx (StableHLO) export, utils, version/sysconfig/callbacks namespaces."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn


class TestPyLayer:
    def test_custom_exp(self):
        from paddle_tpu.autograd import PyLayer

        class Exp(PyLayer):
            @staticmethod
            def forward(ctx, x):
                y = paddle.exp(x)
                ctx.save_for_backward(y)
                return y

            @staticmethod
            def backward(ctx, dy):
                (y,) = ctx.saved_tensor
                return dy * y

        x = paddle.to_tensor(np.array([0.0, 1.0, -1.0], "float32"))
        x.stop_gradient = False
        y = Exp.apply(x)
        np.testing.assert_allclose(y.numpy(), np.exp(x.numpy()), rtol=1e-6)
        y.sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), np.exp(x.numpy()),
                                   rtol=1e-6)

    def test_multi_output(self):
        from paddle_tpu.autograd import PyLayer

        class SplitSq(PyLayer):
            @staticmethod
            def forward(ctx, x):
                ctx.save_for_backward(x)
                return x * x, x * 3.0

            @staticmethod
            def backward(ctx, d1, d2):
                (x,) = ctx.saved_tensor
                return d1 * 2.0 * x + d2 * 3.0

        x = paddle.to_tensor(np.array([2.0], "float32"))
        x.stop_gradient = False
        a, b = SplitSq.apply(x)
        (a + b).sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), [2 * 2.0 + 3.0],
                                   rtol=1e-6)

    def test_backward_api(self):
        import paddle_tpu.autograd as ag

        x = paddle.to_tensor(np.array([1.0, 2.0], "float32"))
        x.stop_gradient = False
        y = (x ** 2).sum()
        ag.backward([y])
        np.testing.assert_allclose(x.grad.numpy(), [2.0, 4.0])


class TestDeviceNamespace:
    def test_queries(self):
        import paddle_tpu.device as device

        assert isinstance(device.get_device(), str)
        assert device.device_count() >= 1
        assert not device.cuda.is_available()
        assert device.cuda.device_count() == 0
        device.synchronize()
        types = device.get_all_device_type()
        assert "cpu" in types


class TestAutoCheckpoint:
    def test_epoch_range_resume(self, tmp_path):
        from paddle_tpu.incubate.checkpoint import TrainEpochRange

        net = nn.Linear(2, 2)
        done = []
        r = TrainEpochRange(5, save_dir=str(tmp_path), job_id="job1",
                            state={"model": net})
        for epoch in r:
            done.append(epoch)
            net.weight.set_value(np.full((2, 2), float(epoch), "float32"))
            if epoch == 2:
                break  # simulate preemption after epoch-2 checkpointing? no:
                # break before _save_state of epoch 2 happens (generator)
        assert done == [0, 1, 2]
        # epochs 0,1 were checkpointed (save happens after each completed
        # yield-resume cycle); restart resumes from epoch 2
        net2 = nn.Linear(2, 2)
        r2 = TrainEpochRange(5, save_dir=str(tmp_path), job_id="job1",
                             state={"model": net2})
        resumed = list(r2)
        assert resumed[0] == 2
        assert resumed[-1] == 4
        np.testing.assert_allclose(net2.weight.numpy(),
                                   np.full((2, 2), 1.0))  # epoch-1 state

    def test_checker_env(self, monkeypatch):
        from paddle_tpu.incubate.checkpoint import AutoCheckpointChecker

        monkeypatch.setenv("PADDLE_JOB_ID", "xyz")
        c = AutoCheckpointChecker()
        assert c.job_id == "xyz"
        assert c.get_job_checkpoint_path("/base") == "/base/xyz"


class TestOnnxExport:
    def test_stablehlo_export_roundtrip(self, tmp_path):
        import jax

        import paddle_tpu.onnx as onnx
        from paddle_tpu.static import InputSpec

        net = nn.Linear(4, 2)
        net.eval()
        path = onnx.export(net, str(tmp_path / "model"),
                           input_spec=[InputSpec([1, 4], "float32", "x")])
        assert os.path.exists(path)
        blob = open(path, "rb").read()
        rehydrated = jax.export.deserialize(blob)
        x = np.ones((1, 4), "float32")
        out = rehydrated.call(x)
        expect = net(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5)

    def test_onnx_format_rejected(self, tmp_path):
        import paddle_tpu.onnx as onnx

        with pytest.raises(NotImplementedError):
            onnx.export(nn.Linear(2, 2), str(tmp_path / "m"), format="onnx")


class TestUtils:
    def test_deprecated_warns(self):
        import warnings

        from paddle_tpu.utils import deprecated

        @deprecated(update_to="paddle.new_api", since="2.0")
        def old_api():
            return 42

        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert old_api() == 42
        assert any("deprecated" in str(x.message) for x in w)

    def test_unique_name(self):
        from paddle_tpu.utils import unique_name

        with unique_name.guard():
            a = unique_name.generate("fc")
            b = unique_name.generate("fc")
        assert a == "fc_0" and b == "fc_1"

    def test_run_check(self, capsys):
        from paddle_tpu.utils import run_check

        run_check()
        assert "successfully" in capsys.readouterr().out

    def test_dlpack_roundtrip(self):
        from paddle_tpu.utils import dlpack

        x = paddle.to_tensor(np.arange(6, dtype="float32").reshape(2, 3))
        cap = dlpack.to_dlpack(x)
        y = dlpack.from_dlpack(cap)
        np.testing.assert_allclose(y.numpy(), x.numpy())


def test_misc_namespaces():
    import paddle_tpu.callbacks as cb
    import paddle_tpu.sysconfig as sysconfig
    import paddle_tpu.version as version

    assert hasattr(cb, "ModelCheckpoint")
    assert version.full_version
    assert os.path.isdir(sysconfig.get_include())


def test_structured_errors_taxonomy():
    from paddle_tpu.framework import errors

    with pytest.raises(errors.InvalidArgumentError):
        errors.enforce(False, "bad arg")
    # typed errors remain catchable as their natural python bases
    with pytest.raises(ValueError):
        errors.enforce(1 == 2, "still a ValueError")
    with pytest.raises(errors.UnimplementedError):
        errors.enforce(False, "todo", errors.UnimplementedError)
    assert issubclass(errors.NotFoundError, KeyError)
    assert issubclass(errors.ResourceExhaustedError, MemoryError)


def test_check_nan_inf_per_op_flag():
    import jax

    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_check_nan_inf": True})
    jax.config.update("jax_debug_nans", False)  # isolate the eager check
    try:
        with pytest.raises(FloatingPointError, match="log"):
            paddle.log(paddle.to_tensor(np.array([-1.0], np.float32)))
    finally:
        paddle.set_flags({"FLAGS_check_nan_inf": False})


def test_benchmark_flag_syncs():
    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_benchmark": True})
    try:
        out = paddle.exp(paddle.to_tensor(np.ones(4, np.float32)))
        np.testing.assert_allclose(out.numpy(), np.e, rtol=1e-6)
    finally:
        paddle.set_flags({"FLAGS_benchmark": False})

"""Train → save_inference_model → serve with the zero-copy Predictor.

    python examples/serve_predictor.py
"""
import os

import tempfile

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.static as static


def main():
    paddle.enable_static()
    main_prog, startup = static.Program(), static.Program()
    with static.program_guard(main_prog, startup):
        x = static.data("x", (None, 16), "float32")
        y = static.data("y", (None, 1), "float32")
        h = static.nn.fc(x, size=32, activation="relu")
        pred = static.nn.fc(h, size=1)
        loss = ((pred - y) ** 2).mean()
        paddle.optimizer.Adam(learning_rate=0.01).minimize(loss)

    exe = static.Executor()
    exe.run(startup)
    rs = np.random.RandomState(0)
    w_true = rs.randn(16, 1).astype("float32")
    for i in range(100):
        xb = rs.randn(32, 16).astype("float32")
        (lv,) = exe.run(main_prog, feed={"x": xb, "y": xb @ w_true},
                        fetch_list=[loss])
    print(f"final train loss: {float(lv):.5f}")

    prefix = os.path.join(tempfile.mkdtemp(), "model")
    static.save_inference_model(prefix, [x], [pred], exe,
                                program=main_prog.clone(for_test=True))
    paddle.disable_static()

    from paddle_tpu import inference

    predictor = inference.create_predictor(inference.Config(prefix + ".pdmodel"))
    xb = rs.randn(4, 16).astype("float32")
    out = predictor.run([xb])[0]
    print("served predictions:", out.ravel())
    print("expected:          ", (xb @ w_true).ravel())

    # SaveOptimModel (analysis_predictor.h:265): persist the post-analysis
    # model as the native StableHLO triple — later loads skip the import,
    # the pass stack, and tracing
    optim_prefix = os.path.join(tempfile.mkdtemp(), "optimized")
    predictor.save_optimized_model(optim_prefix)
    fast = inference.create_predictor(inference.Config(optim_prefix))
    out2 = fast.run([xb])[0]
    assert np.allclose(out2, out, rtol=1e-6, atol=1e-7)
    print("optimized-artifact serve matches")


if __name__ == "__main__":
    main()

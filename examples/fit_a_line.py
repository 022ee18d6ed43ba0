"""Linear regression — the reference's first book example
(reference: python/paddle/fluid/tests/book/test_fit_a_line.py), on
synthetic housing-shaped data: train with the default-program API, save an
inference model, reload it and predict.

Run: python examples/fit_a_line.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_programs(main_prog=None, startup_prog=None):
    """Pure graph construction (no training, no execution): linear model,
    loss, and SGD step. Returns (main, startup, feed_names,
    fetch_vars=[avg_cost, y_predict]) — also the entry point
    tools/lint_program.py-style program linting uses in CI."""
    import paddle_tpu as fluid

    main_prog = main_prog if main_prog is not None else fluid.Program()
    startup_prog = startup_prog if startup_prog is not None else fluid.Program()
    with fluid.program_guard(main_prog, startup_prog):
        x = fluid.data("x", shape=[-1, 13], dtype="float32")
        y = fluid.data("y", shape=[-1, 1], dtype="float32")
        y_predict = fluid.layers.fc(x, size=1, act=None)
        avg_cost = fluid.layers.mean(
            fluid.layers.square_error_cost(y_predict, y)
        )
        fluid.optimizer.SGD(learning_rate=0.01).minimize(avg_cost)
    return main_prog, startup_prog, ["x", "y"], [avg_cost, y_predict]


def main():
    import jax

    print(f"backend: {jax.devices()[0].platform}")

    import paddle_tpu as fluid

    _, _, _, (avg_cost, y_predict) = build_programs(
        fluid.default_main_program(), fluid.default_startup_program()
    )

    rng = np.random.RandomState(0)
    w_true = rng.randn(13, 1).astype("float32")
    xs = rng.randn(256, 13).astype("float32")
    ys = xs @ w_true + 0.1 * rng.randn(256, 1).astype("float32")

    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    for epoch in range(50):
        for i in range(0, 256, 32):
            feed = {"x": xs[i:i + 32], "y": ys[i:i + 32]}
            (loss,) = exe.run(feed=feed, fetch_list=[avg_cost])
        if epoch % 10 == 0:
            print(f"epoch {epoch}: loss {float(loss[0]):.4f}")
    assert float(loss[0]) < 0.1, "did not converge"

    # save -> reload -> infer (the book flow)
    save_dir = tempfile.mkdtemp()
    fluid.io.save_inference_model(save_dir, ["x"], [y_predict], exe)
    infer_prog, feed_names, fetch_names = fluid.io.load_inference_model(
        save_dir, exe
    )
    probe = rng.randn(4, 13).astype("float32")
    (pred,) = exe.run(infer_prog, feed={feed_names[0]: probe},
                      fetch_list=fetch_names)
    np.testing.assert_allclose(pred, probe @ w_true, atol=0.5)
    print("inference model round-trip OK; predictions track ground truth")


if __name__ == "__main__":
    main()

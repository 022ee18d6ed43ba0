"""MNIST conv net — the reference's recognize_digits book example
(reference: python/paddle/fluid/tests/book/test_recognize_digits.py), on
synthetic digits: conv-pool-conv-pool-fc, Adam, accuracy metric, then the
AnalysisPredictor serving path.

Run: python examples/recognize_digits.py
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def synthetic_digits(rng, n):
    """Blob-per-class images: learnable without a dataset download."""
    labels = rng.randint(0, 10, n).astype("int64")
    imgs = rng.randn(n, 1, 28, 28).astype("float32") * 0.1
    for i, c in enumerate(labels):
        r, col = divmod(int(c), 4)
        imgs[i, 0, 4 + r * 7:10 + r * 7, 2 + col * 6:8 + col * 6] += 1.5
    return imgs, labels.reshape(-1, 1)


def build_programs(main_prog=None, startup_prog=None):
    """Pure graph construction (no training, no execution): the conv net,
    loss/metric, and Adam step. Returns (main, startup, feed_names,
    fetch_vars=[loss, acc, prediction]) — also the entry point
    tools/lint_program.py-style program linting uses in CI."""
    import paddle_tpu as fluid

    main_prog = main_prog if main_prog is not None else fluid.Program()
    startup_prog = startup_prog if startup_prog is not None else fluid.Program()
    with fluid.program_guard(main_prog, startup_prog):
        img = fluid.data("img", shape=[-1, 1, 28, 28], dtype="float32")
        label = fluid.data("label", shape=[-1, 1], dtype="int64")
        c1 = fluid.layers.conv2d(img, num_filters=8, filter_size=5, act="relu")
        p1 = fluid.layers.pool2d(c1, pool_size=2, pool_stride=2)
        c2 = fluid.layers.conv2d(p1, num_filters=16, filter_size=5, act="relu")
        p2 = fluid.layers.pool2d(c2, pool_size=2, pool_stride=2)
        flat = fluid.layers.reshape(p2, [0, 16 * 4 * 4])
        prediction = fluid.layers.fc(flat, size=10, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(prediction, label)
        )
        acc = fluid.layers.accuracy(prediction, label)
        fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    return main_prog, startup_prog, ["img", "label"], [loss, acc, prediction]


def main():
    import jax

    print(f"backend: {jax.devices()[0].platform}")

    import paddle_tpu as fluid

    _, _, _, (loss, acc, prediction) = build_programs(
        fluid.default_main_program(), fluid.default_startup_program()
    )

    rng = np.random.RandomState(0)
    xs, ys = synthetic_digits(rng, 512)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    for epoch in range(6):
        accs = []
        for i in range(0, 512, 64):
            feed = {"img": xs[i:i + 64], "label": ys[i:i + 64]}
            l, a = exe.run(feed=feed, fetch_list=[loss, acc])
            accs.append(float(a[0]))
        print(f"epoch {epoch}: acc {np.mean(accs):.3f}")
    assert np.mean(accs) > 0.9, "did not learn the digit blobs"

    # serve through the AnalysisPredictor (conv+bn/fc fusion passes apply)
    from paddle_tpu import inference as paddle_infer

    save_dir = tempfile.mkdtemp()
    fluid.io.save_inference_model(save_dir, ["img"], [prediction], exe)
    config = paddle_infer.Config(save_dir)
    predictor = paddle_infer.create_predictor(config)
    h = predictor.get_input_handle(predictor.get_input_names()[0])
    h.copy_from_cpu(xs[:16])
    predictor.run()
    out = predictor.get_output_handle(
        predictor.get_output_names()[0]
    ).copy_to_cpu()
    served_acc = float((out.argmax(1) == ys[:16, 0]).mean())
    print(f"predictor serving acc on 16 samples: {served_acc:.2f}")
    assert served_acc > 0.8


if __name__ == "__main__":
    main()

"""Python twin of predict.r (reference: r/example/mobilenet.py) — the
executable contract the R script translates through reticulate.

Usage: python predict.py <saved_model_dir> [input.npy]
Builds + saves a tiny conv classifier when the dir is empty, then loads it
through the AnalysisPredictor and prints the output shape/checksum.
"""

import os
import sys

import numpy as np


def ensure_model(model_dir):
    if os.path.exists(os.path.join(model_dir, "__model__")):
        return
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.data("img", shape=[-1, 3, 32, 32], dtype="float32")
        c = fluid.layers.conv2d(img, num_filters=8, filter_size=3, act="relu")
        p = fluid.layers.pool2d(c, pool_size=2, pool_stride=2)
        flat = fluid.layers.reshape(p, [0, 8 * 15 * 15])
        out = fluid.layers.fc(flat, size=10, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(
            model_dir, ["img"], [out], exe, main_program=main
        )


def main():
    model_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/r_demo_model"
    ensure_model(model_dir)

    from paddle_tpu import inference as paddle_infer

    config = paddle_infer.Config(model_dir)
    config.disable_gpu()
    predictor = paddle_infer.create_predictor(config)

    if len(sys.argv) > 2:
        data = np.load(sys.argv[2]).astype("float32")
    else:
        data = np.random.RandomState(0).randn(1, 3, 32, 32).astype("float32")
    inp = predictor.get_input_handle(predictor.get_input_names()[0])
    inp.copy_from_cpu(data)
    predictor.run()
    out = predictor.get_output_handle(
        predictor.get_output_names()[0]
    ).copy_to_cpu()
    print("output shape:", out.shape, "sum:", float(out.sum()))
    return out


if __name__ == "__main__":
    main()

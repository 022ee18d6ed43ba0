"""C ABI + Go binding tests: compile the C smoke host against libcapi.so,
run it out-of-process (the embedded interpreter boots fresh), and compare
its output against the in-process predictor. reference test pattern:
paddle/fluid/inference/capi/ tests + go/demo."""

import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.ir import Program, program_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _save_model(tmpdir, rng):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", [-1, 6])
        h = fluid.layers.fc(x, 8, act="relu")
        pred = fluid.layers.fc(h, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        model_dir = os.path.join(str(tmpdir), "model")
        fluid.io.save_inference_model(model_dir, ["x"], [pred], exe,
                                      main_program=main)
    return model_dir, pred.name


@pytest.fixture(scope="module")
def capi_lib():
    from paddle_tpu.inference.capi import build_capi

    try:
        return build_capi()
    except Exception as e:  # no toolchain/libpython — skip, don't fail
        pytest.skip(f"cannot build libcapi: {e}")


def test_capi_smoke_from_c_host(tmp_path, rng, capi_lib):
    model_dir, _ = _save_model(tmp_path, rng)
    capi_dir = os.path.dirname(capi_lib)
    exe_path = os.path.join(str(tmp_path), "capi_smoke")
    build = subprocess.run(
        ["g++", os.path.join(REPO, "tests", "capi_smoke.c"),
         f"-I{capi_dir}", f"-L{capi_dir}", "-lcapi",
         f"-Wl,-rpath,{capi_dir}", "-o", exe_path],
        capture_output=True, text=True, timeout=120,
    )
    assert build.returncode == 0, build.stderr

    batch, feat = 3, 6
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [exe_path, model_dir, str(batch), str(feat)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    lines = dict(
        l.split("=", 1) if "=" in l else (l.split(":")[0], l.split(":", 1)[1])
        for l in proc.stdout.strip().splitlines()
    )
    assert lines["inputs"].split()[0] == "1"
    assert lines["clone_match"] == "1"
    got = np.array([float(v) for v in lines["values"].split()], "float32")

    # in-process predictor on the same input must agree exactly
    from paddle_tpu import inference

    config = inference.Config(model_dir)
    config.disable_tpu()
    p = inference.create_predictor(config)
    x = (np.arange(batch * feat) % 7).astype("float32") * 0.25 - 0.5
    ref = p.run([x.reshape(batch, feat)])[0].reshape(-1)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


def test_go_binding_symbols_resolve():
    """Toolchain-free ABI drift check (tools/check_go_binding.py): every
    C.<symbol> the Go binding references must exist in paddle_tpu_capi.h.
    The actual `go build` remains environment-gated below (no Go toolchain
    and no network in this image — recorded per round in ROUND*_NOTES)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "check_go_binding.py")],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_go_binding_compiles(tmp_path, rng, capi_lib):
    if shutil.which("go") is None:
        pytest.skip("no Go toolchain in this image")
    model_dir, _ = _save_model(tmp_path, rng)
    godir = os.path.join(REPO, "go", "paddle")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["CGO_CFLAGS"] = f"-I{os.path.dirname(capi_lib)}"
    env["CGO_LDFLAGS"] = (
        f"-L{os.path.dirname(capi_lib)} -lcapi "
        f"-Wl,-rpath,{os.path.dirname(capi_lib)}"
    )
    proc = subprocess.run(
        ["go", "run", os.path.join(REPO, "go", "demo", "main.go"),
         model_dir],
        capture_output=True, text=True, timeout=600, env=env, cwd=godir,
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr}"
    assert "ok" in proc.stdout


def _save_train_model(tmpdir):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", [-1, 2])
        y = fluid.data("y", [-1, 1])
        pred = fluid.layers.fc(x, 1, num_flatten_dims=1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.2).minimize(loss)
    model_dir = os.path.join(str(tmpdir), "train_model")
    fluid.io.save_train_model(model_dir, main, startup, loss=loss)
    return model_dir


def test_capi_train_from_c_host(tmp_path, capi_lib):
    """C host trains the exported program end to end (reference:
    paddle/fluid/train/demo/demo_trainer.cc flow over the C ABI)."""
    model_dir = _save_train_model(tmp_path)
    capi_dir = os.path.dirname(capi_lib)
    exe_path = os.path.join(str(tmp_path), "capi_train_smoke")
    build = subprocess.run(
        ["g++", os.path.join(REPO, "tests", "capi_train_smoke.c"),
         f"-I{capi_dir}", f"-L{capi_dir}", "-lcapi",
         f"-Wl,-rpath,{capi_dir}", "-o", exe_path],
        capture_output=True, text=True, timeout=120,
    )
    assert build.returncode == 0, build.stderr
    save_dir = os.path.join(str(tmp_path), "saved")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [exe_path, model_dir, "20", save_dir],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert r.returncode == 0, (r.stdout, r.stderr[-2000:])
    assert "CAPI_TRAIN_OK" in r.stdout
    # persistables were saved (param + optimizer state files exist)
    assert os.path.isdir(save_dir) and len(os.listdir(save_dir)) >= 2


def test_trainer_bridge_warm_start(tmp_path, rng):
    """Python-level bridge check: save_train_model with executor saves
    persistables; a new trainer warm-starts from them instead of re-running
    random init (the reference train API's LoadPersistables flow)."""
    from paddle_tpu.inference import capi_bridge as bridge

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.data("x", [-1, 2])
        y = fluid.data("y", [-1, 1])
        pred = fluid.layers.fc(x, 1, num_flatten_dims=1,
                               param_attr=fluid.ParamAttr(name="tw"))
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    model_dir = os.path.join(str(tmp_path), "warm")
    with fluid.scope_guard(scope):
        exe.run(startup)
        scope.set("tw", np.full((2, 1), 0.25, dtype="float32"))
        fluid.io.save_train_model(model_dir, main, startup, loss=loss,
                                  executor=exe)

    tr = bridge.new_trainer(model_dir, use_tpu=False)
    got = np.asarray(tr.scope.find_var("tw"))
    np.testing.assert_allclose(got, 0.25)
    # and it can step
    feed_x = rng.randn(4, 2).astype("float32")
    feed_y = rng.randn(4, 1).astype("float32")
    bridge.trainer_set_input(tr, "x", 0, (4, 2), memoryview(feed_x.tobytes()))
    bridge.trainer_set_input(tr, "y", 0, (4, 1), memoryview(feed_y.tobytes()))
    dt, shape, raw = bridge.trainer_run(tr, "")
    assert np.isfinite(np.frombuffer(raw, "float32")).all()

"""The examples/ book scripts stay runnable (slow: each is an end-to-end
train + serve flow in a subprocess)."""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("name", ["fit_a_line", "recognize_digits",
                                  "serve_transformer", "serve_generation",
                                  "wide_deep"])
def test_example_runs(name):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples", f"{name}.py")],
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]

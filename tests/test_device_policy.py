"""Device policy and compile-cache placement (PR 21).

The platform is read once, stated, and never degraded: a ``TPUPlace`` with
no TPU raises unless the process was pinned to the CPU on purpose; the
persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says,
else in ``<checkout>/.jax_cache``; ``chip_smoke.py`` fails without a chip.
"""

import json
import os
import subprocess
import sys

import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu.core import compile_cache, places

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture
def unpinned():
    """This process as it would be with JAX_PLATFORMS unset and no TPU."""
    jax.config.update("jax_platforms", None)
    yield
    jax.config.update("jax_platforms", "cpu")


def test_tpu_place_maps_to_virtual_device_when_pinned_to_cpu():
    assert jax.config.jax_platforms == "cpu"
    devs = jax.local_devices()
    assert len(devs) >= 8  # conftest's virtual mesh
    assert fluid.TPUPlace(0).jax_device() == devs[0]
    assert fluid.TPUPlace(5).jax_device() == devs[5]
    assert places.tpu_device_count() == len(devs)
    with pytest.raises(RuntimeError, match=r"TPUPlace\(64\)"):
        fluid.TPUPlace(64).jax_device()


def test_tpu_place_raises_without_tpu_when_not_pinned(unpinned):
    assert {d.platform for d in jax.local_devices()} == {"cpu"}
    with pytest.raises(RuntimeError, match="JAX_PLATFORMS=cpu"):
        fluid.TPUPlace(0).jax_device()
    with pytest.raises(RuntimeError, match="no TPU"):
        places.tpu_device_count()
    # the serving engine's default place is the same TPUPlace(0)
    from paddle_tpu.serving import GenerationEngine

    with pytest.raises(RuntimeError, match="no TPU"):
        GenerationEngine()
    # an explicit CPUPlace is still the CPU
    assert fluid.CPUPlace().jax_device().platform == "cpu"


def test_tpu_place_prefers_tpus_over_other_local_devices(monkeypatch,
                                                         unpinned):
    class Dev:
        def __init__(self, platform, i):
            self.platform, self.id = platform, i

    fake = [Dev("cpu", 0), Dev("tpu", 0), Dev("tpu", 1)]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: fake)
    assert fluid.TPUPlace(1).jax_device() is fake[2]
    assert places.tpu_device_count() == 2


def test_cache_dir_follows_the_standard_variable(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.cache_dir() == str(tmp_path)
    assert compile_cache.enabled()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.cache_dir() == os.path.join(REPO, ".jax_cache")
    # unplaced: off on the CPU backend, on for an accelerator
    assert not compile_cache.enabled()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert compile_cache.enabled()
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SMOKE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert not proc.stdout.strip(), "no result may be printed without a chip"


def _rehearse(devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    proc = subprocess.run([sys.executable, SMOKE, "--rehearse-cpu"], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    # two lines: the report, then the verdict in the chip check's shape
    body, last = proc.stdout.strip().splitlines()
    verdict = json.loads(last)
    assert list(verdict) == ["ok", "device"] and verdict["ok"] is True
    assert list(verdict["device"]) == ["platform", "kind", "count"]
    assert verdict["device"]["platform"] == "cpu"
    assert verdict["device"]["count"] == devices
    assert '"rehearsal": true' in body
    report = json.loads(body)
    assert report["device"] == verdict["device"]
    assert list(report)[-1] == "claim" and report["claim"] is None
    return report


def test_chip_smoke_rehearses_on_the_cpu():
    report = _rehearse(devices=1)
    assert report["train"]["last_loss"] < report["train"]["first_loss"]
    assert report["serve"]["requests_completed"] >= 8
    assert report["four_chip"] == {"ran": False, "devices": 1}


@pytest.mark.slow
def test_chip_smoke_rehearses_the_mesh_leg():
    four = _rehearse(devices=8)["four_chip"]
    assert four["ran"] and four["last_loss"] < four["first_loss"]
    assert four["spread"]["feed_devices"] == 4

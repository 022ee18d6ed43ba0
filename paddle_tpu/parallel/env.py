"""Mesh / collective execution context.

TPU-native replacement for the reference's communicator registry keyed by
ring_id (reference: paddle/fluid/platform/collective_helper.h:50-69 — NCCLComm
instances per (ring_id, device)). Here a "ring" is a *named mesh axis* on a
jax.sharding.Mesh; binding ring_id -> axis name is a dynamic context installed
while tracing a program under shard_map/pjit. XLA lowers the collective to ICI
neighbor exchanges — no communicator objects, no stream management.
"""

import contextlib
import os

import numpy as np

import jax
from jax.sharding import Mesh

_bindings = {}
_current_mesh = None


@contextlib.contextmanager
def mesh_context(mesh):
    """Install the mesh a Program is being compiled against, so op
    lowerings that build nested shard_map regions (ops/pipeline.py) can
    find it. The analog of the reference's global DeviceContextPool —
    device topology as ambient state (reference: paddle/fluid/platform/
    device_context.h:331)."""
    global _current_mesh
    old = _current_mesh
    _current_mesh = mesh
    try:
        yield
    finally:
        _current_mesh = old


def current_mesh():
    return _current_mesh


_dgc_axis = None


@contextlib.contextmanager
def dgc_axis_context(axis_name):
    """Installed by CompiledProgram while tracing a DGC program in
    per-shard sparse-exchange mode: the dgc_momentum lowering reads it to
    run the top-k (index, value) all_gather over this axis instead of the
    dense update (ops/optimizers.py)."""
    global _dgc_axis
    old = _dgc_axis
    _dgc_axis = axis_name
    try:
        yield
    finally:
        _dgc_axis = old


def current_dgc_axis():
    return _dgc_axis


@contextlib.contextmanager
def collective_context(bindings):
    """bindings: {ring_id: mesh_axis_name}."""
    global _bindings
    old = _bindings
    _bindings = dict(bindings)
    try:
        yield
    finally:
        _bindings = old


def current_mesh_axis(ring_id=0):
    return _bindings.get(ring_id)


def make_mesh(shape=None, axis_names=None, devices=None):
    """Build a Mesh over the local devices. shape=None → 1-D 'data' axis over
    all devices (the analog of the reference's flat allreduce ring,
    reference: paddle/fluid/framework/parallel_executor.cc:113); a 2-D shape
    maps outer axis to DCN and inner to ICI (the hierarchical allreduce analog,
    parallel_executor.cc:196)."""
    devices = devices if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devices),)
        axis_names = axis_names or ("data",)
    axis_names = tuple(axis_names)
    dev_array = np.array(devices[: int(np.prod(shape))]).reshape(shape)
    return Mesh(dev_array, axis_names)


class ParallelEnv:
    """Process-level distributed environment discovered from env vars
    (reference: python/paddle/fluid/dygraph/parallel.py:54 ParallelEnv,
    launch.py:105 PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM)."""

    def __init__(self):
        self._rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
        self._world_size = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
        self._endpoints = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "").split(",")
        self._current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT", "")

    @property
    def rank(self):
        return self._rank

    @property
    def local_rank(self):
        return self._rank

    @property
    def nranks(self):
        return self._world_size

    @property
    def world_size(self):
        return self._world_size

    @property
    def trainer_endpoints(self):
        return self._endpoints

    @property
    def current_endpoint(self):
        return self._current_endpoint

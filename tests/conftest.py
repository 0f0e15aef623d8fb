"""Test configuration: fake an 8-device cluster on CPU.

The reference tests "multi-node" code serially by linking mpistubs/ (a fake
1-proc MPI).  Our equivalent trick runs JAX on CPU with 8 virtual devices
(SURVEY.md §4), so mesh/sharding/collective code paths execute for real
without TPU hardware.  Must run before jax initialises its backends.
"""

import os

# FORCE (not setdefault): the outer environment may name another
# platform; subprocesses spawned by tests (the C-binding binaries embed
# Python) inherit os.environ and must get CPU like the test process.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(12345)


@pytest.fixture(params=["native", "numpy"])
def library(request, monkeypatch):
    """A test twice: with the native library, and with every caller of
    ``native.available()`` on its numpy/Python branch (``_lib`` gone)."""
    from gpu_mapreduce_tpu import native
    if request.param == "numpy":
        monkeypatch.setattr(native, "_lib", None)
    elif not native.available():
        pytest.skip(f"no native library: {native.build_error()}")
    return request.param


@pytest.fixture
def traced():
    """``traced(run)``: run() with the obs tracer on; returns (its
    result, the spans it left)."""
    from gpu_mapreduce_tpu.obs import get_tracer

    def call(run):
        tr = get_tracer()
        was = tr.enabled
        tr.enable(ring=1 << 14)
        tr.clear()
        try:
            return run(), tr.events()
        finally:
            tr.clear()
            if not was:
                tr.disable()
    return call

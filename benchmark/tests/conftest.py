"""The benchmark's own tests: ``python -m pytest benchmark/tests`` (not part
of tier-1).  CPU only: eight virtual devices, as ``tests/conftest.py``
makes them; the chip check is replaced in the tests that run the harness
loop, never in the harness."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402


@pytest.fixture
def cpu_harness(monkeypatch, tmp_path):
    """The real harness with three things replaced, here only: the chip
    check (any device passes), the cache directory (a temporary one), and
    the peaks table (the CPU gets the v5e's numbers, so that the readers
    run)."""
    import json

    from benchmark import cache, harness, kernels

    def any_device(chips):
        import jax
        return jax.devices()

    monkeypatch.setattr(harness, "require_chips", any_device)
    monkeypatch.setattr(cache, "ROOT", str(tmp_path / "cache"))
    with open(kernels.PEAKS_FILE) as f:
        table = json.load(f)
    table["kinds"]["cpu"] = table["kinds"]["TPU v5 lite"]
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps(table))
    monkeypatch.setattr(kernels, "PEAKS_FILE", str(peaks))
    return harness

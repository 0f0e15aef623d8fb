"""chip_smoke.py on the CPU: its two legs run tiny on the fake 8-device
mesh (Pallas in interpret mode) and agree with their references; its
``main()`` refuses a machine without a TPU; and the package's compile
cache goes where the contract says (ISSUE 22)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh
    return make_mesh()


def test_leg_invertedindex_tiny(mesh, tmp_path):
    r = chip_smoke.leg_invertedindex(mesh, str(tmp_path),
                                     file_bytes=64 << 10)
    assert r["parts"] == 8 and r["files"] == 16
    assert r["npairs"] > r["nunique"] > 0
    # the over-long URLs (1 in 500) were dropped by both sides, and the
    # long tail took the second gather
    assert r["map_stats"]["nlong_max"] > 0
    # CPU: the kernel is interpreted, so no Mosaic custom call — the
    # leg asserts the two agree; the chip run asserts the compiled case
    assert r["interpret"] is True and r["mosaic_custom_call"] is False
    assert r["exchange"]["rows"] == r["npairs"]


def test_leg_graph_tiny(mesh, tmp_path):
    r = chip_smoke.leg_graph(mesh, str(tmp_path), scale=8)
    assert r["edges"] == 8 << 8
    assert r["components"] >= 1 and r["pagerank_l1"] < 1e-5
    assert r["exchange"]["rows"] > 0


def test_leg_detects_a_wrong_answer(mesh, tmp_path, monkeypatch):
    """The comparison really compares: a reference that disagrees by one
    URL fails the leg."""
    real = chip_smoke.index_reference

    def off_by_one(paths):
        want, npairs = real(paths)
        want.pop(next(iter(want)))
        return want, npairs

    monkeypatch.setattr(chip_smoke, "index_reference", off_by_one)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.leg_invertedindex(mesh, str(tmp_path),
                                     file_bytes=64 << 10)


def _run(code_or_script, env_extra, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra)
    return subprocess.run([sys.executable, *code_or_script], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_main_refuses_cpu():
    p = _run(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert "found platform 'cpu'" in p.stdout
    assert "nothing was run" in p.stdout
    assert '"ok"' not in p.stdout            # no result line


_CACHE_PROBE = ("import jax, gpu_mapreduce_tpu; "
                "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_honours_the_variable(tmp_path):
    want = str(tmp_path / "elsewhere")
    p = _run(["-c", _CACHE_PROBE], {"JAX_PLATFORMS": "cpu",
                                    "JAX_COMPILATION_CACHE_DIR": want})
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == want
    # the serve daemon's helper must not re-root it either
    p = _run(["-c", "import jax, gpu_mapreduce_tpu\n"
              "from gpu_mapreduce_tpu.plan.cache import "
              "enable_executable_cache\n"
              "assert enable_executable_cache() is None\n"
              "print(jax.config.jax_compilation_cache_dir)"],
             {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": want,
              "MRTPU_CAS_DIR": str(tmp_path / "cas")})
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == want


def test_compile_cache_default_is_in_the_checkout():
    p = _run(["-c", _CACHE_PROBE], {"JAX_PLATFORMS": "cpu"},
             drop=("JAX_COMPILATION_CACHE_DIR",))
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == os.path.join(REPO, ".jax_cache")

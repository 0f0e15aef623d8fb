"""The harness takes a cell as data: a new configuration, traffic mix,
per-layer metric and ``workloads`` entry are files ADDED under ``benchmark/``
and entries added to ``BENCHMARK.json``; a metric that is there and that the
new cell reports gets the cell's name in its ``workloads`` list in
``BENCHMARK.json``.  No file under ``benchmark/`` is edited, the copy still
passes the contract's tests, and the new cell is listed and runs, untraced
and traced."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmark import cells

ROOT = cells.ROOT

RUNNER = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
sys.path.insert(0, os.getcwd())
sys.path.insert(1, {root!r})        # the package under test
from benchmark import cells, harness, kernels, xtrace
import benchmark
assert os.path.dirname(benchmark.__file__) == os.path.join(os.getcwd(), "benchmark")
def any_device(chips):              # the chip check, replaced here only
    import jax
    return jax.devices()
harness.require_chips = any_device
# as conftest.py and test_harness.py do: the CPU gets the v5e's peaks and
# its executor threads stand in for device planes
table = json.load(open(kernels.PEAKS_FILE))
table["kinds"]["cpu"] = table["kinds"]["TPU v5 lite"]
kernels.PEAKS_FILE = os.path.join(os.getcwd(), "peaks_cpu.json")
json.dump(table, open(kernels.PEAKS_FILE, "w"))
real = xtrace.load
def load(path, host_names=None):
    raw = real(path)
    for name, events in sorted(raw["host"].items()):
        if "PjRtCpuClient" in name:
            raw["devices"][len(raw["devices"])] = {{
                "ops": [e for e in events if e[1] > e[0]], "modules": []}}
    return raw
xtrace.load = load
for trace in (False, True):
    line = harness.run_cell(cells.load_cell({name!r}), 3, 0.5, trace, 0.0)
    print(json.dumps(line))
"""


def _hashes(top):
    out = {}
    for dirpath, dirs, files in os.walk(top):
        dirs[:] = [d for d in dirs if d not in ("cache", "__pycache__")]
        for fn in files:
            p = os.path.join(dirpath, fn)
            with open(p, "rb") as f:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_new_cell_is_added_files_and_entries(tmp_path):
    for fn in ("BENCHMARK.json", "PERF.md"):
        shutil.copy(os.path.join(ROOT, fn), tmp_path)
    bench = tmp_path / "benchmark"
    shutil.copytree(os.path.join(ROOT, "benchmark"), bench,
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    before = _hashes(bench)

    # a new deployment (a tiny R-MAT whose graph comes from --seed), a new
    # traffic mix (the graph in set-up, the upper edges as the job), a new
    # layer metric: three files
    config = json.loads((bench / "configs" / "mrmpi-rmat-1chip.json")
                        .read_text())
    config.update(name="rmat-tiny", scale=7)
    (bench / "configs" / "rmat-tiny.json").write_text(json.dumps(config))
    (bench / "traffic" / "upper-only.json").write_text(json.dumps({
        "kind": "oink_script",
        "setup": ["rmat {scale} {edge_factor} {a} {b} {c} {d} {fraction} "
                  "{seed} -o NULL mre"],
        "setup_checks": ["refs.graph:check_edges"],
        "job": ["edge_upper -i mre -o NULL mru"],
        "job_checks": ["refs.graph:check_upper"],
        "device_results": ["mru"],
        "work": {"edges": "2 ** scale * edge_factor"}}))
    (bench / "layer_metrics" / "edge_upper_s.json").write_text(json.dumps({
        "reader": "stage_seconds", "args": {"stages": ["edge_upper"]}}))

    # ... and entries in BENCHMARK.json: the configuration, the cell, the new
    # metric, and the cell's name on two metrics that are there (an
    # end-to-end rate and a per-layer one), whose files stay as they are
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "rmat-tiny", "source": config["source"],
        "file": "benchmark/configs/rmat-tiny.json", "reduced": ["scale"],
        "why": "a test's deployment"})
    spec["workloads"].append({
        "name": "upper-tiny", "config": "rmat-tiny", "traffic": "upper-only",
        "chips": 1, "why": "a test's cell"})
    spec["per_layer"].append({
        "name": "edge_upper_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "group + reduce", "moves": "job_s",
        "workloads": ["upper-tiny"]})
    joined = []
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("edge_rate", "group_reduce_s"):
            m["workloads"].append("upper-tiny")
            joined.append(m["name"])
    assert sorted(joined) == ["edge_rate", "group_reduce_s"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    after = _hashes(bench)
    assert {k: after[k] for k in before} == before      # nothing edited
    assert sorted(set(after) - set(before)) == [
        "configs/rmat-tiny.json", "layer_metrics/edge_upper_s.json",
        "traffic/upper-only.json"]

    # the copy still meets the contract's limits and its own rules
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    contract = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "benchmark/tests/test_contract.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert contract.returncode == 0, contract.stdout[-3000:]

    listed = subprocess.run(
        [sys.executable, "benchmark/run.py", "--list"], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120)
    assert listed.returncode == 0, listed.stderr
    assert "upper-tiny\tchips=1\tconfig=rmat-tiny\ttraffic=upper-only" \
        in listed.stdout

    ran = subprocess.run(
        [sys.executable, "-c", RUNNER.format(root=ROOT, name="upper-tiny")],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert ran.returncode == 0, ran.stderr[-2000:]
    plain, traced = [json.loads(ln) for ln in ran.stdout.splitlines()
                     if ln.startswith('{"correct"')]
    for line in (plain, traced):
        assert line["correct"] is True and line["failed"] == 0
    assert set(plain["metrics"]) == {"job_s", "edge_rate", "setup_s"}
    # the new metric, the joined one, and those every cell reports; none of
    # another cell's (the CPU has no memory statistics: no peak_hbm_gib)
    assert set(traced["metrics"]) == {
        "edge_upper_s", "group_reduce_s", "entry_self_s",
        "compiles_in_window", "warm_start_s", "device_idle_share"}
    assert traced["metrics"]["edge_upper_s"]["unit"] == "s"
    assert traced["metrics"]["group_reduce_s"]["value"] > 0

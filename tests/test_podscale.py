"""Pod-scale compile sanity: the exchange must trace/compile fast at
P=32 (VERDICT r1 #8: its trace grows with P — ``shuffle._send_windows``).

Runs in a subprocess because the virtual device count is fixed at jax
init (conftest pins 8 for everything else).
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r"""
import os, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
os.environ["JAX_ENABLE_X64"] = "1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import numpy as np
from gpu_mapreduce_tpu.core.frame import KVFrame
from gpu_mapreduce_tpu.core.column import DenseColumn
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.parallel.sharded import shard_frame
from gpu_mapreduce_tpu.parallel import shuffle

mesh = make_mesh()
assert shuffle.mesh_axis_size(mesh) == 32
rng = np.random.default_rng(5)
keys = rng.integers(0, 997, size=4096).astype(np.uint64)
vals = np.arange(len(keys), dtype=np.uint64)
import collections
oracle = collections.Counter(zip(keys.tolist(), vals.tolist()))
t0 = time.time()
skv = shard_frame(KVFrame(DenseColumn(keys), DenseColumn(vals)), mesh)
out = shuffle.exchange(skv, ("hash", None))
got = collections.Counter((int(k), int(v))
                          for k, v in out.to_host().pairs())
assert got == oracle, "pair multiset mismatch"
print(f"P=32 exchange: {time.time()-t0:.1f}s", flush=True)
print("OK")
"""


def test_exchange_compiles_at_p32():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout, r.stdout


_SCRIPT_R3 = r"""
import os, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
os.environ["JAX_ENABLE_X64"] = "1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import numpy as np, tempfile, os as _os
from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.parallel.staging import stage_graph
from gpu_mapreduce_tpu.models.cc import _cc_sharded_fn

mesh = make_mesh()
rng = np.random.default_rng(5)
e = rng.integers(0, 200, (4096, 2)).astype(np.uint64)

t0 = time.time()
mr = MapReduce(mesh)
mr.map(1, lambda i, kv, p: kv.add_batch(e, np.zeros(len(e), np.uint8)))
sg = stage_graph(mr, mesh)
labels, it = _cc_sharded_fn(mesh, sg.n, max(sg.n, 1))(sg.src, sg.dst,
                                                      sg.valid)
assert labels.shape == (sg.n,)
print(f"staged cc @P=32: {time.time()-t0:.1f}s", flush=True)

from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex
t0 = time.time()
with tempfile.TemporaryDirectory() as tmp:
    paths = []
    for i in range(32):
        p = _os.path.join(tmp, f"f{i}.html")
        open(p, "wb").write(b'<a href="http://d%02d.org/a">x</a>pad' % i * 3)
        paths.append(p)
    ii = InvertedIndex(comm=mesh, engine="xla")
    nhits, nuniq = ii.run(paths)
    assert (nhits, nuniq) == (96, 32), (nhits, nuniq)
print(f"SPMD ingestion @P=32: {time.time()-t0:.1f}s", flush=True)
print("OK")
"""


def test_round3_paths_compile_at_p32():
    """Round-3 SPMD paths — device staging and the shard_map ingestion —
    must trace/compile and run at pod scale (P=32)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT_R3], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout, r.stdout


_SCRIPT_R4 = r"""
import os, time, tempfile, collections
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=32"
os.environ["JAX_ENABLE_X64"] = "1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import numpy as np
from gpu_mapreduce_tpu.core.frame import KVFrame
from gpu_mapreduce_tpu.core.column import DenseColumn
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.parallel.sharded import shard_frame
from gpu_mapreduce_tpu.parallel import shuffle

mesh = make_mesh()
P = shuffle.mesh_axis_size(mesh)
assert P == 32

# (a) speculative exchange at P=32: repeat same-shape exchange must hit
# the cap cache (no second fresh phase-2 sizing) and stay correct
rng = np.random.default_rng(9)
keys = rng.integers(0, 2047, size=8192).astype(np.uint64)
vals = np.arange(len(keys), dtype=np.uint64)
oracle = collections.Counter(zip(keys.tolist(), vals.tolist()))
shuffle._SPEC_CACHE.clear()
for rep in range(2):
    skv = shard_frame(KVFrame(DenseColumn(keys), DenseColumn(vals)), mesh)
    t0 = time.time()
    out = shuffle.exchange(skv, ("hash", None))
    got = collections.Counter((int(k), int(v))
                              for k, v in out.to_host().pairs())
    assert got == oracle, f"rep {rep}: mismatch"
    print(f"spec rep {rep}: {time.time()-t0:.1f}s", flush=True)
assert len(shuffle._SPEC_CACHE) == 1

# (b) per-shard output files at P=32 through the mesh InvertedIndex
from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex
with tempfile.TemporaryDirectory() as tmp:
    paths = []
    exp = collections.defaultdict(set)
    for i in range(P):
        p = os.path.join(tmp, f"f{i:02d}.html")
        with open(p, "wb") as f:
            u = b"http://pod%02d.org/x" % (i % 11)
            f.write((b'<a href="' + u + b'">x</a>pad ') * 3)
            exp[u].add(p)
        paths.append(p)
    ii = InvertedIndex(engine="xla", comm=mesh)
    outdir = os.path.join(tmp, "out")
    nh, nu = ii.run(paths, outdir=outdir)
    parts = sorted(os.listdir(outdir))
    assert parts == [f"part-{q:05d}" for q in range(P)], parts
    got = {}
    for part in parts:
        for line in open(os.path.join(outdir, part)):
            url, names = line.rstrip("\n").split("\t")
            got[url.encode()] = set(names.split(" "))
    assert got == dict(exp)
    assert nh == 3 * P and nu == 11
print("OK")
"""


def test_round4_paths_compile_at_p32():
    """r4 paths at pod scale: speculative exchange capacity reuse and
    the per-shard output writer trace/compile and run at P=32."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT_R4], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout, r.stdout


_SCRIPT_R5 = r"""
import os, time, collections, tempfile
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=64"
os.environ["JAX_ENABLE_X64"] = "1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
import numpy as np
from gpu_mapreduce_tpu.core.frame import KVFrame
from gpu_mapreduce_tpu.core.column import DenseColumn, ShardTables
from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.parallel.mesh import make_mesh, make_mesh2
from gpu_mapreduce_tpu.parallel.sharded import shard_frame
from gpu_mapreduce_tpu.parallel import shuffle

# (a) the flat exchange at P=64 — beyond the r1 P=32 compile-sanity bar
mesh = make_mesh()
P = shuffle.mesh_axis_size(mesh)
assert P == 64
rng = np.random.default_rng(11)
keys = rng.integers(0, 1499, size=8192).astype(np.uint64)
vals = np.arange(len(keys), dtype=np.uint64)
oracle = collections.Counter(zip(keys.tolist(), vals.tolist()))
t0 = time.time()
skv = shard_frame(KVFrame(DenseColumn(keys), DenseColumn(vals)), mesh)
out = shuffle.exchange(skv, ("hash", None))
got = collections.Counter((int(k), int(v))
                          for k, v in out.to_host().pairs())
assert got == oracle, "P=64 flat: mismatch"
print(f"P=64 flat exchange: {time.time()-t0:.1f}s", flush=True)

# (b) 8x8 hierarchical DCN route at P=64
mrh = MapReduce(make_mesh2(8, 8))
mrh.map(1, lambda i, kv, p: kv.add_batch(keys, vals))
nuh = mrh.collate()
assert nuh == len(np.unique(keys))
print("P=64 8x8 hier: ok", flush=True)

# (c) r5 generic per-shard file ingestion + dest-sharded tables at P=64
from gpu_mapreduce_tpu.oink.kernels import read_words
with tempfile.TemporaryDirectory() as tmp:
    paths = []
    for i in range(96):
        p = os.path.join(tmp, f"w{i}.txt")
        open(p, "wb").write(b" ".join(b"tok%d" % (j % 251)
                                      for j in range(i, i + 40)))
        paths.append(p)
    mrw = MapReduce(make_mesh())
    nw = mrw.map_files(paths, read_words)
    assert nw == 96 * 40
    assert mrw.last_ingest["mode"] == "mesh", mrw.last_ingest
    assert isinstance(mrw.kv.one_frame().key_decode, ShardTables)
    mrw.collate()
print("P=64 mesh ingest: ok", flush=True)
print("OK")
"""


def test_round5_paths_compile_at_p64():
    """r5 paths beyond P=32 (VERDICT r4 #9): the flat exchange,
    the 8×8 hierarchical route, and the generic per-shard file ingest
    trace/compile and run at P=64."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT_R5], cwd=REPO,
                       env=env, capture_output=True, text=True,
                       timeout=560)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "OK" in r.stdout, r.stdout

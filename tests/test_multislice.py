"""Multi-slice (DCN) mesh mapping (VERDICT r1 #9): the proc axis factors
into (slice, chip); the shuffle routes hierarchically — ICI all-to-all
within a slice grouping rows by destination chip, then ONE cross-slice
all-to-all between same-chip-index peers.  Results must be identical to
the flat mesh (the hierarchy is a routing detail, not a semantic)."""

import numpy as np
import pytest

from gpu_mapreduce_tpu import MapReduce
from gpu_mapreduce_tpu.parallel.mesh import (make_mesh, make_mesh2,
                                             mesh_axis_size)
from gpu_mapreduce_tpu.parallel.sharded import ShardedKV, shard_frame
from gpu_mapreduce_tpu.parallel.shuffle import exchange
from gpu_mapreduce_tpu.core.frame import KVFrame
from gpu_mapreduce_tpu.core.column import DenseColumn


@pytest.mark.parametrize("shape", [(2, 4), (4, 2), (2, 2)])
def test_hier_exchange_matches_flat(shape, rng):
    S, C = shape
    P = S * C
    n = 500
    keys = rng.integers(0, 1 << 40, n).astype(np.uint64)
    vals = rng.integers(0, 1 << 30, n).astype(np.uint64)
    fr = KVFrame(DenseColumn(keys), DenseColumn(vals))

    flat = exchange(shard_frame(fr, make_mesh(P)), ("hash", None))
    hier = exchange(shard_frame(fr, make_mesh2(S, C)), ("hash", None))
    assert mesh_axis_size(hier.mesh) == P
    np.testing.assert_array_equal(flat.counts, hier.counts)
    f1, f2 = flat.to_host(), hier.to_host()
    o1 = np.lexsort((np.asarray(f1.value.data), np.asarray(f1.key.data)))
    o2 = np.lexsort((np.asarray(f2.value.data), np.asarray(f2.key.data)))
    np.testing.assert_array_equal(np.asarray(f1.key.data)[o1],
                                  np.asarray(f2.key.data)[o2])
    np.testing.assert_array_equal(np.asarray(f1.value.data)[o1],
                                  np.asarray(f2.value.data)[o2])
    # per-shard contents must match exactly (same key→proc map)
    for i in range(P):
        a = np.sort(np.asarray(flat.key)[i * flat.cap:
                                         i * flat.cap + flat.counts[i]])
        b = np.sort(np.asarray(hier.key)[i * hier.cap:
                                         i * hier.cap + hier.counts[i]])
        np.testing.assert_array_equal(a, b)


def test_full_pipeline_on_multislice_mesh(rng):
    keys = (rng.integers(0, 50, 3000)).astype(np.uint64)
    import collections
    want = collections.Counter(keys.tolist())

    mr = MapReduce(make_mesh2(2, 4))
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, np.ones(len(keys),
                                                          np.uint64)))
    mr.collate()
    from gpu_mapreduce_tpu.ops.reduces import count
    n = mr.reduce(count, batch=True)
    assert n == len(want)
    got = {int(k): int(v) for k, v in mr.kv.one_frame().to_host().pairs()}
    assert got == dict(want)


def test_cc_find_on_multislice_mesh(tmp_path, rng):
    from gpu_mapreduce_tpu.oink import ObjectManager, run_command
    from tests.test_graph_commands import union_find_labels
    e = rng.integers(0, 80, (200, 2)).astype(np.uint64)
    e = np.unique(e[e[:, 0] != e[:, 1]], axis=0)
    path = tmp_path / "g.txt"
    path.write_text("\n".join(f"{a} {b}" for a, b in e) + "\n")
    out = tmp_path / "cc.out"
    obj = ObjectManager(comm=make_mesh2(2, 4))
    cmd = run_command("cc_find", ["0"], obj=obj, inputs=[str(path)],
                      outputs=[str(out)], screen=False)
    oracle = union_find_labels(e, np.unique(e))
    got = {int(a): int(b) for a, b in
           np.loadtxt(out, dtype=np.uint64).reshape(-1, 2)}
    assert got == oracle
    assert cmd.ncc == len(set(oracle.values()))


def test_gather_and_broadcast_on_multislice(rng):
    mr = MapReduce(make_mesh2(2, 4))
    keys = np.arange(64, dtype=np.uint64)
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
    mr.aggregate()
    mr.gather(2)
    fr = mr.kv.one_frame()
    assert isinstance(fr, ShardedKV)
    assert (fr.counts[2:] == 0).all() and fr.counts[:2].sum() == 64
    mr.broadcast(0)
    fr = mr.kv.one_frame()
    assert all(int(c) == int(fr.counts[0]) for c in fr.counts)


def test_spmd_ingestion_on_multislice_mesh(tmp_path):
    """Mesh-SPMD InvertedIndex ingestion over a (slice, chip) mesh: the
    per-device corpus placement and shard_map extract run on 2-axis
    meshes identically to flat ones."""
    from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex

    paths = []
    for i in range(8):
        p = tmp_path / f"f{i}.html"
        p.write_bytes(b'<a href="http://s%d.org/p">x</a>fill' % (i % 3) * 5)
        paths.append(str(p))
    ii1 = InvertedIndex()
    n1 = ii1.run(paths)
    ii2 = InvertedIndex(comm=make_mesh2(2, 4))
    n2 = ii2.run(paths)
    assert n1 == n2
    assert ii1.urls == ii2.urls


def test_per_shard_output_on_multislice_mesh(tmp_path):
    """r4: per-shard part files + destination-sharded url dicts work on
    a (slice, chip) mesh too — 8 part files, union == serial oracle."""
    import collections
    import os

    from gpu_mapreduce_tpu.apps.invertedindex import InvertedIndex

    paths = []
    oracle = collections.defaultdict(set)
    for i in range(6):
        p = tmp_path / f"g{i}.html"
        body = []
        for j in range(30):
            u = "http://m%d.org/q%d" % (j % 5, j)
            body.append('<a href="%s">x</a> words ' % u)
            oracle[u.encode()].add(str(p))
        p.write_bytes("".join(body).encode())
        paths.append(str(p))
    ii = InvertedIndex(engine="xla", comm=make_mesh2(2, 4))
    outdir = str(tmp_path / "out")
    nh, nu = ii.run(paths, outdir=outdir)
    parts = sorted(os.listdir(outdir))
    assert parts == [f"part-{p:05d}" for p in range(8)]
    got = {}
    for part in parts:
        for line in open(os.path.join(outdir, part)):
            url, names = line.rstrip("\n").split("\t")
            assert url.encode() not in got
            got[url.encode()] = set(names.split(" "))
    assert got == dict(oracle)


def test_init_multihost_single_process():
    """init_multihost (the MPI_Init analog) joins the multi-controller
    runtime; exercised at num_processes=1 in a subprocess (the runtime
    binds ports and can only initialise once per process)."""
    import os
    import subprocess
    import sys
    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=4'\n"
        "from gpu_mapreduce_tpu.parallel.mesh import (init_multihost,"
        " make_mesh, mesh_axis_size)\n"
        "import socket\n"
        "s = socket.socket(); s.bind(('127.0.0.1', 0))\n"
        "port = s.getsockname()[1]; s.close()\n"
        "pid = init_multihost(f'127.0.0.1:{port}', 1, 0)\n"
        "assert pid == 0, pid\n"
        "import jax\n"
        "assert jax.process_count() == 1\n"
        "assert mesh_axis_size(make_mesh()) == 4\n"
        "print('OK')\n")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_PLATFORMS", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                       capture_output=True, text=True, timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout

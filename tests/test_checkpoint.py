"""Checkpoint/restore round-trips (core/checkpoint.py — a capability
improvement over the reference, which has no restartable persistence:
SURVEY.md §5, page files deleted on destruction)."""

import os

import numpy as np
import pytest

from gpu_mapreduce_tpu import MapReduce
from gpu_mapreduce_tpu.core.runtime import MRError


def kv_pairs(mr):
    pairs = []
    mr.scan_kv(lambda k, v, p: pairs.append((k, v)))
    return pairs


def test_kv_roundtrip(tmp_path):
    mr = MapReduce()
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(1000, dtype=np.uint64), np.arange(1000) * 2))
    n = mr.save(str(tmp_path / "ckpt"))
    assert n >= 1
    mr2 = MapReduce()
    assert mr2.load(str(tmp_path / "ckpt")) == 1000
    assert kv_pairs(mr2) == kv_pairs(mr)


def test_kmv_roundtrip(tmp_path):
    mr = MapReduce()
    mr.map(1, lambda i, kv, p: [kv.add(i % 7, i) for i in range(100)])
    mr.convert()
    mr.save(str(tmp_path / "c"))
    groups = {}
    mr.scan_kmv(lambda k, vs, p: groups.__setitem__(k, list(vs)))
    mr2 = MapReduce()
    assert mr2.load(str(tmp_path / "c")) == 7
    groups2 = {}
    mr2.scan_kmv(lambda k, vs, p: groups2.__setitem__(k, list(vs)))
    assert groups == groups2


def test_bytes_and_objects_roundtrip(tmp_path):
    mr = MapReduce()
    mr.map(1, lambda i, kv, p: [kv.add(w, 1) for w in
                                (b"alpha", b"beta", b"alpha")])
    mr.save(str(tmp_path / "b"))
    mr2 = MapReduce()
    mr2.load(str(tmp_path / "b"))
    assert sorted(kv_pairs(mr2)) == sorted(kv_pairs(mr))

    mro = MapReduce()
    mro.map(1, lambda i, kv, p: kv.add(("tup", 3), {"d": [1, 2]}))
    mro.save(str(tmp_path / "o"))
    mro2 = MapReduce()
    mro2.load(str(tmp_path / "o"))
    assert kv_pairs(mro2) == [(("tup", 3), {"d": [1, 2]})]


def test_spilled_roundtrip(tmp_path):
    """A spilled multi-frame KV checkpoints frame-by-frame and restores
    with identical content."""
    mr = MapReduce(outofcore=1, memsize=1, maxpage=1,
                   fpath=str(tmp_path / "spill"))
    keys = np.arange(300_000, dtype=np.uint64)
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
    nf = mr.save(str(tmp_path / "ck"))
    assert nf > 1                      # genuinely multi-frame
    mr2 = MapReduce()
    assert mr2.load(str(tmp_path / "ck")) == 300_000


def test_mesh_dataset_checkpoints_to_host(tmp_path):
    from gpu_mapreduce_tpu.parallel.mesh import make_mesh

    mr = MapReduce(make_mesh(4))
    keys = np.arange(64, dtype=np.uint64) % 9
    mr.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
    mr.aggregate()
    mr.save(str(tmp_path / "m"))
    mr2 = MapReduce()                   # restores WITHOUT the mesh
    assert mr2.load(str(tmp_path / "m")) == 64


def test_script_save_load(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    from gpu_mapreduce_tpu.oink.script import OinkScript

    s = OinkScript(screen=False, logfile=None)
    s.run_string("mr a\n")
    s.obj.get_mr("a").map(1, lambda i, kv, p: kv.add(1, 2))
    s.run_string(f"a save {tmp_path}/ck\n"
                 f"mr b\n"
                 f"b load {tmp_path}/ck\n")
    assert kv_pairs(s.obj.get_mr("b")) == [(1, 2)]


def test_load_missing_manifest(tmp_path):
    with pytest.raises(MRError, match="manifest"):
        MapReduce().load(str(tmp_path / "nope"))


def test_save_refuses_open_buffers(tmp_path):
    mr = MapReduce()
    kvh = mr.open()
    kvh.add(1, 2)
    with pytest.raises(MRError, match="uncompleted"):
        mr.save(str(tmp_path / "x"))
    mr.close()
    assert mr.save(str(tmp_path / "x")) == 1


def test_load_streams_into_outofcore_budget(tmp_path):
    """Restoring into an outofcore MR spills frame-by-frame — resident
    bytes stay within ~the budget, never the whole checkpoint."""
    src = MapReduce()
    keys = np.arange(400_000, dtype=np.uint64)
    src.map(1, lambda i, kv, p: kv.add_batch(keys, keys))
    src.save(str(tmp_path / "big"))

    dst = MapReduce(outofcore=1, memsize=1, maxpage=1,
                    fpath=str(tmp_path / "sp"))
    assert dst.load(str(tmp_path / "big")) == 400_000
    assert dst.kv._resident_bytes() <= 2 * (1 << 20)
    assert sum(1 for _ in dst.kv.frames()) >= 1   # frames stream back


def test_collapse_mixed_dtype_stays_exact():
    """uint64 keys above 2^53 with int64 values must NOT round through
    a float64 promotion (review r2)."""
    mr = MapReduce()
    big = (1 << 60) + 1
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.array([big], np.uint64), np.array([-1], np.int64)))
    mr.collapse(0)
    groups = {}
    mr.scan_kmv(lambda k, vs, p: groups.__setitem__(k, list(vs)))
    assert groups[0][0] == big
    assert groups[0][1] == -1


def test_example_in_checkpoint(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    from gpu_mapreduce_tpu.oink.script import OinkScript

    s = OinkScript(screen=False, logfile=None)
    s.run_file(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "examples", "in.checkpoint"))
    a = sorted((tmp_path / "deg.original").read_text().split())
    b = sorted((tmp_path / "deg.restored").read_text().split())
    assert a == b and len(a) > 0


def test_save_double_fault_preserves_old_checkpoint(tmp_path, monkeypatch):
    """ADVICE r3: if the tmp→path rename fails AND the old→path restore
    also fails, the previous checkpoint must survive on disk (the
    cleanup used to rmtree the only remaining copy)."""
    import os

    from gpu_mapreduce_tpu.core import checkpoint

    path = str(tmp_path / "ck")
    mr = MapReduce()
    mr.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(8, dtype=np.uint64), np.ones(8, np.uint64)))
    mr.save(path)

    mr2 = MapReduce()
    mr2.map(1, lambda i, kv, p: kv.add_batch(
        np.arange(4, dtype=np.uint64), np.zeros(4, np.uint64)))

    real_rename = os.rename

    def failing_rename(src, dst):
        if dst == path:            # both the swap and the restore
            raise OSError("injected rename failure")
        return real_rename(src, dst)

    monkeypatch.setattr(checkpoint.os, "rename", failing_rename)
    with pytest.raises(MRError, match="survives"):
        mr2.save(path)
    monkeypatch.undo()

    old = [d for d in os.listdir(tmp_path) if d.startswith("ck.old.")]
    assert old, "previous checkpoint dir was deleted in the double fault"
    mr3 = MapReduce()
    mr3.load(str(tmp_path / old[0]))
    got = []
    mr3.scan_kv(lambda k, v, p: got.append(int(k)))
    assert sorted(got) == list(range(8))

"""plan/ subsystem: golden equivalence (fused output == eager output)
for every fusible chain, plan-cache hit/eviction, fallback-on-host-tier,
bounded shuffle jit caches and per-call exchange stats (ISSUE 2)."""

import numpy as np
import pytest

from gpu_mapreduce_tpu.core.mapreduce import MapReduce
from gpu_mapreduce_tpu.core.runtime import global_counters
from gpu_mapreduce_tpu.ops.reduces import (count, cull, max_values,
                                           sum_values)
from gpu_mapreduce_tpu.parallel.mesh import make_mesh
from gpu_mapreduce_tpu.plan import plan_cache, plan_history

TEXT1 = b"the quick brown fox jumps over the lazy dog\nthe fox ran\n"
TEXT2 = b"pack my box with five dozen liquor jugs\nthe dog slept\n"


def _filler(keys, vals):
    def m(itask, kv, ptr):
        kv.add_batch(keys, vals)
    return m


def scan_pairs(mr):
    got = []
    mr.scan_kv(lambda k, v, p: got.append((k if isinstance(k, bytes)
                                           else int(k), int(v))))
    return sorted(got)


def run_chain(comm, fuse, kernel, keys, vals, **settings):
    mr = MapReduce(comm, fuse=fuse, **settings)
    mr.map(1, _filler(keys, vals))
    mr.aggregate()
    mr.convert()
    n = mr.reduce(kernel, batch=True)
    pairs = scan_pairs(mr)
    return int(n), pairs


def intcount_keys(n=3000, card=97):
    k = ((np.arange(n, dtype=np.uint64) * 7919) % card).astype(np.uint64)
    return k, np.ones(n, np.int64)


# ---------------------------------------------------------------------------
# golden equivalence: fused == eager, serial + fake-cluster mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ndev", [None, 1, 4, 8])
@pytest.mark.parametrize("kernel", [count, sum_values, max_values, cull])
def test_intcount_chain_equivalence(ndev, kernel):
    """The intcount pipeline (dense u64 keys) through every registered
    kernel reduce: fused output byte-identical to eager."""
    keys, _ = intcount_keys()
    vals = np.arange(len(keys), dtype=np.int64)
    comm = make_mesh(ndev) if ndev else None
    eager = run_chain(comm, 0, kernel, keys, vals)
    fused = run_chain(make_mesh(ndev) if ndev else None, 1, kernel,
                      keys, vals)
    assert eager == fused


@pytest.mark.parametrize("ndev", [None, 4])
def test_wordfreq_host_reduce_equivalence(tmp_path, ndev):
    """wordfreq with byte-string keys and a HOST python reduce: the
    collate fuses (byte keys intern + exchange + group in 2 programs),
    the host-tier reduce falls back — output identical to eager."""
    from gpu_mapreduce_tpu.apps.wordfreq import _fileread, _sum
    p1, p2 = tmp_path / "a.txt", tmp_path / "b.txt"
    p1.write_bytes(TEXT1)
    p2.write_bytes(TEXT2)
    files = [str(p1), str(p2)]

    def wf(fuse):
        mr = MapReduce(make_mesh(ndev) if ndev else None, fuse=fuse)
        nwords = mr.map_files(files, _fileread)
        mr.collate()
        nunique = mr.reduce(_sum)
        return int(nwords), int(nunique), scan_pairs(mr)

    assert wf(0) == wf(1)


def test_wordfreq_app_end_to_end_fused(tmp_path):
    """The full wordfreq app (collate→reduce→gather→sort→scan) under
    MRTPU-style fuse=1 via settings: top-N identical to eager."""
    from gpu_mapreduce_tpu.apps.wordfreq import _fileread, _sum
    from gpu_mapreduce_tpu.apps.common import top_n
    p1 = tmp_path / "a.txt"
    p1.write_bytes(TEXT1 + TEXT2)

    def wf(fuse):
        mr = MapReduce(make_mesh(4), fuse=fuse)
        mr.map_files([str(p1)], _fileread)
        mr.collate()
        mr.reduce(_sum)
        return sorted((k, int(v)) for k, v in top_n(mr, 5))

    assert wf(0) == wf(1)


@pytest.mark.parametrize("kernel", [count, cull])
def test_invertedindex_pairs_equivalence(kernel):
    """The invertedindex shape — (url_id, doc_id) u64 pairs, heavy key
    repetition — counted/dedup'd fused vs eager on the mesh."""
    rng = np.random.default_rng(7)
    urls = rng.integers(0, 200, 5000).astype(np.uint64)
    docs = rng.integers(0, 16, 5000).astype(np.uint64)
    eager = run_chain(make_mesh(8), 0, kernel, urls, docs.astype(np.int64))
    fused = run_chain(make_mesh(8), 1, kernel, urls, docs.astype(np.int64))
    assert eager == fused


@pytest.mark.parametrize("ndev", [None, 4])
def test_spill_breaks_fusion_still_correct(tmp_path, ndev):
    """outofcore=1 is a fusion boundary: the chain replays eagerly
    (spilled frames stream the external path) and output matches."""
    keys, vals = intcount_keys(5000)
    comm = make_mesh(ndev) if ndev else None
    eager = run_chain(comm, 0, count, keys, vals, outofcore=1,
                      memsize=1, maxpage=1, fpath=str(tmp_path))
    fused = run_chain(make_mesh(ndev) if ndev else None, 1, count, keys,
                      vals, outofcore=1, memsize=1, maxpage=1,
                      fpath=str(tmp_path))
    assert eager == fused
    assert all(not g["fused"] for g in plan_history()[-1]["groups"])


def test_host_callback_reduce_is_barrier():
    """A python reduce callback never defers — it flushes the recorded
    [aggregate, convert] prefix (which fuses) and runs eagerly, so its
    side effects stay ordered."""
    keys, vals = intcount_keys(500)
    seen = []

    def pysum(key, values, kv, ptr):
        seen.append(key)
        kv.add(key, sum(values))

    def run(fuse):
        seen.clear()
        mr = MapReduce(make_mesh(4), fuse=fuse)
        mr.map(1, _filler(keys, vals))
        mr.aggregate()
        mr.convert()
        mr.reduce(pysum)
        n = len(seen)           # side effect visible immediately
        return n, scan_pairs(mr)

    assert run(0) == run(1)
    kinds = [g["kind"] for g in plan_history()[-1]["groups"]]
    assert kinds == ["exchange"]   # collate fused; reduce never recorded


def test_ptr_reduce_is_barrier():
    """reduce(f, ptr=other_mr) writes into ANOTHER object (the sssp
    shape): it must execute in issue order, not at some later flush."""
    keys, vals = intcount_keys(300, card=11)
    mr = MapReduce(make_mesh(4), fuse=1)
    mr.map(1, _filler(keys, vals))
    other = MapReduce(make_mesh(4))
    other.open()

    def emit(key, values, kv, ptr):
        ptr.kv.add(key, len(values))
        kv.add(key, len(values))

    mr.aggregate()
    mr.convert()
    mr.reduce(emit, ptr=other)
    assert other.close() == 11      # side effect landed before close


def test_sort_stage_replays_eagerly():
    keys, vals = intcount_keys(800)

    def run(fuse):
        mr = MapReduce(make_mesh(4), fuse=fuse)
        mr.map(1, _filler(keys, vals))
        mr.aggregate()
        mr.convert()
        mr.reduce(count, batch=True)
        mr.sort_values(-1)
        return scan_pairs(mr)

    assert run(0) == run(1)


def test_p1_mesh_local_fusion():
    """P==1 mesh: aggregate early-outs eagerly (sharding the frame),
    then [convert, reduce] fuses into ONE local program."""
    keys, vals = intcount_keys(1000, card=31)
    eager = run_chain(make_mesh(1), 0, sum_values, keys, vals)
    fused = run_chain(make_mesh(1), 1, sum_values, keys, vals)
    assert eager == fused
    kinds = [g["kind"] for g in plan_history()[-1]["groups"]]
    assert "local" in kinds


# ---------------------------------------------------------------------------
# recorder semantics
# ---------------------------------------------------------------------------

def test_pipeline_context_manager_and_pending_count():
    keys, vals = intcount_keys(600)
    mr = MapReduce(make_mesh(4))
    mr.map(1, _filler(keys, vals))
    with mr.pipeline():
        na = mr.aggregate()
        nc = mr.convert()
        nr = mr.reduce(count, batch=True)
        # still recorded — nothing executed yet
        assert mr._plan is not None and len(mr._plan.stages) == 3
    # exit flushed; PendingCounts resolve to the real counts
    assert na == len(keys)
    assert int(nc) == 97 and nr == 97
    assert f"{nr}" == "97"
    assert nr + 1 == 98 and nr > 0


def test_discarded_pending_count_raises():
    """A PendingCount whose stage was discarded by an aborted pipeline()
    must raise when resolved — a silent 0 would look like a real count
    for an op that never ran."""
    from gpu_mapreduce_tpu import MRError
    keys, vals = intcount_keys(200, card=7)
    mr = MapReduce(make_mesh(4))
    mr.map(1, _filler(keys, vals))
    with pytest.raises(ValueError, match="user bug"):
        with mr.pipeline():
            n = mr.aggregate()
            raise ValueError("user bug")
    with pytest.raises(MRError, match="discarded"):
        int(n)


def test_pipeline_adopts_pending_auto_stages():
    """fuse=1 defers an aggregate; a pipeline() block entered afterwards
    must adopt it so stages execute in issue order (not convert/reduce
    against un-aggregated shards)."""
    keys, vals = intcount_keys(2000, card=97)
    eager = run_chain(make_mesh(4), 0, count, keys, vals)

    mr = MapReduce(make_mesh(4), fuse=1)
    mr.map(1, _filler(keys, vals))
    mr.aggregate()                      # deferred into the auto recorder
    with mr.pipeline():
        mr.convert()
        mr.reduce(count, batch=True)
    n = int(mr.kv_stats(0)[0])
    assert (n, scan_pairs(mr)) == eager


def test_kv_read_is_a_barrier():
    """Direct mr.kv/mr.kmv reads (apps, oink commands poke these) flush
    the pending plan — no stale/None state under fuse=1."""
    keys, vals = intcount_keys(400, card=13)
    mr = MapReduce(make_mesh(4), fuse=1)
    mr.map(1, _filler(keys, vals))
    mr.aggregate()
    mr.convert()
    assert mr._plan is not None and mr._plan.stages
    assert mr.kmv is not None           # property read flushed the plan
    assert mr._plan is None or not mr._plan.stages


def test_pending_count_coercion_is_a_barrier():
    """Reading a deferred count mid-chain flushes the recorded prefix."""
    keys, vals = intcount_keys(400)
    mr = MapReduce(make_mesh(4), fuse=1)
    mr.map(1, _filler(keys, vals))
    n = mr.aggregate()
    assert mr._plan is not None
    assert int(n) == len(keys)      # coercion flushed the plan
    assert mr._plan is None         # auto recorder uninstalled


def test_fuse_dispatch_reduction():
    """The acceptance headline: the fused chain launches fewer compiled
    programs than the eager chain."""
    keys, vals = intcount_keys(2048, card=257)

    def dispatches(fuse):
        mr = MapReduce(make_mesh(4), fuse=fuse)
        mr.map(1, _filler(keys, vals))
        c0 = global_counters().snapshot()["ndispatch"]
        mr.aggregate()
        mr.convert()
        int(mr.reduce(count, batch=True))
        return global_counters().snapshot()["ndispatch"] - c0

    eager, fused = dispatches(0), dispatches(1)
    assert fused < eager, (fused, eager)


def test_fused_output_compacts_to_eager_size():
    """Duplicate-heavy keys: the fused chain's resident KV must not stay
    sized at row capacity — it compacts to the eager tier's
    round_cap(max groups) shapes."""
    n, card = 20000, 37
    keys = ((np.arange(n, dtype=np.uint64) * 7919) % card)
    vals = np.ones(n, np.int64)

    def run(fuse):
        mr = MapReduce(make_mesh(4), fuse=fuse)
        mr.map(1, _filler(keys, vals))
        mr.aggregate()
        mr.convert()
        int(mr.reduce(count, batch=True))
        fr = mr.kv.one_frame()
        return fr.key.shape[0], scan_pairs(mr)

    (esize, epairs), (fsize, fpairs) = run(0), run(1)
    assert epairs == fpairs
    assert fsize == esize          # not ~20000 rows for 37 groups


def test_set_fuse_off_flushes_auto_recorder():
    keys, vals = intcount_keys(300, card=9)
    mr = MapReduce(make_mesh(4), fuse=1)
    mr.map(1, _filler(keys, vals))
    mr.aggregate()
    assert mr._plan is not None and mr._plan.stages
    mr.set(fuse=0)
    assert mr._plan is None        # flushed + uninstalled
    n = mr.convert()               # eager again: a real int
    assert isinstance(n, int)


def test_kv_assignment_flushes_pending_plan():
    """mr.kv = ... replaces the dataset; pending deferred ops were
    issued against the OLD one and must run first (eager order)."""
    keys, vals = intcount_keys(400, card=13)
    mr = MapReduce(make_mesh(4), fuse=1)
    mr.map(1, _filler(keys, vals))
    na = mr.aggregate()
    mr.kv = mr._new_kv()           # barrier: aggregate ran on old data
    assert int(na) == 400


def test_pipeline_exception_discards_tail():
    """An exception inside pipeline() aborts the un-flushed tail — the
    user's exception surfaces, not a replay error's."""
    keys, vals = intcount_keys(200, card=7)
    mr = MapReduce(make_mesh(4))
    mr.map(1, _filler(keys, vals))
    with pytest.raises(ValueError, match="user bug"):
        with mr.pipeline():
            mr.aggregate()
            raise ValueError("user bug")
    # dataset untouched by the discarded stage; eager ops still work
    mr.aggregate()
    mr.convert()
    assert int(mr.reduce(count, batch=True)) == 7


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_hit_and_span_attr():
    keys, vals = intcount_keys(512, card=41)

    def run():
        mr = MapReduce(make_mesh(4), fuse=1)
        mr.map(1, _filler(keys, vals))
        mr.aggregate()
        mr.convert()
        mr.reduce(count, batch=True)
        return scan_pairs(mr), mr

    from gpu_mapreduce_tpu.obs import get_tracer
    tracer = get_tracer().enable()
    try:
        tracer.clear()
        before = plan_cache().stats()
        first, _ = run()
        second, mr = run()
        assert first == second
        after = mr.stats()["plan"]["plan"]
        assert after["hits"] >= before["hits"] + 1
        evs = [e for e in tracer.events() if e["name"] == "plan.execute"]
        assert evs, "plan.execute spans missing"
        assert any(e["args"].get("cache_hit") for e in evs)
        assert any(not e["args"].get("cache_hit") for e in evs)
    finally:
        tracer.disable()


def test_unhashable_hash_fn_runs_uncached():
    """An unhashable callable stage arg can't key the plan cache — the
    plan must still execute (uncached), not crash at flush."""
    class WeirdHash:
        __hash__ = None                       # unhashable
        host_hash = True                      # host tier → eager replay

        def __call__(self, keys):
            return [int.from_bytes(k, "little") % 4 for k in keys]
    keys, vals = intcount_keys(200, card=9)
    eager = run_chain(make_mesh(4), 0, count, keys, vals)
    mr = MapReduce(make_mesh(4), fuse=1)
    mr.map(1, _filler(keys, vals))
    mr.aggregate(WeirdHash())
    mr.convert()
    n = int(mr.reduce(count, batch=True))
    assert (n, scan_pairs(mr)) == eager


def test_pending_count_division_and_stats_barrier():
    keys, vals = intcount_keys(500, card=25)
    mr = MapReduce(make_mesh(4), fuse=1)
    mr.map(1, _filler(keys, vals))
    mr.aggregate()
    mr.convert()
    n = mr.reduce(count, batch=True)
    assert n / 5 == 5.0 and n // 7 == 3 and n % 7 == 4
    assert -n == -25 and abs(n) == 25 and divmod(n, 7) == (3, 4)
    # stats() is a barrier: counters include the pending chain
    mr2 = MapReduce(make_mesh(4), fuse=1)
    mr2.map(1, _filler(keys, vals))
    mr2.aggregate()
    mr2.convert()
    mr2.reduce(count, batch=True)
    assert mr2._plan is not None and mr2._plan.stages
    s = mr2.stats()
    assert mr2._plan is None or not mr2._plan.stages
    assert s["cssize"] > 0          # the exchange actually ran


def test_plan_cache_eviction():
    cache = plan_cache()
    old = cache.maxsize
    cache.resize(1)
    try:
        ev0 = cache.stats()["evictions"]
        for card in (11, 13, 17):    # distinct shapes → distinct keys
            keys, vals = intcount_keys(256, card=card)
            run_chain(make_mesh(4), 1, count, keys, vals)
        st = cache.stats()
        assert st["size"] <= 1
        assert st["evictions"] > ev0
    finally:
        cache.resize(old)


def test_shuffle_jit_caches_bounded():
    """The phase1/phase2 executable caches evict past maxsize instead of
    growing without limit (ISSUE 2 satellite)."""
    from gpu_mapreduce_tpu.parallel import shuffle
    old = shuffle.PHASE2_CACHE.maxsize
    shuffle.PHASE2_CACHE.resize(2)
    try:
        ev0 = shuffle.PHASE2_CACHE.stats()["evictions"]
        for n in (64, 256, 1024, 4096):
            keys = (np.arange(n, dtype=np.uint64) * 31) % 7
            run_chain(make_mesh(4), 0, count, keys,
                      np.ones(n, np.int64))
        st = shuffle.PHASE2_CACHE.stats()
        assert st["size"] <= 2
        assert st["evictions"] > ev0
    finally:
        shuffle.PHASE2_CACHE.resize(old)


# ---------------------------------------------------------------------------
# per-call exchange stats (ISSUE 2 satellite)
# ---------------------------------------------------------------------------

def test_exchange_call_stats_per_object():
    """Two MapReduce objects keep their OWN exchange telemetry."""
    k1, v1 = intcount_keys(512, card=7)
    k2, v2 = intcount_keys(2048, card=300)
    mr1 = MapReduce(make_mesh(4))
    mr1.map(1, _filler(k1, v1))
    mr1.aggregate()
    mr2 = MapReduce(make_mesh(4))
    mr2.map(1, _filler(k2, v2))
    mr2.aggregate()
    s1, s2 = mr1.last_exchange, mr2.last_exchange
    assert s1 is not None and s2 is not None
    assert s1.rows == 512 and s2.rows == 2048     # not clobbered
    # the stats object also rides the sharded frame itself
    fr = mr2.kv.one_frame()
    assert getattr(fr, "exchange_stats", None) is s2
    # and mr1's still reads its own exchange after mr2's ran
    assert mr1.last_exchange is s1
    assert mr1.kv.one_frame().exchange_stats is s1


def test_fused_chain_sets_last_exchange():
    keys, vals = intcount_keys(1024, card=19)
    mr = MapReduce(make_mesh(4), fuse=1)
    mr.map(1, _filler(keys, vals))
    mr.aggregate()
    mr.convert()
    int(mr.reduce(count, batch=True))
    assert mr.last_exchange is not None
    assert mr.last_exchange.rows == 1024


# ---------------------------------------------------------------------------
# dump_plan / plan_dump
# ---------------------------------------------------------------------------

def test_dump_plan_command(tmp_path):
    from gpu_mapreduce_tpu.oink.command import run_command
    keys, vals = intcount_keys(128, card=5)
    run_chain(make_mesh(4), 1, count, keys, vals)   # ensure history
    out = tmp_path / "plan.txt"
    cmd = run_command("dump_plan", [str(out)])
    text = out.read_text()
    assert "plan " in text and "group" in text
    assert "aggregate" in text
    cmd2 = run_command("dump_plan", ["-"], screen=False)
    assert "aggregate" in cmd2.result_msg


def test_oink_script_set_fuse(tmp_path):
    """`set fuse 1` in an OINK script: the wordfreq command runs its
    collate/reduce through the plan path with identical results."""
    import io
    from gpu_mapreduce_tpu.oink import OinkScript
    data = tmp_path / "data.txt"
    data.write_bytes(TEXT1 + TEXT2)

    def run(fuse):
        out = io.StringIO()
        s = OinkScript(screen=out)
        s.run_string(f"set fuse {fuse}\n"
                     f"wordfreq 5 -i {data} -o NULL NULL\n")
        return [ln for ln in out.getvalue().splitlines()
                if ln.strip() and not ln.startswith("WordFreq:")]

    assert run(0) == run(1)

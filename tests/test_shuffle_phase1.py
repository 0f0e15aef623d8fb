"""Phase 1 of the shuffle is one payload sort by destination (ISSUE 33).

The rows ride a stable sort keyed by the destination, and what is
counted or ranged per destination is a reduction over the ``P``
destinations.  The form this replaced — an ``argsort`` of the
destinations, a ``take`` of keys and of values by it, ``bincount``'s
scatter-add for the counts and a scatter-min and a scatter-max a column
for the wire codec's ranges — is kept HERE, as the oracle: the new body
must equal it byte for byte, and its lowered text must hold none of its
operations.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu.ops import sort as sortops
from gpu_mapreduce_tpu.parallel import shuffle, wire
from gpu_mapreduce_tpu.parallel.mesh import make_mesh, row_spec


# ---------------------------------------------------------------------------
# the oracle: phase 1 as it stood until PR 33 (scatter form)
# ---------------------------------------------------------------------------

def oracle_bucket_stats(nprocs, key, value, dest, k_elig, v_elig):
    def minmax(col):
        w = wire._widen(col)
        info = jnp.iinfo(w.dtype)
        mn = jnp.full((nprocs,), info.max, w.dtype).at[dest].min(
            w, mode="drop")
        mx = jnp.full((nprocs,), info.min, w.dtype).at[dest].max(
            w, mode="drop")
        return wire._bits64(mn), wire._bits64(mx)

    zero = jnp.zeros((nprocs,), jnp.uint64)
    kmn, kmx = minmax(key) if k_elig else (zero, zero)
    vmn, vmx = minmax(value) if v_elig else (zero, zero)
    return jnp.stack([kmn, kmx, vmn, vmx], axis=1)


def oracle_phase1_body(nprocs, dest_of, wire_elig, k, v, c):
    cap = k.shape[0]
    valid = jnp.arange(cap) < c
    dest = jnp.where(valid, dest_of(k).astype(jnp.int32), nprocs)
    order = jnp.argsort(dest, stable=True)
    sk = jnp.take(k, order, axis=0)
    sv = jnp.take(v, order, axis=0)
    cl = jnp.bincount(dest, length=nprocs + 1)[:nprocs].astype(jnp.int32)
    if wire_elig is None:
        return sk, sv, cl, None
    return sk, sv, cl, oracle_bucket_stats(nprocs, k, v, dest, *wire_elig)


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------

CAP = 32
WIDE = sortops.RIDE_WORDS + 4       # a row wide enough to go by index

# (id, key (dtype, width), value (dtype, width)): the cells' column
# types first (word count, graph build, InvertedIndex)
COLUMNS = [
    ("u64_u8", ("u8", None), ("u1", None)),
    ("u64x2_u8", ("u8", 2), ("u1", None)),
    ("u64_u32", ("u8", None), ("u4", None)),
    ("u32x3_u64", ("u4", 3), ("u8", None)),
    ("u64_i64neg", ("u8", None), ("i8", None)),
    ("i64neg_u64x2", ("i8", None), ("u8", 2)),
    ("u64_f64", ("u8", None), ("f8", None)),
    ("wide_i64neg", ("u4", WIDE), ("i8", None)),
    ("u64x3_u64x2", ("u8", 3), ("u8", 2)),      # the value does not fit
]


@pytest.fixture(scope="module", params=[4, 8], ids=["P4", "P8"])
def mesh(request):
    return make_mesh(request.param)


def _column(rng, n, dtype, width):
    """Nonzero everywhere, the padding rows too, so a leak shows; signed
    columns straddle zero and floats are no whole numbers."""
    shape = (n,) if width is None else (n, width)
    dt = np.dtype(dtype)
    if dt.kind == "f":
        return rng.uniform(-9.0, 9.0, shape).astype(dt) + 0.125
    hi = 200 if dt.itemsize == 1 else 1 << 20
    col = rng.integers(1, hi, shape).astype(dt)
    return col - 100 if dt.kind == "i" else col


def _hash_dest(keys, P):
    return np.asarray(shuffle.default_hash(jnp.asarray(keys))) % P


def _keys_for(rng, counts, P, kdt, kw, allowed):
    """``[P * CAP]`` keys whose hash sends every valid row of every
    shard to a destination in ``allowed`` (padding rows anywhere)."""
    pool = _column(rng, 64 * CAP * P, kdt, kw)
    ok = pool[np.isin(_hash_dest(pool, P), allowed)]
    k = _column(rng, P * CAP, kdt, kw)
    at = 0
    for i, n in enumerate(counts):
        k[i * CAP:i * CAP + n] = ok[at:at + n]
        at += n
    return k


def _counts(rng, P):
    """An empty shard, a shard full to ``cap``, one row, the rest
    anything."""
    c = rng.integers(1, CAP, P)
    c[0], c[1], c[2] = 0, CAP, 1
    return c.astype(np.int32)


def _range_dest(counts, P, empty):
    """Reshard's spec: the rows in global order dealt to the targets in
    uneven runs, the targets in ``empty`` getting none."""
    total = int(counts.sum())
    offsets = np.concatenate([[0], np.cumsum(counts)[:-1]])
    live = [d for d in range(P) if d not in empty]
    cuts = np.sort(np.random.default_rng(7).integers(
        0, total + 1, len(live) - 1))
    sizes = np.diff(np.concatenate([[0], cuts, [total]]))
    ends, at = [], 0
    for d in range(P):
        if d in live:
            at += int(sizes[live.index(d)])
        ends.append(at)
    return ("range", tuple(int(o) for o in offsets), tuple(ends))


def _on_mesh(mesh, body, elig):
    """``body``'s outputs over the mesh: three, and the stats where the
    wire codec is on."""
    spec = row_spec(mesh)
    nouts = 3 if elig is None else 4
    return jax.jit(jax.shard_map(
        lambda k, v, c: body(k, v, c)[:nouts], mesh=mesh,
        in_specs=(spec,) * 3, out_specs=(spec,) * nouts))


# every destination; all rows to one; two destinations with no row
PATTERNS = ["spread", "one_dest", "missing_dests"]


@pytest.mark.parametrize("wire_on", [True, False], ids=["wire", "raw"])
@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("kind", ["hash", "fixed", "range"])
@pytest.mark.parametrize("cols", COLUMNS, ids=[c[0] for c in COLUMNS])
def test_phase1_equals_the_scatter_form(mesh, cols, kind, pattern, wire_on):
    _, (kdt, kw), (vdt, vw) = cols
    P = int(mesh.devices.size)
    rng = np.random.default_rng(33)
    counts = _counts(rng, P)
    allowed = {"spread": list(range(P)), "one_dest": [P - 2],
               "missing_dests": [d for d in range(P) if d not in (0, P - 1)]
               }[pattern]
    if kind == "hash":
        dest = ("hash", None)
        k = _keys_for(rng, counts, P, kdt, kw, allowed)
    else:
        k = _column(rng, P * CAP, kdt, kw)
        if kind == "fixed":
            # shard i to shard i % n: n = 1 is every row to one
            # destination, n = 2 leaves P - 2 destinations with no row
            dest = ("fixed_mod", {"spread": P, "one_dest": 1,
                                  "missing_dests": 2}[pattern])
        else:
            dest = _range_dest(counts, P,
                               [d for d in range(P) if d not in allowed])
    v = _column(rng, P * CAP, vdt, vw)
    elig = wire.columns_eligible(k, v) if wire_on else None
    dest_of = shuffle._dest_fn(dest, P, mesh)
    new = _on_mesh(mesh, lambda *a: shuffle.phase1_shard_body(
        P, dest_of, elig, *a), elig)
    old = _on_mesh(mesh, lambda *a: oracle_phase1_body(
        P, dest_of, elig, *a), elig)
    got, want = new(k, v, counts), old(k, v, counts)
    assert len(got) == len(want) == (4 if wire_on else 3)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
    # counts and stats whole (the empty buckets' sentinels with them);
    # rows on every valid row of every shard
    for g, w in zip(got[2:], want[2:]):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    cl = np.asarray(got[2]).reshape(P, P)
    assert cl.sum(axis=1).tolist() == counts.tolist()
    if kind == "hash":
        assert not np.delete(cl, allowed, axis=1).any()
    if pattern != "spread":
        assert (cl.sum(axis=0) == 0).any()
    for g, w in zip(got[:2], want[:2]):
        g = np.asarray(g).reshape((P, CAP) + g.shape[1:])
        w = np.asarray(w).reshape((P, CAP) + w.shape[1:])
        for i, n in enumerate(counts):
            assert np.array_equal(g[i, :n], w[i, :n])


# ---------------------------------------------------------------------------
# the mechanism, pinned in the lowered text
# ---------------------------------------------------------------------------

def _ops(text):
    return re.findall(r'stablehlo\.(scatter|gather|while|sort)"?\(', text)


def _sds(P, cap, dtype, width=None):
    shape = (P * cap,) if width is None else (P * cap, width)
    return jax.ShapeDtypeStruct(shape, dtype)


# the cells' column types: wordfreq-zipf-4chip, graph-build-4chip,
# invindex-puma-4chip
CELL_COLUMNS = [("word", ("u8", None), ("u1", None)),
                ("graph", ("u8", 2), ("u1", None)),
                ("invindex", ("u8", None), ("u4", None))]


@pytest.mark.parametrize("dest", [("hash", None), ("fixed_mod", 1),
                                  ("range", (0, 40, 80, 120),
                                   (10, 10, 150, 160))],
                         ids=["hash", "fixed", "range"])
@pytest.mark.parametrize("wire_on", [True, False], ids=["wire", "raw"])
@pytest.mark.parametrize("cols", CELL_COLUMNS,
                         ids=[c[0] for c in CELL_COLUMNS])
def test_phase1_lowers_to_one_sort(cols, wire_on, dest):
    from gpu_mapreduce_tpu.obs import names
    mesh = make_mesh(4)
    _, (kdt, kw), (vdt, vw) = cols
    k, v = _sds(4, 64, kdt, kw), _sds(4, 64, vdt, vw)
    elig = wire.columns_eligible(k, v) if wire_on else None
    text = shuffle._phase1_build(mesh, dest, False, elig).lower(
        k, v, jax.ShapeDtypeStruct((4,), jnp.int32)).as_text()
    assert re.search(r"module @(\w+)", text).group(1) == \
        names.SHUFFLE_PHASE1
    assert _ops(text) == ["sort"]


@pytest.mark.parametrize("cols,taken", [
    (("u64_f64", ("u8", None), ("f8", None)), 1),
    (("wide_u8", ("u4", WIDE), ("u1", None)), 1),
    (("u64x3_u64x2", ("u8", 3), ("u8", 2)), 1),
    (("wide_f64", ("u4", WIDE), ("f8", None)), 2)],
    ids=lambda c: c[0] if isinstance(c, tuple) else None)
def test_what_cannot_ride_goes_by_index(cols, taken):
    """The same program either way: one sort, no scatter, no ``while``;
    a column that cannot ride costs one ``take`` by the sorted row
    index, and the row index is one more operand of the sort."""
    mesh = make_mesh(4)
    _, (kdt, kw), (vdt, vw) = cols
    k, v = _sds(4, 64, kdt, kw), _sds(4, 64, vdt, vw)
    assert sortops.riding([k, v]).count(False) == taken
    text = shuffle._phase1_build(
        mesh, ("hash", None), False, wire.columns_eligible(k, v)).lower(
        k, v, jax.ShapeDtypeStruct((4,), jnp.int32)).as_text()
    assert sorted(_ops(text)) == ["gather"] * taken + ["sort"]


def test_the_scatter_form_would_be_caught():
    """The check can fail: the oracle's program holds what it forbids."""
    mesh = make_mesh(4)
    dest_of = shuffle._dest_fn(("hash", None), 4, mesh)
    k, v = _sds(4, 64, "u8"), _sds(4, 64, "u1")
    old = _on_mesh(mesh, lambda *a: oracle_phase1_body(
        4, dest_of, (True, False), *a), (True, False))
    ops = _ops(old.lower(k, v, jax.ShapeDtypeStruct((4,), jnp.int32)
                         ).as_text())
    assert ops.count("scatter") >= 3 and ops.count("gather") >= 2
    assert "sort" in ops


def test_the_exchange_span_says_which_form_ran():
    """``shuffle.exchange`` counts the columns that rode the sort and
    those taken by index, from the same rule the program used."""
    from gpu_mapreduce_tpu.core.column import DenseColumn
    from gpu_mapreduce_tpu.core.frame import KVFrame
    from gpu_mapreduce_tpu.obs import get_tracer
    from gpu_mapreduce_tpu.parallel.sharded import shard_frame
    mesh = make_mesh(4)
    rng = np.random.default_rng(5)
    tr = get_tracer()
    tr.reset()
    tr.enable()
    try:
        for vdt, want in (("u1", (2, 0)), ("f8", (1, 1))):
            skv = shard_frame(KVFrame(
                DenseColumn(_column(rng, 100, "u8", None)),
                DenseColumn(_column(rng, 100, vdt, None))), mesh)
            out = shuffle.exchange(skv, ("hash", None))
            assert int(np.sum(out.counts)) == 100
            ev = [e for e in tr.events()
                  if e["name"] == "shuffle.exchange"][-1]
            assert (ev["args"]["cols_rode"],
                    ev["args"]["cols_by_index"]) == want
    finally:
        tr.reset()

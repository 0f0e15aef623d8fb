"""Phase 2 of the shuffle moves runs, not rows (ISSUE 31).

After phase 1 every destination's rows are one contiguous run of the
dest-sorted shard and the packed output is the sources' runs one after
another, so the send block is ``P`` windows and the receive side ``P``
window updates a round.  The form this replaced — a ``searchsorted``
over all ``cap`` rows, a ``take`` and a scatter of all ``cap`` rows to
build the send block, a scatter of every received slot into the output,
and the same per row again to encode and decode the wire codec — is
kept HERE, as the oracle: the new bodies must equal it byte for byte,
and their lowered text must hold none of its operations.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gpu_mapreduce_tpu.parallel import shuffle, wire
from gpu_mapreduce_tpu.parallel.mesh import (make_mesh, make_mesh2,
                                             mesh_axis_size, row_spec)


# ---------------------------------------------------------------------------
# the oracle: phase 2 as it stood until PR 31 (scatter form)
# ---------------------------------------------------------------------------

def oracle_send_window(nprocs, B, start, rows, counts_local):
    cap = rows.shape[0]
    cum = jnp.cumsum(counts_local)
    r = jnp.arange(cap)
    d = jnp.searchsorted(cum, r, side="right").astype(jnp.int32)
    off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                           cum[:-1].astype(jnp.int32)])
    q0 = r - jnp.take(off, jnp.minimum(d, nprocs - 1))
    in_window = (q0 >= start) & (q0 < start + B)
    q = jnp.where(in_window, q0 - start, B)
    send = jnp.zeros((nprocs, B) + rows.shape[1:], rows.dtype)
    return send.at[d, q].set(rows, mode="drop")


def oracle_place(out, recv, base, counts_from, start):
    """The receive scatter: slot q of source j to base[j] + start + q,
    slots past counts_from[j] pushed out of range and dropped."""
    cap_out, B = out.shape[0], recv.shape[1]
    q_global = start + jnp.arange(B, dtype=jnp.int32)[None, :]
    pos = jnp.where(q_global < counts_from[:, None],
                    base[:, None] + q_global, cap_out)
    return out.at[pos.reshape(-1)].set(
        recv.reshape((-1,) + out.shape[1:]), mode="drop")


def _oracle_base(counts_from):
    cum = jnp.cumsum(counts_from)
    return cum, jnp.concatenate([jnp.zeros(1, jnp.int32),
                                 cum[:-1].astype(jnp.int32)])


def oracle_phase2_body(nprocs, mesh, B, nrounds, cap_out, k, v, cl):
    counts_from = shuffle._exchange_counts(cl, mesh)
    _, base = _oracle_base(counts_from)
    out_k = jnp.zeros((cap_out,) + k.shape[1:], k.dtype)
    out_v = jnp.zeros((cap_out,) + v.shape[1:], v.dtype)
    for r in range(nrounds):
        recv_k = shuffle._exchange_blocks(
            oracle_send_window(nprocs, B, r * B, k, cl), mesh)
        recv_v = shuffle._exchange_blocks(
            oracle_send_window(nprocs, B, r * B, v, cl), mesh)
        out_k = oracle_place(out_k, recv_k, base, counts_from, r * B)
        out_v = oracle_place(out_v, recv_v, base, counts_from, r * B)
    return out_k, out_v, jnp.sum(counts_from)


def oracle_phase2_wire_body(nprocs, mesh, tiers, cap_out, kpack, vpack,
                            k, v, cl, stats):
    def encode(col, base_bits, dest, pack):
        base = wire._base_in(base_bits, col.dtype)
        return (col - jnp.take(base, dest)).astype(jnp.dtype(pack))

    def decode(packed, base_bits, src, valid, dtype):
        base = wire._base_in(base_bits, dtype)
        full = jnp.take(base, src) + packed.astype(dtype)
        return jnp.where(valid, full, jnp.zeros((), dtype))

    meta_local = jnp.stack([cl.astype(jnp.uint64), stats[:, 0],
                            stats[:, 2]], axis=1)
    meta_from = shuffle._exchange_blocks(meta_local[:, None, :],
                                         mesh)[:, 0, :]
    counts_from = meta_from[:, 0].astype(jnp.int32)
    cap = k.shape[0]
    denc = jnp.minimum(
        jnp.searchsorted(jnp.cumsum(cl), jnp.arange(cap), side="right"),
        nprocs - 1).astype(jnp.int32)
    ke = encode(k, stats[:, 0], denc, kpack) if kpack else k
    ve = encode(v, stats[:, 2], denc, vpack) if vpack else v
    cumf, base = _oracle_base(counts_from)
    out_k = jnp.zeros((cap_out,) + ke.shape[1:], ke.dtype)
    out_v = jnp.zeros((cap_out,) + ve.shape[1:], ve.dtype)
    start = 0
    for B in tiers:
        recv_k = shuffle._exchange_blocks(
            oracle_send_window(nprocs, B, start, ke, cl), mesh)
        recv_v = shuffle._exchange_blocks(
            oracle_send_window(nprocs, B, start, ve, cl), mesh)
        out_k = oracle_place(out_k, recv_k, base, counts_from, start)
        out_v = oracle_place(out_v, recv_v, base, counts_from, start)
        start += B
    nrecv = jnp.sum(counts_from)
    if kpack or vpack:
        idx = jnp.arange(cap_out)
        src = jnp.minimum(jnp.searchsorted(cumf, idx, side="right"),
                          nprocs - 1).astype(jnp.int32)
        valid = idx < nrecv
        if kpack:
            out_k = decode(out_k, meta_from[:, 1], src, valid, k.dtype)
        if vpack:
            out_v = decode(out_v, meta_from[:, 2], src, valid, v.dtype)
    return out_k, out_v, nrecv


# ---------------------------------------------------------------------------
# inputs: dest-sorted shards from a count matrix
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    return make_mesh(4)


# the one choice the exchange makes, from the mesh: one all_to_all on a
# one-axis mesh, one an axis (shuffle._a2a_hier) on a (slice, chip) mesh
MESH_SHAPES = {"flat": lambda: make_mesh(4), "2x2": lambda: make_mesh2(2, 2)}


def _column(rng, cap, dtype, width, lo=1, hi=200):
    """Nonzero everywhere — the padding rows past sum(counts) too, so a
    row that leaks from there shows."""
    shape = (cap,) if width is None else (cap, width)
    return rng.integers(lo, hi, shape).astype(dtype)


def _shards(rng, counts_mat, cap, kdt, kw, vdt, vw):
    P = counts_mat.shape[0]
    assert counts_mat.sum(axis=1).max() <= cap
    k = np.concatenate([_column(rng, cap, kdt, kw) for _ in range(P)])
    v = np.concatenate([_column(rng, cap, vdt, vw) for _ in range(P)])
    return k, v, counts_mat.astype(np.int32).reshape(-1)


def _on_mesh(mesh, body, nin):
    spec = row_spec(mesh)

    def shard(*a):
        out_k, out_v, nrecv = body(*a)
        return out_k, out_v, nrecv[None]
    return jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=(spec,) * nin,
                                 out_specs=(spec,) * 3))


def _same(got, want):
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)


def _mat(*rows):
    return np.asarray(rows, np.int64)


EVEN = _mat([3, 5, 2, 4], [4, 4, 4, 4], [1, 0, 7, 2], [6, 2, 2, 3])
# columns 1 and 3 receive nothing, shard 2 sends nothing
EMPTY_DESTS = _mat([5, 0, 6, 0], [9, 0, 1, 0], [0, 0, 0, 0], [2, 0, 3, 0])
ONE_DEST = _mat([0, 0, 13, 0], [0, 0, 9, 0], [0, 0, 16, 0], [0, 0, 1, 0])
SKEWED = _mat([11, 1, 0, 2], [2, 3, 1, 0], [9, 0, 4, 1], [10, 2, 1, 3])
# shard rows sum to cap = 16: the last destination's window starts
# inside the last B rows of cap in every round
FULL_CAP = _mat([5, 4, 1, 6], [0, 0, 0, 16], [4, 4, 4, 4], [1, 2, 3, 10])
# destination 3 receives exactly cap_out = 32 rows: its last source's
# block ends inside the last B rows of cap_out
FULL_OUT = _mat([1, 2, 3, 9], [4, 0, 2, 8], [2, 1, 5, 8], [0, 3, 1, 7])

# (id, counts, cap, B, nrounds, cap_out, key (dtype, width), value)
RAW_CASES = [
    ("even_1d", EVEN, 16, 8, 1, 32, ("u8", None), ("u4", None)),
    ("even_n1", EVEN, 16, 8, 1, 32, ("u8", 1), ("i8", None)),
    ("even_n2", EVEN, 16, 8, 1, 32, ("u8", 2), ("u1", None)),
    ("value_n2", EVEN, 16, 8, 1, 32, ("u4", None), ("u8", 2)),
    ("empty_dests", EMPTY_DESTS, 16, 8, 2, 32, ("u8", 2), ("u1", None)),
    ("one_dest", ONE_DEST, 16, 8, 2, 64, ("i8", None), ("u4", 1)),
    ("several_rounds", SKEWED, 16, 4, 3, 32, ("u8", 2), ("u1", None)),
    ("window_in_last_B_of_cap", FULL_CAP, 16, 8, 2, 64,
     ("u8", None), ("u1", None)),
    ("block_ends_in_last_B_of_cap_out", FULL_OUT, 16, 4, 3, 32,
     ("u8", 2), ("u4", None)),
    ("cap_out_below_cap", EVEN // 2, 32, 8, 1, 8, ("u8", None),
     ("u1", None)),
    ("cap_out_above_cap", ONE_DEST, 16, 16, 1, 64, ("u8", 1),
     ("i8", None)),
    ("count_zero", EVEN * 0, 16, 8, 1, 8, ("u8", 2), ("u1", None)),
    ("B_above_cap", EVEN, 16, 32, 1, 32, ("u4", None), ("u8", None)),
]
# the wire body: (.., tiers, .., kpack, vpack); a pack needs a 1-D
# integer column wider than a byte (wire.col_eligible)
WIRE_CASES = [
    ("ladder", SKEWED, 16, (8, 4), 32, ("u8", None), ("u4", None),
     None, None),
    ("ladder_n2", FULL_CAP, 16, (8, 4, 4), 64, ("u8", 2), ("u1", None),
     None, None),
    ("pack_key", SKEWED, 16, (8, 4), 32, ("u8", None), ("u1", None),
     "uint8", None),
    ("pack_both", FULL_OUT, 16, (4, 4, 2), 32, ("u8", None),
     ("u4", None), "uint16", "uint8"),
    ("pack_signed", EVEN, 16, (8,), 32, ("i8", None), ("i8", None),
     "uint8", "uint16"),
    ("pack_empty_dests", EMPTY_DESTS, 16, (8, 2), 32, ("u4", None),
     ("i8", None), "uint8", "uint8"),
    ("pack_one_dest_cap_out_above", ONE_DEST, 16, (8, 8), 64,
     ("u8", None), ("u8", None), "uint32", "uint8"),
    ("pack_count_zero", EVEN * 0, 16, (8,), 8, ("u8", None),
     ("u4", None), "uint8", "uint8"),
    ("pack_cap_out_below_cap", EVEN // 2, 32, (8,), 8, ("u8", None),
     ("i8", None), "uint8", "uint8"),
]


@pytest.mark.parametrize("mesh_shape", list(MESH_SHAPES))
@pytest.mark.parametrize("case", RAW_CASES, ids=[c[0] for c in RAW_CASES])
def test_phase2_equals_the_scatter_form(case, mesh_shape):
    mesh = MESH_SHAPES[mesh_shape]()
    _, counts, cap, B, nrounds, cap_out, (kdt, kw), (vdt, vw) = case
    assert B * nrounds >= counts.max() and cap_out >= counts.sum(0).max()
    P = mesh_axis_size(mesh)
    k, v, cl = _shards(np.random.default_rng(31), counts, cap, kdt, kw,
                       vdt, vw)
    args = (P, mesh, B, nrounds, cap_out)
    new = _on_mesh(mesh, lambda *a: shuffle.phase2_shard_body(*args, *a), 3)
    old = _on_mesh(mesh, lambda *a: oracle_phase2_body(*args, *a), 3)
    got, want = new(k, v, cl), old(k, v, cl)
    _same(got, want)
    nrecv = counts.sum(0)
    assert np.asarray(got[2]).tolist() == nrecv.tolist()
    gk = np.asarray(got[0]).reshape(P, cap_out, -1)
    for d in range(P):          # packed: no zero row before nrecv
        assert gk[d, :nrecv[d]].any(axis=1).all()
        assert not gk[d, nrecv[d]:].any()


def _bucket_bases(col, counts_mat, cap, signed):
    """The [P*P, 4]-shaped stats phase 1 would hand the codec: each
    bucket's minimum (columns 0 and 2 are read), as uint64 bits."""
    P = counts_mat.shape[0]
    mins = np.zeros((P, P), np.int64 if signed else np.uint64)
    for i in range(P):
        off = 0
        for d in range(P):
            n = counts_mat[i, d]
            if n:
                mins[i, d] = col[i * cap + off:i * cap + off + n].min()
            off += n
    return mins.view(np.uint64)


@pytest.mark.parametrize("mesh_shape", list(MESH_SHAPES))
@pytest.mark.parametrize("case", WIRE_CASES,
                         ids=[c[0] for c in WIRE_CASES])
def test_phase2_wire_equals_the_scatter_form(case, mesh_shape):
    mesh = MESH_SHAPES[mesh_shape]()
    (_, counts, cap, tiers, cap_out, (kdt, kw), (vdt, vw), kpack,
     vpack) = case
    assert sum(tiers) >= counts.max() and cap_out >= counts.sum(0).max()
    P = mesh_axis_size(mesh)
    rng = np.random.default_rng(32)
    k, v, cl = _shards(rng, counts, cap, kdt, kw, vdt, vw)
    if np.dtype(kdt).kind == "i":       # bases below zero, deltas < 200
        k = k - 100
    if np.dtype(vdt).kind == "i":
        v = v - 100
    stats = np.zeros((P * P, 4), np.uint64)
    if kw is None:
        stats[:, 0] = _bucket_bases(k, counts, cap,
                                    np.dtype(kdt).kind == "i").reshape(-1)
    if vw is None:
        stats[:, 2] = _bucket_bases(v, counts, cap,
                                    np.dtype(vdt).kind == "i").reshape(-1)
    args = (P, mesh, tiers, cap_out, kpack, vpack)
    new = _on_mesh(mesh,
                   lambda *a: wire.phase2_wire_shard_body(*args, *a), 4)
    old = _on_mesh(mesh, lambda *a: oracle_phase2_wire_body(*args, *a), 4)
    _same(new(k, v, cl, stats), old(k, v, cl, stats))


@pytest.mark.parametrize("dtype,width", [
    ("u1", None), ("u4", None), ("u8", None), ("i8", None),
    ("u8", 1), ("u8", 2), ("u4", 0)])
@pytest.mark.parametrize("counts,cap", [
    ([10, 1, 0, 2], 16), ([0, 0, 16, 0], 16), ([4, 4, 4, 4], 16),
    ([0, 0, 0, 0], 16), ([1, 2, 3, 10], 16), ([7, 0, 0, 1], 8)])
def test_send_window_equals_the_scatter_form(dtype, width, counts, cap):
    """Every window of every round, starts past the largest bucket and
    past ``cap`` included: a window that runs past the end reads zeros,
    never clamped rows."""
    rng = np.random.default_rng(33)
    rows = jnp.asarray(_column(rng, cap, dtype, width))
    cl = jnp.asarray(counts, jnp.int32)
    for B in (4, 8, 32):
        for start in (0, 3, B, 2 * B, cap - 1, cap + B):
            got = shuffle._build_send_window(4, B, start, rows, cl)
            want = oracle_send_window(4, B, start, rows, cl)
            _same([got], [want])


# ---------------------------------------------------------------------------
# the mechanism, pinned in the lowered text
# ---------------------------------------------------------------------------

def _bad_ops(text: str, P: int) -> list:
    """Operations phase 2 must not hold: any scatter, sort or while, and
    any gather whose index operand has more than ``P`` rows."""
    bad = re.findall(r"stablehlo\.(scatter|sort|while)\b", text)
    gathers = re.findall(
        r'stablehlo\.gather"?\(.*?\) .*?:\s*\(tensor<[^>]*>, '
        r'tensor<([^>]*)>\)', text, re.S)
    assert len(gathers) == len(re.findall(r'stablehlo\.gather"?\(', text))
    for index_type in gathers:
        if int(re.match(r"(\d+)x", index_type + "x").group(1)) > P:
            bad.append("gather[%s]" % index_type)
    return bad


def _sds(P, cap, dtype, width=None):
    shape = (P * cap,) if width is None else (P * cap, width)
    return jax.ShapeDtypeStruct(shape, dtype)


# (cap, B, tiers, cap_out): B * nrounds for the raw body, the ladder
# for the wire body
SHAPES = [(64, 16, (16, 8), 128), (256, 64, (64, 64, 32), 64)]


@pytest.mark.parametrize("cap,B,tiers,cap_out", SHAPES)
@pytest.mark.parametrize("form", ["raw", "wire", "wire_packs",
                                  "fused_raw", "fused_wire", "mega"])
def test_phase2_lowers_to_windows(mesh, form, cap, B, tiers, cap_out):
    from gpu_mapreduce_tpu.obs import names
    from gpu_mapreduce_tpu.plan import fuser
    P = mesh_axis_size(mesh)
    u64, u8, i32 = jnp.uint64, jnp.uint8, jnp.int32
    key2, key1, val = _sds(P, cap, u64, 2), _sds(P, cap, u64), \
        _sds(P, cap, u8)
    cl = jax.ShapeDtypeStruct((P * P,), i32)
    st = jax.ShapeDtypeStruct((P * P, 4), u64)
    raw_plan = ("raw", B, len(tiers), cap_out)
    wire_plan = ("wire", tiers, cap_out, "uint16", None)
    want_name = None
    if form == "raw":
        text = shuffle._phase2_build(mesh, B, len(tiers), cap_out
                                     ).lower(key2, val, cl).as_text()
        want_name = names.SHUFFLE_PHASE2
    elif form == "wire":
        text = shuffle._phase2_wire_build(
            mesh, tiers, cap_out, None, None
        ).lower(key2, val, cl, st).as_text()
        want_name = names.SHUFFLE_PHASE2_WIRE
    elif form == "wire_packs":
        text = shuffle._phase2_wire_build(
            mesh, tiers, cap_out, "uint16", "uint8"
        ).lower(key1, _sds(P, cap, jnp.int64), cl, st).as_text()
        want_name = names.SHUFFLE_PHASE2_WIRE
    else:
        # the fuser's compositions: phase 2 + the group step (a sort of
        # its own) in one program — so only the exchange's part of the
        # text is held to the rule: everything up to the first sort
        if form == "fused_raw":
            text = fuser._fused_exchange_build(
                mesh, raw_plan, "kmv", None).lower(
                key1, val, cl).as_text()
        elif form == "fused_wire":
            text = fuser._fused_exchange_build(
                mesh, wire_plan, "kmv", None).lower(
                key1, val, cl, st).as_text()
        else:
            text = fuser._mega_build(
                mesh, ("fixed_mod", P), wire_plan, cap_out, "kmv",
                None, (True, False)).lower(
                key1, val, jax.ShapeDtypeStruct((P,), i32)).as_text()
            # phase 1 (its argsort, its takes, the stats' scatter-min)
            # comes first: the exchange starts at its first collective
            text = text[text.index("all_to_all"):]
        text = text[:text.index("stablehlo.sort")]
    if want_name:
        assert re.search(r"module @(\w+)", text).group(1) == want_name
    assert "dynamic_slice" in text and "dynamic_update_slice" in text
    assert "all_to_all" in text
    assert _bad_ops(text, P) == []


def test_the_scatter_form_would_be_caught(mesh):
    """The check can fail: the oracle's program holds what it forbids."""
    P = mesh_axis_size(mesh)
    spec = row_spec(mesh)
    cap, B, cap_out = 64, 16, 128
    old = jax.jit(jax.shard_map(
        lambda k, v, cl: oracle_phase2_body(P, mesh, B, 2, cap_out,
                                            k, v, cl)[:2],
        mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 2))
    text = old.lower(_sds(P, cap, jnp.uint64, 2), _sds(P, cap, jnp.uint8),
                     jax.ShapeDtypeStruct((P * P,), jnp.int32)).as_text()
    bad = _bad_ops(text, P)
    assert "scatter" in bad and "while" in bad
    assert any(b.startswith("gather[") for b in bad)

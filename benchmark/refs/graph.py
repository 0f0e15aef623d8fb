"""Plain numpy references for MR-MPI's graph commands, and the checks that
hold an OINK script job to them.

The references (``components_reference``, ``pagerank_reference``) and the
edge checks are copied from ``chip_smoke.py`` (PR 22).  A check is a
function ``check_x(env)``; a traffic file names the ones its job needs as
``"refs.graph:check_x"``.  ``env`` carries the script object, the job's
output directory, the messages the commands printed, the configuration and
a ``memo`` dict in which earlier checks leave what later ones need (the
edge list pulled to the host, the reference's upper edges).

The graph comes from the system's own ``rmat`` (a device generator no
independent program can replay), so the references are functions of the
edge list the system made: the checks first hold that list to what R-MAT
promises (exact count, ids in range, no duplicate), then compute everything
else from it independently.  The slow references (components, PageRank) are
kept in the benchmark's cache keyed by a digest of the edges.
"""

import glob
import hashlib
import os
import re

import numpy as np

from benchmark import check


# -- references ---------------------------------------------------------------

def components_reference(e: np.ndarray, verts: np.ndarray) -> np.ndarray:
    """Label of every vertex in ``verts`` = smallest vertex id of its
    component: numpy min-label hooking with pointer jumping."""
    src = np.searchsorted(verts, e[:, 0])
    dst = np.searchsorted(verts, e[:, 1])
    label = np.arange(len(verts))
    while True:
        low = np.minimum(label[src], label[dst])
        new = label.copy()
        np.minimum.at(new, src, low)
        np.minimum.at(new, dst, low)
        new = new[new]
        if np.array_equal(new, label):
            return verts[label]
        label = new


def pagerank_reference(e: np.ndarray, verts: np.ndarray, iters: int,
                       damping: float = 0.85) -> tuple:
    """``iters`` steps of float64 power iteration from the uniform vector,
    dangling mass spread uniformly; returns (ranks, the largest rank change
    of each step) — the command's stopping rule reads the latter."""
    n = len(verts)
    src = np.searchsorted(verts, e[:, 0])
    dst = np.searchsorted(verts, e[:, 1])
    deg = np.bincount(src, minlength=n).astype(np.float64)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    r, deltas = np.full(n, 1.0 / n), []
    for _ in range(iters):
        inflow = np.bincount(dst, weights=(r * inv)[src], minlength=n)
        dangling = r[deg == 0].sum() / n
        r2 = (1.0 - damping) / n + damping * (inflow + dangling)
        deltas.append(float(np.abs(r2 - r).max()))
        r = r2
    return r, deltas


# -- helpers ------------------------------------------------------------------

def mr_edges(mr) -> np.ndarray:
    """Every key row of a named MR object, pulled to the host."""
    from gpu_mapreduce_tpu.oink.kernels import kv_keys
    rows = []
    mr.scan_kv(lambda fr, p: rows.append(kv_keys(fr)), batch=True)
    return np.concatenate(rows).astype(np.uint64)


def pack(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """(a, b) vertex pairs as one u64 each (ids are below 2**32, checked by
    the caller) — numpy sorts those far faster than rows."""
    return (a << np.uint64(32)) | b


def read_pairs(prefix: str, dtype) -> tuple:
    """'key value' lines of an -o output: one file, or one per shard."""
    files = sorted(glob.glob(prefix + "*"))
    check(files, f"no output at {prefix}")
    rows = [np.loadtxt(f, dtype=dtype, ndmin=2) for f in files]
    rows = np.concatenate([r for r in rows if len(r)])
    order = np.argsort(rows[:, 0], kind="stable")
    return rows[order, 0], rows[order, 1]


def _edges(env) -> np.ndarray:
    if "edges" not in env.memo:
        env.memo["edges"] = mr_edges(env.script.obj.get_mr("mre"))
    return env.memo["edges"]


def _packed(env) -> np.ndarray:
    """The system's edges packed, sorted, duplicates merged."""
    if "packed" not in env.memo:
        e = _edges(env)
        env.memo["packed"] = np.unique(pack(e[:, 0], e[:, 1]))
    return env.memo["packed"]


def _upper(env) -> np.ndarray:
    """The reference's upper-edge set of the system's edge list, packed and
    sorted: self loops out, (min, max) per edge, duplicates merged."""
    if "upper" not in env.memo:
        e = _edges(env)
        keep = e[:, 0] != e[:, 1]
        env.memo["upper"] = np.unique(pack(e[keep].min(1), e[keep].max(1)))
    return env.memo["upper"]


def _edges_key(env, what: str) -> str:
    """Cache key of a reference computed from this edge list."""
    if "edges_digest" not in env.memo:
        env.memo["edges_digest"] = hashlib.sha256(
            _packed(env).tobytes()).hexdigest()
    with open(__file__, "rb") as f:
        src = hashlib.sha256(f.read()).hexdigest()
    return f"{what}-{env.memo['edges_digest'][:24]}-{src[:12]}"


# -- checks -------------------------------------------------------------------

def check_edges(env) -> dict:
    """``rmat``: exactly 2^scale * edge_factor edges, ids in range, unique."""
    scale, factor = env.config["scale"], env.config["edge_factor"]
    e = _edges(env)
    nedges = (1 << scale) * factor
    check(e.shape == (nedges, 2), f"rmat made {e.shape} edges")
    check(int(e.max()) < (1 << scale) <= (1 << 32),
          "rmat vertex id out of range")
    check(len(_packed(env)) == nedges, "rmat edges are not unique")
    return {"edges": nedges}


def check_upper(env) -> dict:
    """``edge_upper``: exactly the reference's upper-edge set."""
    upper = mr_edges(env.script.obj.get_mr("mru"))
    want = _upper(env)
    check(np.array_equal(np.sort(pack(upper[:, 0], upper[:, 1])), want),
          f"edge_upper: {len(upper)} edges, reference {len(want)}")
    return {"upper_edges": len(want)}


def check_cc(env) -> dict:
    """``cc_find``: the exact min-label components of the upper edges."""
    packed = _upper(env)
    want_upper = np.stack([packed >> np.uint64(32),
                           packed & np.uint64(0xFFFFFFFF)], 1)
    uverts = np.unique(want_upper)
    key = _edges_key(env, "cc")
    ref = env.cache.load(key)
    if ref is None:
        ref = {"zone": components_reference(want_upper, uverts)}
        env.cache.store(key, ref)
    want_zone = ref["zone"]
    cc_v, cc_zone = read_pairs(os.path.join(env.out, "cc"), np.uint64)
    check(np.array_equal(cc_v, uverts), "cc_find: vertex set differs")
    ncc = len(np.unique(want_zone))
    check(np.array_equal(cc_zone, want_zone),
          f"cc_find: labels differ from the reference ({ncc} components)")
    check(any(f"CC_find: {ncc} components" in m for m in env.messages),
          f"cc_find did not report {ncc} components: {env.messages}")
    return {"components": ncc}


def check_pagerank(env) -> dict:
    """``pagerank``: within L1 1e-5 of a float64 power iteration under the
    same stopping rule.  The reference takes the steps the command reported,
    and its own rank changes must agree that this was the step to stop at
    (2 % slack: the command iterates in float32)."""
    tol = float(env.config["pagerank_tolerance"])
    damping = float(env.config["pagerank_damping"])
    e = _edges(env)
    verts = np.unique(e)
    pr_v, pr = read_pairs(os.path.join(env.out, "pr"), np.float64)
    check(np.array_equal(pr_v.astype(np.uint64), verts),
          "pagerank: vertex set differs")
    found = [re.search(r"PageRank: .* (\d+) iterations", m)
             for m in env.messages]
    iters = [int(m.group(1)) for m in found if m]
    check(len(iters) == 1 and 0 < iters[0] < int(env.config["pagerank_maxiter"]),
          f"pagerank did not report its iterations: {env.messages}")
    key = _edges_key(env, f"pr{iters[0]}-{tol:g}-{damping:g}")
    ref = env.cache.load(key)
    if ref is None:
        want_pr, deltas = pagerank_reference(e, verts, iters[0], damping)
        ref = {"ranks": want_pr, "deltas": np.asarray(deltas)}
        env.cache.store(key, ref)
    want_pr, deltas = ref["ranks"], ref["deltas"].tolist()
    check(deltas[-1] <= tol * 1.02
          and all(d > tol * 0.98 for d in deltas[:-1]),
          f"pagerank stopped after {iters[0]} iterations; the reference's "
          f"rank changes were {deltas}")
    check(bool(np.all(np.isfinite(pr))), "pagerank: non-finite rank")
    l1 = float(np.abs(pr - want_pr).sum())
    check(l1 < 1e-5, f"pagerank: L1 error {l1:.3g} against the reference")
    return {"pagerank_iterations": iters[0], "pagerank_l1": l1}

"""R-MAT graph generation — vectorised on device.

The reference generates edges one at a time with drand48 in a serial map
callback (``oink/map_rmat_generate.cpp:14-67``): per edge, ``nlevels``
recursive quadrant choices with probabilities (a,b,c,d), optionally
perturbed per level by ``fraction`` noise and renormalised.

TPU-first: one ``lax.scan`` over levels, each level drawing a uniform per
*edge* (a [m] vector op), building vertex ids MSB-first by shifting bits
in — the batch equivalent of the reference's delta-halving walk.  Noise,
when enabled, perturbs per-edge per-level probability vectors exactly like
the reference's serial walk (a [m,4] op).  `jax.random` (threefry) replaces
drand48 — bit-identity with the reference is not a goal (SURVEY.md §7);
determinism under our own seeds is.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


@functools.partial(jax.jit, static_argnames=("m", "nlevels", "noisy"))
def rmat_edges(key, m: int, nlevels: int, abcd, frac: float, noisy: bool
               ) -> Tuple[jax.Array, jax.Array]:
    """Generate m R-MAT edges in a 2^nlevels-vertex graph.

    Returns (vi[m], vj[m]) uint64.  ``abcd`` is a length-4 array of
    quadrant probabilities; ``noisy`` statically gates the per-level
    fraction perturbation (frac == 0 ⇒ pass noisy=False)."""
    with jax.named_scope("generate"):
        abcd = jnp.asarray(abcd, jnp.float32)
        probs0 = jnp.broadcast_to(abcd, (m, 4)) if noisy else abcd[None, :]

        def level(carry, lkey):
            i, j, probs = carry
            ku, kn = jax.random.split(lkey)
            u = jax.random.uniform(ku, (m,), jnp.float32)
            t = jnp.cumsum(probs, axis=1)          # [*,4]: a, a+b, a+b+c, 1
            t = jnp.broadcast_to(t, (m, 4))
            # quadrant: 0=a (i0,j0)  1=b (j1)  2=c (i1)  3=d (i1,j1)
            jbit = ((u >= t[:, 0]) & (u < t[:, 1])) | (u >= t[:, 2])
            ibit = u >= t[:, 1]
            i = (i << np.uint64(1)) | ibit.astype(jnp.uint64)
            j = (j << np.uint64(1)) | jbit.astype(jnp.uint64)
            if noisy:
                nz = jax.random.uniform(kn, (m, 4), jnp.float32,
                                        minval=-0.5, maxval=0.5)
                probs = probs * (1.0 + frac * nz)
                probs = probs / jnp.sum(probs, axis=1, keepdims=True)
            return (i, j, probs), None

        zeros = jnp.zeros((m,), jnp.uint64)
        keys = jax.random.split(key, nlevels)
        (vi, vj, _), _ = lax.scan(level, (zeros, zeros, probs0), keys)
        return vi, vj


@jax.jit
def rmat_edge_rows(vi, vj):
    """One generated batch as KV rows, where the columns are: the [m, 2]
    edge keys and their NULL (one zero byte) values — what the ``rmat``
    commands add to a dataset that lives on a mesh."""
    with jax.named_scope("rows"):
        return jnp.stack([vi, vj], axis=1), jnp.zeros(vi.shape, jnp.uint8)


def generate_unique(seed: int, nlevels: int, nnonzero: int,
                    abcd=(0.25, 0.25, 0.25, 0.25), frac: float = 0.0,
                    add_edges=None) -> Tuple[np.ndarray, int]:
    """Host driver: regenerate until 2^nlevels * nnonzero unique edges exist
    (the reference RMAT command's cull loop, ``oink/rmat.cpp:46-60``) —
    used directly by tests; the OINK command runs the same loop through the
    MapReduce algebra instead.  Returns (edges [n,2] uint64, iterations)."""
    order = 1 << nlevels
    ntotal = order * nnonzero
    root = jax.random.PRNGKey(seed)
    niterate = 0
    # ONE generation shape for every round: a per-round pow2 of the
    # remaining need meant a fresh XLA compile per round (~7 compiles —
    # 20-40s each on real TPU); the full-size batch trimmed to `need`
    # keeps the exact reference semantics with a single compile
    m = max(8, 1 << (ntotal - 1).bit_length())
    # dedupe on packed u64 keys (vi<<nlevels | vj): scalar np.unique is
    # several times faster than 2-column row unique, and vertex ids
    # always fit — nlevels ≤ 32 means 2*nlevels ≤ 64 bits
    assert nlevels <= 32, "RMAT scale above 32 exceeds the u64 edge key"
    shift = np.uint64(nlevels)
    mask = np.uint64(order - 1)
    # first-come acceptance over the WHOLE m-candidate batch each round
    # (the reference accepts the first ntotal unique edges in generation
    # order, oink/rmat.cpp:46-60; trimming candidates to the remainder
    # wasted most of each batch and took ~2-3x the rounds)
    accepted: list = []
    naccepted = 0
    sorted_seen = np.zeros(0, np.uint64)
    while naccepted < ntotal:
        niterate += 1
        root, sub = jax.random.split(root)
        vi, vj = rmat_edges(sub, m, nlevels, jnp.asarray(abcd), frac,
                            noisy=frac > 0.0)
        keys = (np.asarray(vi) << shift) | np.asarray(vj)
        # first occurrence of each key within the batch, in batch order
        uniq, first_idx = np.unique(keys, return_index=True)
        if len(sorted_seen):
            pos = np.searchsorted(sorted_seen, uniq)
            pos = np.minimum(pos, len(sorted_seen) - 1)
            fresh_mask = sorted_seen[pos] != uniq
            uniq, first_idx = uniq[fresh_mask], first_idx[fresh_mask]
        take = uniq[np.argsort(first_idx)][: ntotal - naccepted]
        accepted.append(take)
        naccepted += len(take)
        sorted_seen = np.sort(np.concatenate([sorted_seen, take]))
        if add_edges is not None:
            add_edges(np.stack([take >> shift, take & mask], 1))
    seen_keys = np.sort(np.concatenate(accepted))
    seen = np.stack([seen_keys >> shift, seen_keys & mask], 1)
    return seen, niterate

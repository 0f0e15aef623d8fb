"""What the job modules need from a result: wait for a mesh-resident one,
hold it to the mesh (the assertions of ``chip_smoke.py``, PR 22), take a
small order-independent checksum of it on the device, and hash the files a
job wrote."""

import functools

import numpy as np

from benchmark import check


def hash_file(h, path: str) -> None:
    """Feed a file's bytes to the hash object ``h``."""
    with open(path, "rb") as f:
        while chunk := f.read(1 << 22):
            h.update(chunk)


def frames(mr) -> list:
    out = []
    for ds in (mr.kv, mr.kmv):
        if ds is not None:
            out.extend(ds.frames())
    return out


def block(mr) -> None:
    """Return when every device array the MR object holds is ready."""
    import jax
    for fr in frames(mr):
        for name in ("key", "value"):
            a = getattr(fr, name, None)
            if isinstance(a, jax.Array):
                a.block_until_ready()


def check_spread(name: str, frame, ndev: int) -> None:
    """A mesh-resident KV frame must really live on every device."""
    from gpu_mapreduce_tpu.parallel.sharded import ShardedKV
    check(isinstance(frame, ShardedKV),
          f"{name}: result is {type(frame).__name__}, not mesh-resident")
    for a in (frame.key, frame.value):
        check(len(a.sharding.device_set) == ndev,
              f"{name}: array spans {len(a.sharding.device_set)} of "
              f"{ndev} devices")
    check(len(frame.counts) == ndev and int(frame.counts.min()) > 0,
          f"{name}: per-shard rows {frame.counts.tolist()}")


def check_exchange(name: str, mr) -> dict:
    ex = mr.last_exchange
    check(ex is not None and ex.rows > 0 and ex.sent_bytes > 0,
          f"{name}: the exchange moved nothing ({ex})")
    return {"rows": int(ex.rows), "sent_bytes": int(ex.sent_bytes),
            "rounds": int(ex.nrounds)}


@functools.lru_cache(maxsize=None)
def _checksum_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def checksum(key, counts):
        nshards = counts.shape[0]
        cap = key.shape[0] // nshards
        row = jnp.arange(key.shape[0])
        valid = (row % cap) < counts[row // cap]
        k = key.reshape(key.shape[0], -1).astype(jnp.uint64)
        h = jnp.zeros(key.shape[0], jnp.uint64)
        for j in range(k.shape[1]):     # a 64-bit mix per row (splitmix64)
            h = (h ^ k[:, j]) + jnp.uint64(0x9E3779B97F4A7C15)
            h = (h ^ (h >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
            h = (h ^ (h >> 27)) * jnp.uint64(0x94D049BB133111EB)
            h = h ^ (h >> 31)
        return jnp.sum(jnp.where(valid, h, jnp.uint64(0)))
    return checksum


def key_checksum(frame):
    """A device scalar: the wrapping sum of a 64-bit mix of every valid key
    row of a ``ShardedKV`` frame — the same rows in any order and any
    padding give the same sum.  Dispatched, not waited for."""
    return _checksum_fn()(frame.key, np.asarray(frame.counts, np.int64))

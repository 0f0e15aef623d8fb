"""Record the small four-chip trace that holds ``readers/collective_seconds``
to a real profile (``data/tiny_mesh.xplane.pb``).

Run on a four-chip host, from the checkout root:
``python3 benchmark/tests/record_collective_trace.py <outdir>``.  Two
annotated "jobs", each one execution of the package's own sharded
connected-components loop (``jit_cc_loop``: a ``while`` with one ``pmin`` a
round over a 2^16-vertex label vector) and one of a program outside the
names the metric reads (``jit_other_psum``: one ``psum`` of the same size),
with host work between them.  Prints what the reader makes of it for both
sets of names; the test asserts those relations, not the seconds.  The
file kept beside the tests was made this way in PR 46.
"""

import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

N, ROWS = 1 << 16, 1 << 18


def main(outdir: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import xtrace
    from benchmark.readers import collective_seconds as reader
    from gpu_mapreduce_tpu.models.cc import _cc_sharded_fn
    from gpu_mapreduce_tpu.parallel.mesh import (make_mesh, mesh_axes,
                                                 row_spec)

    mesh = make_mesh(devices=jax.devices()[:4])
    shard = NamedSharding(mesh, row_spec(mesh))
    rng = np.random.default_rng(46)
    # a ring plus random chords: a few rounds to converge
    src = np.concatenate([np.arange(N), rng.integers(0, N, ROWS - N)])
    dst = np.concatenate([(np.arange(N) + 1) % N, rng.integers(0, N, ROWS - N)])
    src_d = jax.device_put(src.astype(np.int32), shard)
    dst_d = jax.device_put(dst.astype(np.int32), shard)
    valid = jax.device_put(np.ones(ROWS, bool), shard)
    loop = _cc_sharded_fn(mesh, N, N)

    @jax.jit
    def other_psum(x):
        return jax.shard_map(lambda v: lax.psum(v, mesh_axes(mesh)),
                             mesh=mesh, in_specs=row_spec(mesh),
                             out_specs=P())(x)

    x = jax.device_put(np.arange(4 * N, dtype=np.float32), shard)
    labels, iters = loop(src_d, dst_d, valid)
    jax.block_until_ready((labels, other_psum(x)))
    print("rounds", int(iters), "components", len(np.unique(np.asarray(labels))))

    logdir = tempfile.mkdtemp(prefix="record_collective_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=options)
    for _ in range(2):
        with jax.profiler.TraceAnnotation(xtrace.JOB_SPAN):
            jax.block_until_ready(loop(src_d, dst_d, valid))
            time.sleep(0.002)
            jax.block_until_ready(other_psum(x))
        time.sleep(0.001)
    jax.profiler.stop_trace()
    path = xtrace.find_xplane(logdir)
    os.makedirs(outdir, exist_ok=True)
    dst_path = os.path.join(outdir, "tiny_mesh.xplane.pb")
    shutil.copy(path, dst_path)
    raw = xtrace.load(dst_path, {xtrace.JOB_SPAN})
    print("planes", raw["planes"])
    for dev, lines in sorted(raw["devices"].items()):
        names = sorted({xtrace.op_name(e[2]) for e in lines["ops"]})
        print("device", dev, "modules",
              sorted({xtrace.module_name(e[2]) for e in lines["modules"]}),
              "ops", names[:60])
    prefixes = ["all-reduce", "all-gather", "all-to-all",
                "collective-permute", "reduce-scatter"]
    for modules in (["jit_cc_loop"], ["jit_other_psum"],
                    ["jit_cc_loop", "jit_other_psum"], ["jit_absent"]):
        print(modules, reader.collective_seconds(raw, modules, prefixes))
    print("bytes", os.path.getsize(dst_path))
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

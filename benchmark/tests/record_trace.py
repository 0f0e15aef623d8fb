"""Record the small TPU trace the tests of the trace reduction read.

Run on the chip, from the checkout root:
``python3 benchmark/tests/record_trace.py <outdir>`` writes
``<outdir>/tiny_tpu.xplane.pb`` (three annotated "jobs", each a few device
programs with host work between them) and prints what the reduction makes
of it.  The file kept beside the tests was made this way in PR 23.
"""

import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(outdir: str) -> int:
    import jax
    import jax.numpy as jnp
    from benchmark import xtrace

    @jax.jit
    def extract(x):
        return jnp.cumsum(x * 3 + 1)

    @jax.jit
    def tail(x):
        return jnp.sort(x)[::2].sum()

    x = jnp.arange(1 << 20, dtype=jnp.int32)
    tail(extract(x)).block_until_ready()
    logdir = tempfile.mkdtemp(prefix="record_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=options)
    for _ in range(3):
        with jax.profiler.TraceAnnotation(xtrace.JOB_SPAN):
            with jax.profiler.TraceAnnotation("stage.map_device"):
                y = extract(x).block_until_ready()
            with jax.profiler.TraceAnnotation("stage.reduce"):
                time.sleep(0.002)               # host work, device idle
                tail(y).block_until_ready()
        time.sleep(0.001)
    jax.profiler.stop_trace()
    src = xtrace.find_xplane(logdir)
    os.makedirs(outdir, exist_ok=True)
    dst = os.path.join(outdir, "tiny_tpu.xplane.pb")
    shutil.copy(src, dst)
    raw = xtrace.load(dst)
    print("planes", raw["planes"])
    print(xtrace.reduce(raw, {"stage.map_device", "stage.reduce"}))
    print("bytes", os.path.getsize(dst))
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

"""gpu_mapreduce_tpu — a TPU-native MapReduce + graph-analytics framework.

A from-scratch re-design (not a port) of baoxuezhao/GPU-mapreduce —
Sandia's MapReduce-MPI library + OINK scripting + CUDA InvertedIndex —
built on JAX/XLA/Pallas: columnar sharded arrays instead of byte-packed
pages, mesh collectives instead of MPI, sort+segment ops instead of hash
tables, Pallas kernels instead of CUDA.  See SURVEY.md at the repo root for
the full reference analysis and design mapping.

Quick start (the reference's hello world, examples/wordfreq.cpp)::

    from gpu_mapreduce_tpu import MapReduce

    mr = MapReduce()
    mr.map_files(files, read_words_callback)
    mr.collate()
    mr.reduce(sum_counts_callback)
"""

import jax as _jax

# The reference is built around 64-bit keys/counters (MRMPI_BIGINT,
# src/mrtype.h:24; VERTEX=uint64, oink/typedefs.h:22).  JAX defaults to
# 32-bit; enable x64 so u64 graph keys survive device round-trips.  Hot
# kernels cast to u32 lanes internally where it matters.
_jax.config.update("jax_enable_x64", True)

# compiled programs persist between processes (utils/platform.py):
# JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache
from .utils.platform import enable_compile_cache as _enable_compile_cache

_enable_compile_cache()

from .core.mapreduce import MapReduce, SerialBackend
from .core.dataset import KeyValue, KeyMultiValue
from .core.frame import (BlockedMultivalue, KMVFrame, KVFrame,
                         iter_blocks)
from .core.column import BytesColumn, DenseColumn, as_column
from .core.runtime import MRError, Settings, global_counters
from . import ft                      # fault tolerance (ft.schedule,
#                                       ft.resume — doc/reliability.md)

__version__ = "0.1.0"

__all__ = [
    "BlockedMultivalue", "iter_blocks",
    "MapReduce", "SerialBackend", "KeyValue", "KeyMultiValue",
    "KVFrame", "KMVFrame", "BytesColumn", "DenseColumn", "as_column",
    "MRError", "Settings", "global_counters", "ft",
]

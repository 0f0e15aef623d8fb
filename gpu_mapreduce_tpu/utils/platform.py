"""Process-level JAX set-up shared by every entry point.

One thing lives here: where compiled programs are kept between
processes.  The package calls :func:`enable_compile_cache` once at
import, so the library, the OINK CLI, the examples, the serve daemon and
``chip_smoke.py`` all find what an earlier process compiled.
"""

import os

# <checkout>/.jax_cache — a fixed path resolved from this file (the
# directory is part of JAX's cache key handling: one that moves with a
# temp name, pid or time never hits)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Arm JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and no
    directory is set in code.  Unset: ``<checkout>/.jax_cache``.  Every
    program is kept whatever its compile time (JAX's default skips those
    under a second, and a cold process would compile them all again)
    unless the operator set that threshold in the environment too."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir

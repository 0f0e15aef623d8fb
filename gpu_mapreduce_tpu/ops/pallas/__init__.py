"""Purpose-built Pallas kernels (TPU twins of the CUDA hot paths).

Modules: :mod:`.match` (substring mark / compaction — the InvertedIndex
GPU kernels).

Kernel-launch accounting: every *eager* ``pallas_call`` invocation is a
compiled-program launch exactly like a jit dispatch, so it must land in
``Counters.ndispatch`` — otherwise "N dispatches per pipeline" could be
faked by moving work into uncounted kernels (doc/perf.md).  Call sites
route through :func:`note_kernel_launch`; launches traced *inside* an
enclosing jit program ride that program's dispatch count and are
skipped via the tracer check.
"""

from __future__ import annotations


def note_kernel_launch(*operands) -> None:
    """Count one eager ``pallas_call`` launch in ``Counters.ndispatch``.

    No-op when any operand is a tracer: the launch is then part of an
    enclosing jit program whose dispatch the caller already counted
    (``bump_dispatch`` at its call site), so counting here would
    double-bill the same executable."""
    import jax.core
    if any(isinstance(o, jax.core.Tracer) for o in operands):
        return
    from ...core.runtime import bump_dispatch
    bump_dispatch()

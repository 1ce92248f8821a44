"""Shared kernel plumbing.

All Pallas kernels in this package target TPU (BlockSpec VMEM tiling,
128-aligned MXU dims).  On non-TPU backends (the CPU test runs) they run in
``interpret=True`` mode, which executes the kernel body per grid step —
bit-exact semantics, no TPU required.  What interpret mode cannot check,
the TPU's tiling and VMEM rules, ``tests/test_chip_compile.py`` checks by
compiling each kernel for a described v5e.
"""

from __future__ import annotations

import functools

import jax


@functools.cache
def default_interpret() -> bool:
    """Interpret mode exactly when the default device is not a TPU — how the
    CPU tests run the kernels.  The entry points that run on a chip refuse
    a non-TPU platform before any kernel is traced (``chip_smoke.py``,
    ``repro.launch.runtime.require_backend``), so a JAX that fell back to
    the CPU is an error there, never an interpret-mode run."""
    return jax.devices()[0].platform != "tpu"


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


NEG_INF = -1e30

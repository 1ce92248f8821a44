"""Production mesh builders.

Importing this module never touches JAX device state; meshes are built
inside functions only (the dry-run sets the 512-device XLA flag before any
jax import, and smoke tests must keep seeing 1 device).
"""

from __future__ import annotations


def make_auto_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with Auto axis types (GSPMD sharding propagation)."""
    import jax

    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def make_production_mesh(*, multi_pod: bool = False, dp_tp=None):
    """16×16 = 256 chips per pod; 2 pods = 512 chips with a leading "pod"
    axis.  Axis types are Auto (GSPMD sharding propagation).

    ``dp_tp=(dp, tp)`` overrides the per-pod (data, model) split while
    keeping 256 chips/pod — the §Perf mesh-ratio knob (e.g. (64, 4) cuts the
    TP all-reduce wire ~4x for dense models; see EXPERIMENTS.md §Perf)."""
    import jax
    import numpy as np

    dp, tp = dp_tp if dp_tp is not None else (16, 16)
    assert dp * tp == 256, (dp, tp)
    shape = (2, dp, tp) if multi_pod else (dp, tp)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} "
            "(dryrun.py must set --xla_force_host_platform_device_count=512 "
            "before importing jax)"
        )
    return make_auto_mesh(shape, axes, devices=devices[:n])


def make_host_mesh(shape=None, axes=("data", "model")):
    """Small mesh over whatever devices exist — tests and examples."""
    import jax
    import numpy as np

    devices = jax.devices()
    if shape is None:
        shape = (1, len(devices))
    n = int(np.prod(shape))
    return make_auto_mesh(shape, axes, devices=devices[:n])

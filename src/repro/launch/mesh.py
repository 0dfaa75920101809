"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets the placeholder device count
before any jax initialization.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) single pod or (2,16,16) two pods — the graded target meshes.

    Works when the process exposes more devices than the mesh needs (the
    dry-run forces 512 host devices; the single-pod mesh takes the first 256).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import numpy as np
    need = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(f"mesh {shape} needs {need} devices, "
                           f"have {len(devs)} (set XLA_FLAGS host device count)")
    return make_mesh(shape, axes, devices=devs[:need])


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh (tests / local runs) with Auto axis types: GSPMD owns
    every axis a ``shard_map`` does not make manual."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_tp_mesh(tp: int):
    """Tensor-parallel serving mesh: ``tp`` devices on a single ``'tp'`` axis.

    Deliberately one-axis: the TP step builders wrap the model body in a
    shard_map manual over EVERY mesh axis, so the per-token partial sums
    run the schedule collectives (dptree / sptree / ring) on the 'tp' axis
    with no GSPMD axis left over. Replica parallelism composes at the
    process level (``serving.fleet``), not as a second axis here.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    devs = jax.devices()
    if len(devs) < tp:
        raise RuntimeError(f"tp={tp} needs {tp} devices, have {len(devs)} "
                           "(set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return make_mesh((tp,), ("tp",), devices=devs[:tp])


def make_local_mesh():
    """Whatever this process has (1 CPU device in the container)."""
    n = len(jax.devices())
    return make_mesh((1, n), ("data", "model")) if n == 1 else \
        make_mesh((n, 1), ("data", "model"))

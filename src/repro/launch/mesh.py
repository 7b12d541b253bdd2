"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state.  Single pod: 16×16 = 256 chips (data × model); multi-pod:
2×16×16 = 512 chips (pod × data × model).  ``model`` is the pipeline axis;
``data`` (and ``pod``) carry DP/FSDP; see DESIGN.md §4.
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """jax.make_mesh with every axis left to XLA's SPMD partitioner."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 2, model: int = 4):
    """Small mesh for CPU integration runs / tests."""
    return _auto_mesh((data, model), ("data", "model"))


def make_submesh(data: int, model: int, devices=None):
    """Mesh over an *explicit device subset* — the elastic engine's shrink
    path rebuilds the pipeline on the first ``data*model`` devices of the
    given (or process-global) device list, so released devices hold no
    state and can be handed back to the job manager.

    Uses jax.sharding.Mesh directly (jax.make_mesh offers no device subset
    on every supported jax version); Auto axis types are the default there.
    """
    import numpy as np
    devs = list(devices) if devices is not None else list(jax.devices())
    need = data * model
    if len(devs) < need:
        raise ValueError(
            f"submesh needs {need} devices (data={data} x model={model}), "
            f"have {len(devs)}")
    arr = np.array(devs[:need]).reshape(data, model)
    return jax.sharding.Mesh(arr, ("data", "model"))


def data_axes(mesh) -> tuple:
    """The DP axes of a mesh (everything except the pipeline axis)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def dp_degree(mesh) -> int:
    import numpy as np
    return int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))

"""Production mesh factory.

Single pod: 16×16 = 256 chips (data, model).
Multi-pod:  2×16×16 = 512 chips (pod, data, model) — the "pod" axis is
data-parallel by default and becomes the pipeline axis when pipeline
parallelism is enabled.

A FUNCTION, not a module constant: importing this module never touches
jax device state (device count is locked at first jax init, so the
dry-run driver must set XLA_FLAGS before any jax import — see dryrun.py).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def make_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the sharding rules and
    ``with_sharding_constraint`` calls in this repo leave propagation to
    GSPMD, which ``jax.make_mesh``'s default ``Explicit`` axes refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int | None = None):
    """Small mesh over whatever devices exist (tests / local runs)."""
    n = len(jax.devices())
    data = data or (n // model)
    return make_mesh((data, model), ("data", "model"))


def make_serving_mesh(model: int = 1, data: int = 1):
    """A (data, model) mesh over the *first* ``data * model`` devices —
    unlike :func:`make_host_mesh` it does not insist on consuming every
    device, so a serving engine can run a 2-way model mesh on an 8-device
    CI host (the spare devices stay idle).  ``model == data == 1`` still
    returns a real one-device mesh so the mesh-aware code path is
    exercised uniformly."""
    need = data * model
    devices = jax.devices()
    if need > len(devices):
        raise ValueError(
            f"serving mesh {data}x{model} needs {need} devices, have "
            f"{len(devices)} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} before the "
            "first jax import (launch/serve.py --mesh does this for you)")
    return Mesh(np.asarray(devices[:need]).reshape(data, model),
                ("data", "model"))

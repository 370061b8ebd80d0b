"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis is
outer data parallelism whose gradient all-reduce crosses DCI once/step.

Defined as functions (never module-level constants) so importing this
module cannot touch jax device state — smoke tests must keep seeing one
CPU device; only ``dryrun.py`` forces 512 host devices.
"""
from __future__ import annotations

import jax
import numpy as np


def _auto(n_axes: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_parallel: int = 1):
    """Small mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh(
        (n // model_parallel, model_parallel), ("data", "model"),
        axis_types=_auto(2))


def make_partition_mesh(n_parts: int, devices=None):
    """1D ``("parts",)`` mesh over ``n_parts`` devices — the axis the
    distributed graph subsystem (``repro.dist``) shards partitions along.
    Kept separate from the data/model training meshes: graph partitions
    are a *spatial* split of one sparse operator, not batch parallelism
    (a ``DistGraph`` can later be nested under an outer data axis by
    passing a submesh here via ``devices``).  The axis is Auto, matching
    the training meshes above."""
    devs = list(jax.devices()) if devices is None else list(devices)
    if n_parts > len(devs):
        raise ValueError(
            f"{n_parts} partitions need {n_parts} devices, have {len(devs)} "
            "(CPU: set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return jax.sharding.Mesh(np.asarray(devs[:n_parts]), ("parts",),
                             axis_types=_auto(1))


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh) -> int:
    return mesh.shape.get("model", 1)

"""Production mesh construction (functions only — importing this module must
never touch jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with GSPMD auto axes.  The partition rules in
    ``launch/sharding.py`` place arrays with ``device_put``/``out_shardings``
    and let the partitioner propagate everything else; explicit axes (the
    default since JAX 0.7) would instead type-check every op's sharding."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod: (data=16, model=16); two pods: (pod=2, data=16, model=16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CI-scale sharding tests (8 forced host devices)."""
    return _auto_mesh((n_data, n_model), ("data", "model"))


def make_serving_mesh(tp: int, devices=None):
    """One serving replica's mesh: ``(data=1, model=tp)`` over ``devices``
    (the replica's own chips; None = the first ``tp`` visible devices).

    Serving replicas are data-parallel ACROSS replicas (the router owns
    that axis), so within a replica only the model axis is real; the size-1
    data axis keeps every ``data_axes``-consuming rule in
    ``launch/sharding.py`` well-defined.  Requires ``tp`` visible devices
    (on CPU: ``--xla_force_host_platform_device_count``)."""
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if devices is None:
        avail = jax.local_device_count()
        if avail < tp:
            raise ValueError(
                f"--tp {tp} needs {tp} devices but only {avail} are visible; "
                f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{tp} (before the first jax import)")
        devices = jax.devices()[:tp]
    if len(devices) != tp:
        raise ValueError(f"a tp={tp} mesh needs {tp} devices, got "
                         f"{len(devices)}")
    return _auto_mesh((1, tp), ("data", "model"), devices=list(devices))


def data_axes(mesh) -> tuple:
    """The batch-sharding axes of a mesh (pod included when present)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)

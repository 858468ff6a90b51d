"""Production mesh construction.

``make_production_mesh`` is a function (never a module-level constant) so
importing this module touches no jax device state — required because the
dry-run must set XLA_FLAGS before any jax initialization.

Production topology: TPU v5e pods of 256 chips arranged (data=16,
model=16); the multi-pod mesh adds a leading pod axis (2 pods = 512
chips). DCN connects pods (the "pod" axis carries only data-parallel
gradient reduction); ICI carries the model axis.
"""

from __future__ import annotations

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_mesh", "make_serve_mesh",
           "carve_submeshes", "batch_axes"]


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (tests, elastic restarts, reduced smoke meshes)."""
    return _mesh(tuple(shape), tuple(axes))


def make_serve_mesh(model_parallel: int | None = None):
    """(data, model) serving mesh over every visible device.

    Args:
      model_parallel: size of the model (tensor-parallel) axis; default
        all devices (pure TP — the layout the serve rules expect for
        single-host serving). Must divide the device count; the remainder
        becomes the data axis.

    Returns:
      A ``("data", "model")`` mesh of shape
      ``(device_count // model_parallel, model_parallel)``.
    """
    n = jax.device_count()
    mp = model_parallel if model_parallel is not None else n
    if mp < 1 or n % mp:
        raise ValueError(f"model_parallel={mp} does not divide the "
                         f"{n} visible devices")
    return _mesh((n // mp, mp), ("data", "model"))


def carve_submeshes(replicas: int, *, model_parallel: int | None = None,
                    devices=None, exclude=()) -> list:
    """Partition the device set into ``replicas`` disjoint serving meshes.

    The replica-group serving driver (:mod:`repro.launch.replica`) runs
    one deterministic :class:`~repro.launch.serve.ServeEngine` per
    sub-mesh, so each sub-mesh must own its devices exclusively — no
    device appears in two sub-meshes, and every visible device is used.

    Args:
      replicas: number of sub-meshes R. Must divide the device count.
      model_parallel: model (tensor-parallel) axis size of each sub-mesh;
        default all of the replica's devices (pure TP, matching
        :func:`make_serve_mesh`). Must divide the per-replica device
        count; the remainder becomes the sub-mesh's data axis.
      devices: explicit device list to carve (default ``jax.devices()``).
        Devices are assigned to replicas in contiguous runs, so on real
        hardware neighbouring chips (fast ICI) land in the same replica.
      exclude: device ids to drop before carving — the fleet-restart
        path after a device failure (``repro.runtime.elastic``): carve
        the surviving set, leaving known-bad chips out. The post-
        exclusion count must still divide evenly.

    Returns:
      A list of R ``("data", "model")`` meshes with pairwise-disjoint
      device sets, each of shape ``(per // model_parallel,
      model_parallel)`` where ``per = device_count // replicas``.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    if exclude:
        bad = set(exclude)
        devs = [d for d in devs if d.id not in bad]
    n = len(devs)
    if replicas < 1 or n % replicas:
        raise ValueError(f"replicas={replicas} does not divide the "
                         f"{n} visible devices")
    per = n // replicas
    mp = model_parallel if model_parallel is not None else per
    if mp < 1 or per % mp:
        raise ValueError(f"model_parallel={mp} does not divide the "
                         f"{per} devices per replica")
    meshes = []
    for r in range(replicas):
        grid = np.asarray(devs[r * per:(r + 1) * per],
                          dtype=object).reshape(per // mp, mp)
        # jax.sharding.Mesh keeps the carved device order exactly
        # (jax.make_mesh may permute a device list to the physical
        # topology)
        meshes.append(jax.sharding.Mesh(
            grid, ("data", "model"),
            axis_types=(jax.sharding.AxisType.Auto,) * 2))
    return meshes


def batch_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod included when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)

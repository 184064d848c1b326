"""Device-mesh construction for every parallel layout the repo uses.

Never touches jax device state at import time: everything is a function.
The production topology is a TPU v5e pod of 16x16 = 256 chips; multi-pod
adds a leading "pod" axis (2 pods = 512 chips) carrying pure data
parallelism over DCN. The serving path uses the one-axis tensor-parallel
mesh (:func:`make_tp_mesh`); training/dry-run paths use the data x model
meshes below with :mod:`repro.sharding.rules`.

Every constructor works on CPU with virtual devices — set
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` *before* jax
initializes and ``jax.devices()`` reports N host devices (this is how CI
exercises the sharded serving stack without accelerators).
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape, axes) -> Mesh:
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} "
            "(the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512)"
        )
    arr = np.array(devices[:n]).reshape(shape)
    return Mesh(arr, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The deployment mesh: ``("data", "model")`` over one 256-chip pod,
    or ``("pod", "data", "model")`` over two pods (pure DP across DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes) -> Mesh:
    """Arbitrary mesh (tests, elastic re-meshing): ``shape`` and ``axes``
    are parallel tuples, e.g. ``make_mesh((4, 2), ("data", "model"))``.
    Raises ``RuntimeError`` when fewer than ``prod(shape)`` devices exist."""
    return _mesh(tuple(shape), tuple(axes))


def make_host_mesh(model: int = 1) -> Mesh:
    """Mesh over whatever devices exist (CPU tests: 1 or XLA-forced N),
    shaped ``(n_devices // model, model)`` as ``("data", "model")``."""
    n = len(jax.devices())
    return _mesh((n // model, model), ("data", "model"))


def make_tp_mesh(model: int) -> Mesh:
    """One-axis tensor-parallel mesh ``("model",)`` over the first
    ``model`` devices — the serving engine's mesh.

    This is the mesh :class:`repro.sharding.tp.TPContext` builds its
    ``shard_map`` steps over: weight-plane caches are column/row-sharded
    and the KV cache head-sharded along this single ``"model"`` axis (the
    contract is DESIGN.md §11). Batch parallelism in the serving engine is
    slot-level (host scheduling), not a mesh axis, so one axis suffices.
    Raises ``RuntimeError`` when fewer than ``model`` devices exist.
    """
    return _mesh((model,), ("model",))

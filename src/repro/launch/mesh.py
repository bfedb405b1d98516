"""Production mesh builders (assignment §Multi-pod dry-run).

A function, not a module-level constant: importing this module never touches
jax device state.  Shapes: 16×16 = 256 chips per pod (TPU v5e), multi-pod =
2×16×16 = 512 chips with a leading "pod" axis riding the slower DCI links.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh


def _mk(shape, axes) -> Mesh:
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        hint = (" (on the CPU, set XLA_FLAGS="
                "--xla_force_host_platform_device_count before importing "
                "jax)" if devs[0].platform == "cpu" else "")
        raise RuntimeError(f"mesh {shape} needs {n} devices, found "
                           f"{len(devs)} {devs[0].platform} devices{hint}")
    return Mesh(np.asarray(devs[:n]).reshape(shape), axes)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return _mk(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = len(jax.devices())
    data = data or (n // model)
    return _mk((data, model), ("data", "model"))

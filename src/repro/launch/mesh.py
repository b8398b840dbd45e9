"""Mesh construction — the single source of truth for device layout.

Every mesh the system uses (production pod, host-local, data-plane queue
sharding) is built through the one ``_build`` funnel below, so axis names
and shapes cannot drift between the serving stack and the data plane.
All constructors are FUNCTIONS, not module-level constants — importing
this module never touches jax device state (the dry-run sets XLA_FLAGS
before first init).
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _build(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """The one funnel every mesh layout goes through.  Axes are ``Auto``:
    the compiler propagates shardings (``jax.make_mesh`` now defaults to
    ``Explicit`` axes, under which plain indexing of a sharded result,
    such as one queue's slice of the fan-out output, is an error).
    ``devices`` lays the mesh over exactly those devices, in that order."""
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} does not match axes {axes}")
    types = (AxisType.Auto,) * len(axes)
    if devices is not None:
        return Mesh(np.asarray(devices, dtype=object).reshape(shape),
                    tuple(axes), axis_types=types)
    return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _build(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever this process actually has (tests / examples / elastic)."""
    n = jax.device_count()
    model_parallel = min(model_parallel, n)
    return _build((n // model_parallel, model_parallel), ("data", "model"))


def make_queue_mesh(num_queues: int, devices=None):
    """A mesh whose leading axis shards the data-plane queue dimension.

    With ``devices`` the mesh is one ``queues`` axis over exactly those
    devices, whose count must divide ``num_queues`` (``ValueError``
    otherwise).  Without, it composes with ``make_host_mesh`` instead of
    re-deriving the layout: the host mesh is reused whenever its data
    axis divides the queue count; otherwise a dedicated 1-axis mesh is
    built over the largest device count that does.  Returns
    ``(mesh, axis_name)``.
    """
    if devices is not None:
        devices = list(devices)
        if not devices or num_queues % len(devices):
            raise ValueError(f"{len(devices)} devices cannot split "
                             f"{num_queues} queues evenly")
        return _build((len(devices),), ("queues",), devices), "queues"
    m = make_host_mesh(1)
    if num_queues % m.devices.shape[0] == 0:
        return m, "data"
    d = math.gcd(num_queues, jax.device_count())
    return _build((d,), ("queues",)), "queues"

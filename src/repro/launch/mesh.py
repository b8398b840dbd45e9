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
from jax.sharding import AxisType


def _build(shape: tuple[int, ...], axes: tuple[str, ...]):
    """The one funnel every mesh layout goes through.  Axes are ``Auto``:
    the compiler propagates shardings (``jax.make_mesh`` now defaults to
    ``Explicit`` axes, under which plain indexing of a sharded result,
    such as one queue's slice of the fan-out output, is an error)."""
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} does not match axes {axes}")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


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


def make_queue_mesh(num_queues: int):
    """A mesh whose leading axis shards the data-plane queue dimension.

    Composes with ``make_host_mesh`` instead of re-deriving the layout:
    the host mesh is reused whenever its data axis divides the queue
    count; otherwise a dedicated 1-axis mesh is built over the largest
    device count that does.  Returns ``(mesh, axis_name)``.
    """
    m = make_host_mesh(1)
    if num_queues % m.devices.shape[0] == 0:
        return m, "data"
    d = math.gcd(num_queues, jax.device_count())
    return _build((d,), ("queues",)), "queues"

"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run forces 512
host devices via XLA_FLAGS before any jax import, while tests/benches must
see the single real CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    # Auto axes: the sharding policy steers layouts with
    # with_sharding_constraint, which Explicit axes (jax.make_mesh's
    # default under JAX 0.9) reject
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 chips per pod; 2 pods for the multi-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a (data, model) mesh (1,1 on CPU)."""
    n = jax.device_count()
    return _mesh((n, 1), ("data", "model"))

"""Production meshes.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests keep their single CPU device;
only launch/dryrun.py (which forces 512 host devices before any jax import)
ever builds the full meshes.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    through ``with_sharding_constraint`` and let GSPMD propagate, which
    JAX's default Explicit axes refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips; "pod" is the outer
    data axis (hierarchical gradient reduction: intra-pod on "data" over
    ICI, inter-pod on "pod" over DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_serve_mesh(data: int, model: int):
    """(data, model) serving mesh over the first data*model devices:
    slots shard over "data", KV heads over "model" (ServeConfig.mesh)."""
    return _auto_mesh((data, model), ("data", "model"))


def make_debug_mesh(data: int = 2, model: int = 2):
    """Small mesh for subprocess sharding tests (8 forced host devices)."""
    return make_serve_mesh(data, model)

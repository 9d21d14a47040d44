"""Mesh construction for the production pods and local test meshes.

All constructors are FUNCTIONS (never module-level constants) so importing
this module never touches jax device state — required because the dry-run
must set ``XLA_FLAGS`` before the first jax initialisation.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np



def make_production_mesh(*, multi_pod: bool = False):
    """The assigned production mesh.

    Single pod: (16, 16) over ("data", "model") — 256 chips.
    Multi-pod:  (2, 16, 16) over ("pod", "data", "model") — 512 chips.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """A custom mesh (tests, PP demos, elastic restore targets)."""
    import jax
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def local_mesh(data: Optional[int] = None, model: int = 1):
    """A ("data", "model") mesh over the locally visible devices."""
    import jax
    n = jax.device_count()
    data = data if data is not None else n // model
    assert data * model <= n, (data, model, n)
    return make_mesh((data, model), ("data", "model"))


def dp_axes(mesh) -> Tuple[str, ...]:
    """Axes carrying the batch (hierarchical DP: pod composes with data)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def tp_axis(mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))

"""Production mesh builders.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init).

Topology: TPU v5e pod = 16x16 = 256 chips; multi-pod adds the leading 'pod'
axis (2 pods = 512 chips for the dry-run; the same code scales the pod axis
to any count — data parallelism over pods, DCN-connected).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _axis_kwargs(n: int) -> dict:
    """Explicit Auto axis types keep GSPMD semantics stable."""
    return {"axis_types": (AxisType.Auto,) * n}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_axis_kwargs(len(axes)))


def make_mesh(shape, axes):
    """General helper (tests, examples) with stable Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         **_axis_kwargs(len(axes)))


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# active mesh: the planner's gate for distributed candidates
# ---------------------------------------------------------------------------
# The planner (repro.core.plan) enumerates mesh-sharded FFT candidates
# (dist1d / slab / pencil) only when a mesh is *active*: planning must never
# offer an 8-device decomposition to a process that owns one device.  The
# active mesh is process-global state, set explicitly by the launcher (or a
# client that decided to scale out) — device discovery alone never activates
# it, so single-device planning semantics are unchanged by default.
_ACTIVE_MESH = None


def set_active_mesh(mesh) -> None:
    """Install ``mesh`` (or ``None`` to clear) as the planning mesh."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def get_active_mesh():
    """The mesh distributed candidates plan against, or ``None``."""
    return _ACTIVE_MESH


class use_mesh:
    """Context manager: activate ``mesh`` for planning, restore on exit."""

    def __init__(self, mesh):
        self.mesh = mesh
        self._prev = None

    def __enter__(self):
        self._prev = get_active_mesh()
        set_active_mesh(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        set_active_mesh(self._prev)
        return False


def flat_mesh(devices=None, name: str = "data"):
    """A 1D mesh over ``devices`` (default: every visible device)."""
    import numpy as np
    from jax.sharding import Mesh

    devs = list(jax.devices()) if devices is None else list(devices)
    return Mesh(np.array(devs), (name,))


def reshaped_mesh(mesh, shape, names=None):
    """The same devices as ``mesh`` re-viewed with ``shape`` (row-major).

    The distributed candidates carry a mesh *shape* key (``pencil[2x4]``);
    this turns the active mesh into one matching that shape regardless of
    how the launcher factored its axes.
    """
    import math
    import numpy as np
    from jax.sharding import Mesh

    shape = tuple(int(s) for s in shape)
    devs = np.asarray(mesh.devices).reshape(-1)
    if math.prod(shape) != devs.size:
        raise ValueError(f"mesh of {devs.size} devices cannot be viewed "
                         f"as shape {shape}")
    if names is None:
        names = tuple(f"d{i}" for i in range(len(shape)))
    return Mesh(devs.reshape(shape), tuple(names))



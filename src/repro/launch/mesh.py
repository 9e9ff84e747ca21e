"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
the 512-device XLA flag before any jax initialization.
"""
from __future__ import annotations

from repro.compat import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod (data=16, model=16) = 256 chips, or multi-pod
    (pod=2, data=16, model=16) = 512 chips (v5e pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)

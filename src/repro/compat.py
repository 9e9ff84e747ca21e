"""Mesh helpers shared by every module that builds or reads a mesh.

Exports:
  make_mesh(shape, names) — the one mesh constructor (Auto axes)
  get_ambient_mesh()      — ambient (abstract or physical) mesh, or None
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axes.

    Bare ``jax.make_mesh`` builds Explicit axes, under which jnp gathers
    on sharded operands need ``out_sharding=`` and ``jax.grad`` needs an
    ambient mesh; the code here relies on GSPMD propagation, so every
    mesh is Auto."""
    auto = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=auto)


def get_ambient_mesh():
    """The mesh installed by ``jax.set_mesh``, or ``None`` outside one."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh

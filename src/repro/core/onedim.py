"""1D communication-optimal parallel SYRK / SYR2K / SYMM (paper Algs 7–9).

Optimal regime (Thm 9 case 1): n₁ ≤ m·n₂ and P ≤ m·n₂/√(n₁(n₁−1)).
The non-symmetric matrices are column-distributed and never communicated;
only the symmetric matrix moves — as a *packed lower triangle* (n₁(n₁+1)/2
words) through one reduce-scatter (SYRK/SYR2K) or all-gather (SYMM),
bandwidth (1−1/P)·n₁(n₁+1)/2 — exactly eq. (4) including the constant.

Two surfaces per kernel:
  * ``*_local``   — per-shard function for use inside an existing shard_map
                    (the optimizer integration path);
  * ``syrk_1d``.. — full-array wrappers that shard_map over a mesh axis
                    (tests / library use).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .packing import pack_tril, tril_size, unpack_tril


def _padded_tril_len(n1: int, nshards: int) -> int:
    t = tril_size(n1)
    return -(-t // nshards) * nshards


# --------------------------------------------------------------------------
# per-shard bodies
# --------------------------------------------------------------------------
def syrk_1d_local(a_loc: jax.Array, axis: str, n_shards: int) -> jax.Array:
    """Local body of Alg 7.  ``a_loc``: (n1, n2/P) column shard.
    Returns this device's shard of the packed lower triangle of A·Aᵀ
    (padded to a multiple of P)."""
    n1 = a_loc.shape[0]
    g = a_loc @ a_loc.T                                   # local outer product
    packed = pack_tril(g)                                  # n1(n1+1)/2 words
    pad = _padded_tril_len(n1, n_shards) - packed.shape[0]
    packed = jnp.pad(packed, (0, pad))
    # communication-optimal reduce-scatter of the packed triangle (eq. 4)
    return jax.lax.psum_scatter(packed, axis, scatter_dimension=0, tiled=True)


def syr2k_1d_local(a_loc: jax.Array, b_loc: jax.Array, axis: str,
                   n_shards: int) -> jax.Array:
    """Local body of Alg 8: packed shard of A·Bᵀ + B·Aᵀ."""
    n1 = a_loc.shape[0]
    g = a_loc @ b_loc.T
    g = g + g.T                       # A·Bᵀ + B·Aᵀ  ((A·Bᵀ)ᵀ = B·Aᵀ)
    packed = pack_tril(g)
    pad = _padded_tril_len(n1, n_shards) - packed.shape[0]
    packed = jnp.pad(packed, (0, pad))
    return jax.lax.psum_scatter(packed, axis, scatter_dimension=0, tiled=True)


def symm_1d_local(a_packed_loc: jax.Array, b_loc: jax.Array, axis: str,
                  n1: int) -> jax.Array:
    """Local body of Alg 9.  ``a_packed_loc``: this device's shard of the
    packed lower triangle of symmetric A; ``b_loc``: (n1, n2/P) column shard.
    All-gathers the packed triangle (eq. 4 bandwidth), unpacks locally, and
    multiplies: returns C column shard (n1, n2/P)."""
    packed = jax.lax.all_gather(a_packed_loc, axis, axis=0, tiled=True)
    packed = packed[:tril_size(n1)]
    a_full = unpack_tril(packed, n1, diag=True, symmetric=True)
    return a_full @ b_loc


# --------------------------------------------------------------------------
# full-array wrappers
# --------------------------------------------------------------------------
def _axis_size(mesh: jax.sharding.Mesh, axis: str) -> int:
    return mesh.shape[axis]


def syrk_1d(A: jax.Array, mesh: jax.sharding.Mesh, axis: str = "x"
            ) -> jax.Array:
    """C = A·Aᵀ with A column-sharded over ``axis``; returns the packed lower
    triangle (padded), sharded over ``axis``."""
    nsh = _axis_size(mesh, axis)
    f = functools.partial(syrk_1d_local, axis=axis, n_shards=nsh)
    spec_in = P(None, axis)
    spec_out = P(axis)
    return jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec_in,
                                     out_specs=spec_out))(A)


def syr2k_1d(A: jax.Array, B: jax.Array, mesh: jax.sharding.Mesh,
             axis: str = "x") -> jax.Array:
    nsh = _axis_size(mesh, axis)
    f = functools.partial(syr2k_1d_local, axis=axis, n_shards=nsh)
    return jax.jit(jax.shard_map(f, mesh=mesh,
                                     in_specs=(P(None, axis), P(None, axis)),
                                     out_specs=P(axis)))(A, B)


def symm_1d(A_packed: jax.Array, B: jax.Array, n1: int,
            mesh: jax.sharding.Mesh, axis: str = "x") -> jax.Array:
    """C = A·B, A given as packed lower triangle (padded to multiple of P and
    sharded over ``axis``); B column-sharded.  Returns C column-sharded."""
    f = functools.partial(symm_1d_local, axis=axis, n1=n1)
    return jax.jit(jax.shard_map(f, mesh=mesh,
                                     in_specs=(P(axis), P(None, axis)),
                                     out_specs=P(None, axis)))(A_packed, B)


# --------------------------------------------------------------------------
# host-side helpers for tests / data prep
# --------------------------------------------------------------------------
def pack_for_1d_symm(A_full: np.ndarray, n_shards: int) -> np.ndarray:
    """Pack a full symmetric matrix into the padded packed-triangle layout
    expected by :func:`symm_1d`."""
    n1 = A_full.shape[0]
    i, j = np.tril_indices(n1)
    packed = np.asarray(A_full)[i, j]
    pad = _padded_tril_len(n1, n_shards) - packed.shape[0]
    return np.pad(packed, (0, pad))


def unpack_1d_result(packed: np.ndarray, n1: int) -> np.ndarray:
    """Packed (padded) triangle -> dense lower-triangular numpy array."""
    t = tril_size(n1)
    out = np.zeros((n1, n1), dtype=packed.dtype)
    i, j = np.tril_indices(n1)
    out[i, j] = np.asarray(packed)[:t]
    return out

"""Pallas TPU SYMM kernel: C = sym(A)·B with A stored as *packed
lower-triangle tiles*.

TPU adaptation (DESIGN §3): the symmetric operand never materializes its
upper half in HBM — the kernel reads tile (i,k) of sym(A) from the packed
tile array at flat index tri(max(i,k)) + min(i,k) via a scalar-prefetched
lookup, transposing on the fly when k > i and symmetrizing diagonal tiles
in VMEM.  This halves HBM traffic and capacity for A versus a dense GEMM
while keeping every load a dense, MXU-aligned (bm × bm) tile.

Scheduling (cached lookup tables, grid spec, interpret default) and the
in-kernel out_dtype cast live in :mod:`repro.kernels.trigrid`; this file
is only the per-step symmetrize-and-matmul body."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import trigrid


def _symm_body(a: jax.Array, mode, b: jax.Array, *,
               diag_scale: float = 1.0) -> jax.Array:
    """a: (bm, bm) packed tile; mode 0: as-is, 1: transpose, 2: diagonal
    (symmetrize from the lower half — the tile's upper half, structural
    zeros or garbage, is never read).

    ``diag_scale`` is the fused *cotangent prologue*: the matrix
    diagonal of diagonal tiles is scaled in VMEM while symmetrizing.
    With ``diag_scale=2.0`` the kernel consumes a packed (tril-exposed)
    cotangent L directly as sym(L)+diag(L) = L + Lᵀ — no standalone
    elementwise doubling pass ever touches the packed vector."""
    a = a.astype(jnp.float32)
    bm = a.shape[0]
    a_t = a.T
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 1)
    tril = jnp.where(rows >= cols, a, 0.0)
    a_diag = tril + jnp.where(rows > cols, a, 0.0).T
    if diag_scale != 1.0:
        a_diag = a_diag + (diag_scale - 1.0) * jnp.where(rows == cols, a,
                                                         0.0)
    a_eff = jnp.where(mode == 0, a, jnp.where(mode == 1, a_t, a_diag))
    return jnp.dot(a_eff, b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def symm_tiles(a_packed: jax.Array, b: jax.Array, *, bm: int = 128,
               bn: int = 128, interpret: Optional[bool] = None,
               out_dtype=jnp.float32, diag_scale: float = 1.0
               ) -> jax.Array:
    """a_packed: (T, bm, bm) packed lower-triangle tiles of symmetric A
    (T = nt(nt+1)/2, row-major; diagonal tiles tril-valid); b: (n1, n2).
    Returns C = sym_s(A)·B (n1, n2) in ``out_dtype`` (f32 accumulation),
    where sym_s symmetrizes from the lower half with the matrix diagonal
    scaled by ``diag_scale`` (the in-kernel cotangent prologue)."""
    body = _symm_body if diag_scale == 1.0 else \
        functools.partial(_symm_body, diag_scale=diag_scale)
    return trigrid.sym_stream(body, a_packed, b, name="symm", bm=bm,
                              bn=bn, interpret=interpret,
                              out_dtype=out_dtype)

"""Pallas TPU SYRK kernel: C = tril(A·Aᵀ) over a *triangular flat grid*.

TPU adaptation of the paper's sequential Alg 4 (DESIGN §3):
  * the iteration space {(i,j) tile pairs : j ≤ i} is flattened into a 1-D
    grid of T = nt(nt+1)/2 steps driven by scalar-prefetched (i,j) lookup
    tables — no grid step is wasted on the empty upper triangle (a
    rectangular grid + mask would waste ~2× steps and ~2× MXU issue);
  * "fast memory" = VMEM: one (bm × bm) f32 accumulator tile is resident
    per output block while (bm × bk) panels of A stream through — exactly
    the resident-triangle/streamed-panel structure of the paper's
    algorithm;
  * output is *tile-packed* (T, bm, bm): only the lower triangle of tiles
    is ever written to HBM (the symmetric-storage savings), tiles dense
    and MXU-aligned.

Scheduling (cached coord tables, grid specs, interpret default) and the
fused epilogue (diagonal masking, alpha/beta accumulate into an existing
packed C, out_dtype cast — all in-kernel, nothing post-hoc in XLA) live
in :mod:`repro.kernels.trigrid`; this file is only the MXU body.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import trigrid


def _syrk_body(ai: jax.Array, aj: jax.Array) -> jax.Array:
    return jnp.dot(ai.astype(jnp.float32), aj.astype(jnp.float32).T,
                   preferred_element_type=jnp.float32)


def syrk_tiles(a: jax.Array, *, bm: int = 128, bk: int = 128,
               interpret: Optional[bool] = None,
               c0: Optional[jax.Array] = None, alpha: float = 1.0,
               beta: float = 0.0, out_dtype=jnp.float32) -> jax.Array:
    """A (n1, n2) -> packed lower-triangle tiles (T, bm, bm) of
    ``alpha·A·Aᵀ + beta·C0`` in ``out_dtype`` (f32 accumulation).

    n1 % bm == 0 and n2 % bk == 0 required (blas/api.py pads).  ``c0``
    is an optional packed-tile (T, bm, bm) accumulator consumed by the
    in-kernel epilogue when ``beta != 0``."""
    ep = trigrid.Epilogue(alpha=alpha, beta=beta,
                          accumulate=c0 is not None and beta != 0.0,
                          out_dtype=out_dtype)
    return trigrid.rank_update(_syrk_body, (a, a), "ij", name="syrk",
                               bm=bm, bk=bk, interpret=interpret,
                               epilogue=ep,
                               c0=c0 if ep.accumulate else None)

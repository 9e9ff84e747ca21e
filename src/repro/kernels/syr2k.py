"""Pallas TPU SYR2K kernel: C = tril(A·Bᵀ + B·Aᵀ), triangular flat grid.

Same scheduling structure as the SYRK kernel (shared via
:mod:`repro.kernels.trigrid`); each grid step issues two MXU matmuls and
fuses the mirrored accumulation — the two products per tile share the
streamed A/B panels, so HBM traffic per output tile equals SYRK's with
m=2 panels (the paper's m-scaling).  The epilogue (diagonal masking,
alpha/beta accumulate, out_dtype cast) runs in-kernel."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import trigrid


def _syr2k_body(ai: jax.Array, bj: jax.Array, bi: jax.Array,
                aj: jax.Array) -> jax.Array:
    acc = jnp.dot(ai.astype(jnp.float32), bj.astype(jnp.float32).T,
                  preferred_element_type=jnp.float32)
    acc += jnp.dot(bi.astype(jnp.float32), aj.astype(jnp.float32).T,
                   preferred_element_type=jnp.float32)
    return acc


def syr2k_tiles(a: jax.Array, b: jax.Array, *, bm: int = 128,
                bk: int = 128, interpret: Optional[bool] = None,
                c0: Optional[jax.Array] = None, alpha: float = 1.0,
                beta: float = 0.0, out_dtype=jnp.float32,
                diag_scale: float = 1.0) -> jax.Array:
    """A, B (n1, n2) -> packed lower-triangle tiles (T, bm, bm) of
    ``alpha·(A·Bᵀ + B·Aᵀ) + beta·C0`` in ``out_dtype``.  ``diag_scale``
    scales the matrix diagonal in the fused epilogue (the SYMM-backward
    halving runs in-kernel instead of as an XLA pass)."""
    ep = trigrid.Epilogue(alpha=alpha, beta=beta,
                          accumulate=c0 is not None and beta != 0.0,
                          out_dtype=out_dtype, diag_scale=diag_scale)
    return trigrid.rank_update(_syr2k_body, (a, b, b, a), "ijij",
                               name="syr2k", bm=bm, bk=bk,
                               interpret=interpret, epilogue=ep,
                               c0=c0 if ep.accumulate else None)

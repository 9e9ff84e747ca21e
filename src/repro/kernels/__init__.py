"""Pallas TPU kernels (validated in interpret mode on CPU; see
tests/test_kernels.py and tests/test_slstm_kernel.py):

* syrk / syr2k / symm — the paper's three computations with triangular
  flat-grid scheduling and packed-triangle tile storage (``*_tiles``
  kernels, reached through :mod:`repro.blas`'s Pallas route; ref.py
  jnp oracles);
* slstm — fused recurrence scan (§Perf cell-1 TPU endgame: state in
  registers, one HBM pass over the gates).
"""
from . import ref
from .slstm import slstm_scan

__all__ = ["ref", "slstm_scan"]

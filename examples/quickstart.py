"""Quickstart: the paper's three symmetric kernels end to end.

    PYTHONPATH=src python examples/quickstart.py

Walks through:
  1. sequential SYRK/SYR2K/SYMM with *measured* slow-fast traffic vs the
     paper's lower bounds (Cor 3-5, exact constants),
  2. the §VIII-D regime dispatcher picking 1D / 2D / 3D per problem,
  3. parallel 1D + 2D algorithms on a 12-device CPU mesh with results
     checked against numpy,
  4. the Pallas TPU kernels in interpret mode vs the jnp oracle.
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=12")

import numpy as np                                              # noqa: E402
import jax                                                      # noqa: E402
import jax.numpy as jnp                                         # noqa: E402

from repro.compat import make_mesh                              # noqa: E402
from repro.core.seq import seq_symm, seq_syr2k, seq_syrk        # noqa: E402
from repro.core.lower_bounds import (                           # noqa: E402
    memory_independent_lower_bound, sequential_reads_lower_bound)
from repro.core.dispatch import choose_algorithm                # noqa: E402
from repro.core.onedim import (pack_for_1d_symm, symm_1d,       # noqa: E402
                               syrk_1d, unpack_1d_result)
from repro.core.twodim import (assemble_sym, collect_rows,      # noqa: E402
                               distribute_rows, distribute_sym,
                               make_2d_plan, symm_2d, syrk_2d)

rng = np.random.default_rng(0)


# ---------------------------------------------------------------- 1. seq
print("=" * 70)
print("1. Sequential algorithms (Algs 4-6): measured reads vs Cor 3-5")
# n1 = 64 = 8² uses the affine-plane partition with r = 8; M is set so
# r = ⌊√(2M+m²)−m⌋ = 8 is exactly the memory-optimal block (eq. 2).
n1, n2 = 64, 96
A = rng.standard_normal((n1, n2)).astype(np.float32)
B = rng.standard_normal((n1, n2)).astype(np.float32)
S = rng.standard_normal((n1, n1)).astype(np.float32)
S = np.tril(S) + np.tril(S, -1).T

for name, m, M, run in (
        ("SYRK ", 1, 40, lambda: seq_syrk(A, M=40)),
        ("SYR2K", 2, 48, lambda: seq_syr2k(A, B, M=48)),
        ("SYMM ", 2, 48, lambda: seq_symm(S, B, M=48))):
    res = run()
    lb = sequential_reads_lower_bound(n1, n2, M, m)
    print(f"  {name} reads={res.reads:9d}  lower-bound={lb:9.0f}  "
          f"ratio={res.reads / lb:.3f}  (peak fast-mem {res.peak_resident}"
          f" <= M={M}: {res.peak_resident <= M})")

# ------------------------------------------------------------ 2. dispatch
print("=" * 70)
print("2. Regime dispatch (§VIII-D): the optimal family per problem")
for n1_, n2_, P in ((1 << 10, 1 << 16, 8),     # short-wide, few procs -> 1D
                    (1 << 16, 1 << 7, 12),     # tall-skinny          -> 2D
                    (1 << 12, 1 << 12, 512)):  # big P                -> 3D
    ch = choose_algorithm(n1_, n2_, P, m=1)
    print(f"  n1={n1_:6d} n2={n2_:6d} P={P:4d} -> {ch.kind:10s} "
          f"(case {ch.case}, grid c={ch.c}, p2={ch.p2}, "
          f"words/proc={ch.predicted_words:.3e}, "
          f"opt-ratio={ch.optimality_ratio:.3f})")

# ------------------------------------------------------------ 3. parallel
print("=" * 70)
print("3. Parallel algorithms on a 12-device CPU mesh")
P = 4
mesh1 = make_mesh((P,), ("x",))
n1p, n2p = 24, 8 * P
Ap = rng.standard_normal((n1p, n2p)).astype(np.float32)
out = unpack_1d_result(np.asarray(syrk_1d(jnp.asarray(Ap), mesh1)), n1p)
err = np.abs(out - np.tril(Ap @ Ap.T)).max()
print(f"  1D SYRK  (Alg 7, P={P}): max|err| = {err:.2e}")

c = 3
P2 = c * (c + 1)
mesh2 = make_mesh((P2,), ("x",))
n1q, n2q = 4 * c * c, 3 * (c + 1)
plan = make_2d_plan(c, n1q, n2q)
Aq = rng.standard_normal((n1q, n2q)).astype(np.float32)
# the 2D schedules are batch-native, (P, K, ...): one matrix is K = 1
off, diag = syrk_2d(jnp.asarray(distribute_rows(Aq, plan))[:, None], plan,
                    mesh2)
got = assemble_sym(np.asarray(off[:, 0]), np.asarray(diag[:, 0]), plan)
err = np.abs(got - np.tril(Aq @ Aq.T)).max()
print(f"  2D SYRK  (Alg 10, c={c}, P={P2}, triangle-block dist): "
      f"max|err| = {err:.2e}")

Sq = rng.standard_normal((n1q, n1q)).astype(np.float32)
Sq = np.tril(Sq) + np.tril(Sq, -1).T
Bq = rng.standard_normal((n1q, n2q)).astype(np.float32)
s_off, s_diag = distribute_sym(Sq, plan)
cd = symm_2d(jnp.asarray(s_off)[:, None], jnp.asarray(s_diag)[:, None],
             jnp.asarray(distribute_rows(Bq, plan))[:, None], plan, mesh2)
err = np.abs(collect_rows(np.asarray(cd[:, 0]), plan) - Sq @ Bq).max()
print(f"  2D SYMM  (Alg 12): max|err| = {err:.2e}")

lb = memory_independent_lower_bound(n1q, n2q, P2, m=1)
print(f"  memory-independent LB (Cor 10, case {lb.case}): "
      f"{lb.bound:.1f} words/proc")

# ------------------------------------------------------------- 4. kernels
print("=" * 70)
print("4. Pallas TPU kernels (interpret mode) via the repro.blas surface")
from repro import blas                                          # noqa: E402
from repro.kernels import ref                                   # noqa: E402
n = 256
Ak = rng.standard_normal((n, 128)).astype(np.float32)
got = np.asarray(blas.syrk(jnp.asarray(Ak), tile=(128, 128),
                           interpret=True))
want = np.asarray(ref.syrk_ref(jnp.asarray(Ak)))
print(f"  pallas SYRK  max|err| = {np.abs(got - want).max():.2e}")
Sk = rng.standard_normal((n, n)).astype(np.float32)
Sk = np.tril(Sk)                     # kernels take the packed lower triangle
Bk = rng.standard_normal((n, 128)).astype(np.float32)
got = np.asarray(blas.symm(jnp.asarray(Sk), jnp.asarray(Bk),
                           tile=(128, 128), interpret=True))
want = np.asarray(ref.symm_ref(jnp.asarray(Sk), jnp.asarray(Bk)))
print(f"  pallas SYMM  max|err| = {np.abs(got - want).max():.2e}")

# ----------------------------------------------------- 5. unified dispatch
print("=" * 70)
print("5. repro.blas: one entry point, regime-routed execution")
mesh4 = make_mesh((4,), ("x",))
A5 = jnp.asarray(rng.standard_normal((16, 1024)), np.float32)
for op, n1_, n2_, mesh_ in (("syrk", 24, 24, None),
                            ("syrk", 16, 1024, mesh4),
                            ("syrk", 36, 6, None),
                            ("symm", 512, 512, None)):
    print("  " + blas.explain(op, n1_, n2_, mesh=mesh_))
out = blas.syrk(A5, mesh=mesh4)        # packed-triangle 1D under the hood
err = np.abs(np.asarray(out) - np.tril(np.asarray(A5) @ np.asarray(A5).T)
             ).max()
print(f"  blas.syrk(mesh) matches dense oracle: max|err| = {err:.2e}")
print("done.")

"""§Parallel — measured collective traffic of the 1D/2D/3D algorithms vs
the memory-independent bounds (Cor 10-12, Table: parallel lower bounds).

Runs in a SUBPROCESS with a fake multi-device CPU so this process keeps
one device (the dryrun rule).  For each (kernel × regime) the algorithm
is lowered on its mesh, collective WIRE bytes are counted from the
compiled HLO (ring model, §III-B2a pairwise-exchange costs), converted
to words/processor, and compared against the paper's W formula and
lower bound.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = r"""
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.analysis.hlo_cost import analyze_hlo
from repro.compat import make_mesh
from repro.core.dispatch import choose_algorithm
from repro.core.lower_bounds import memory_independent_lower_bound
from repro.core.onedim import syrk_1d, syr2k_1d, symm_1d, pack_for_1d_symm
from repro.core.twodim import (make_2d_plan, syrk_2d, syr2k_2d, symm_2d,
                               distribute_rows, distribute_sym)
from repro.core.threedim import syrk_3d, syr2k_3d, symm_3d, flat_tb_size

def wire_words(lowered):
    hlo = lowered.compile().as_text()
    return analyze_hlo(hlo).collective_wire_bytes / 4.0   # f32 words

rows = []
def emit(**kw):
    rows.append(kw)

# ---------------- 1D (case 1): n1 small, n2 large, P small -------------
P_ = 8
mesh = make_mesh((P_,), ("x",))
n1, n2 = 64, 64 * P_
A = jax.ShapeDtypeStruct((n1, n2), jnp.float32)
B = jax.ShapeDtypeStruct((n1, n2), jnp.float32)
lb = memory_independent_lower_bound(n1, n2, P_, 1).bound
w = wire_words(jax.jit(lambda a: syrk_1d(a, mesh)).lower(A))
formula = (1 - 1/P_) * n1 * (n1 + 1) / 2
emit(kernel="syrk", algo="1d", P=P_, n1=n1, n2=n2,
     measured_words=w, paper_W=formula, lower_bound=lb)
lb2 = memory_independent_lower_bound(n1, n2, P_, 2).bound
w = wire_words(jax.jit(lambda a, b: syr2k_1d(a, b, mesh)).lower(A, B))
emit(kernel="syr2k", algo="1d", P=P_, n1=n1, n2=n2,
     measured_words=w, paper_W=formula, lower_bound=lb2)
from repro.core.onedim import _padded_tril_len
Sp = jax.ShapeDtypeStruct((_padded_tril_len(n1, P_),), jnp.float32)
w = wire_words(jax.jit(lambda s, b: symm_1d(s, b, n1, mesh)).lower(Sp, B))
emit(kernel="symm", algo="1d", P=P_, n1=n1, n2=n2,
     measured_words=w, paper_W=formula, lower_bound=lb2)

# ---------------- 2D (case 2): n1 large, n2 small ----------------------
c = 3
P2 = c * (c + 1)
mesh2 = make_mesh((P2,), ("x",))
n1, n2 = 4 * c * c, 2 * (c + 1)           # mn2 < n1
plan = make_2d_plan(c, n1, n2)
a_spec = jax.ShapeDtypeStruct((P2, c, plan.nb, plan.w), jnp.float32)
lb = memory_independent_lower_bound(n1, n2, P2, 1).bound
w = wire_words(jax.jit(lambda a: syrk_2d(a, plan, mesh2)).lower(a_spec))
formula = 1 * n1 * n2 / c * (1 - 1/P2)
emit(kernel="syrk", algo="2d", P=P2, n1=n1, n2=n2,
     measured_words=w, paper_W=formula, lower_bound=lb)
lb2 = memory_independent_lower_bound(n1, n2, P2, 2).bound
w = wire_words(jax.jit(lambda a, b: syr2k_2d(a, b, plan, mesh2))
               .lower(a_spec, a_spec))
emit(kernel="syr2k", algo="2d", P=P2, n1=n1, n2=n2,
     measured_words=w, paper_W=2 * formula, lower_bound=lb2)
s_off = jax.ShapeDtypeStruct((P2, plan.T, plan.nb, plan.nb), jnp.float32)
s_diag = jax.ShapeDtypeStruct((P2, plan.nb, plan.nb), jnp.float32)
w = wire_words(jax.jit(lambda o, d, b: symm_2d(o, d, b, plan, mesh2))
               .lower(s_off, s_diag, a_spec))
emit(kernel="symm", algo="2d", P=P2, n1=n1, n2=n2,
     measured_words=w, paper_W=2 * formula, lower_bound=lb2)

# ---------------- 3D (case 3): big P ------------------------------------
c, p2 = 2, 2
p1 = c * (c + 1)
P3 = p1 * p2
mesh3 = make_mesh((p1, p2), ("tb", "rep"))
n1 = 2 * c * c
n2 = 2 * (c + 1) * p2
n2s = n2 // p2
plan3 = make_2d_plan(c, n1, n2s)
a3 = jax.ShapeDtypeStruct((p1, p2, c, plan3.nb, plan3.w), jnp.float32)
lb = memory_independent_lower_bound(n1, n2, P3, 1).bound
w = wire_words(jax.jit(lambda a: syrk_3d(a, plan3, mesh3)).lower(a3))
formula = 1 * n1 * n2 / (c * p2) + n1 * n1 / (2 * p1)
emit(kernel="syrk", algo="3d", P=P3, n1=n1, n2=n2,
     measured_words=w, paper_W=formula, lower_bound=lb)
shard = flat_tb_size(plan3)
shard = -(-shard // p2)
s3 = jax.ShapeDtypeStruct((p1, p2, shard), jnp.float32)
lb2 = memory_independent_lower_bound(n1, n2, P3, 2).bound
w = wire_words(jax.jit(lambda s, b: symm_3d(s, b, plan3, mesh3))
               .lower(s3, a3))
emit(kernel="symm", algo="3d", P=P3, n1=n1, n2=n2,
     measured_words=w, paper_W=2 * n1 * n2 / (c * p2) + n1 * n1 / (2 * p1),
     lower_bound=lb2)

print(json.dumps(rows))
"""


def rows() -> List[dict]:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"  # fake devices: never the chip
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> List[dict]:
    data = rows()
    print(f"{'kernel':7s}{'algo':5s}{'P':>4s}{'n1':>7s}{'n2':>7s}"
          f"{'measured':>12s}{'paper W':>12s}{'bound':>12s}"
          f"{'meas/W':>8s}")
    for d in data:
        print(f"{d['kernel']:7s}{d['algo']:5s}{d['P']:4d}{d['n1']:7d}"
              f"{d['n2']:7d}{d['measured_words']:12.0f}"
              f"{d['paper_W']:12.0f}{d['lower_bound']:12.0f}"
              f"{d['measured_words']/max(d['paper_W'],1e-9):8.3f}")
    return data


if __name__ == "__main__":
    main()

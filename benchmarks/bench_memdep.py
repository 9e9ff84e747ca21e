"""§Memdep — the memory-dependent route (Algs 16-18, §IX) vs Cor 6-8.

Sweeps the per-device budget M and lets ``choose_algorithm`` pick the
plan: small budgets force the streamed 3d-limited schedule (column
chunk b and replication degree p₂ shrink with M), large budgets
collapse into the unlimited-memory 3D optimum.  For each executable
plan the schedule is lowered on its mesh and the collective WIRE words
are measured from the compiled HLO (ring model, §III-B2a) against the
paper's tradeoff
   W(x) ≈ m·n1·n2/(c·p2) + x·n1²/(2·P),   x = p2
and the Cor 6-8 memory-dependent lower bound; wall-clock medians run
through the public ``blas.syrk(..., M=M)`` route.

Runs in a SUBPROCESS with a fake multi-device CPU so this process keeps
one device (the dryrun rule).  Rows land in repo-root BENCH_memdep.json
(full grid) or artifacts/BENCH_memdep_small.json (CI smoke).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (n1, n2, P) of the sweep and the budgets (f32 words/device) probed.
#: The M points are chosen so the dispatcher's plan walks the whole
#: tradeoff on a 24-device grid: c=3×2 replicated, c=2×4 replicated,
#: then the memory-independent 3D plan once the working set fits.
_SHAPE = (48, 64, 24)
_SWEEP_FULL = (100, 120, 160, 200, 640, None)
_SWEEP_SMALL = (100, 160, 640)

_CHILD = r"""
import json, statistics, sys, time
import numpy as np
import jax, jax.numpy as jnp

from repro import blas
from repro.analysis.hlo_cost import analyze_hlo
from repro.compat import make_mesh
from repro.blas.meshpath import (REP_AXIS, TB_AXIS, _limited_steps,
                                 _mesh_3d)
from repro.core.lower_bounds import memory_dependent_parallel_lower_bound
from repro.core.threedim import syrk_3d, syrk_3d_limited
from repro.core.twodim import make_2d_plan

cfg = json.loads(sys.argv[1])
n1, n2, Ptot = cfg["shape"]
reps = cfg["reps"]
mesh = make_mesh((Ptot,), ("x",))
A = jnp.asarray(np.random.default_rng(0).standard_normal((n1, n2)),
                jnp.float32)

rows = []
for M in cfg["sweep"]:
    r = blas.plan_route("syrk", n1, n2, mesh=mesh, M=M)
    row = {"M": M, "P": Ptot, "n1": n1, "n2": n2, "route": r.path}
    if r.choice is not None:
        row.update(kind=r.choice.kind, c=r.choice.c, p1=r.choice.p1,
                   p2=r.choice.p2, b=r.choice.b)
    if r.path in ("3d", "3d-limited"):
        c, p2 = r.choice.c, r.choice.p2
        p1 = c * (c + 1)
        mesh3 = _mesh_3d(mesh, p1, p2)
        if r.path == "3d-limited":
            bw, nsteps = _limited_steps(n2, p2, r.choice.b)
            plan_b = make_2d_plan(c, n1, bw)
            spec = jax.ShapeDtypeStruct(
                (p1, p2, nsteps, c, plan_b.nb, plan_b.w), jnp.float32)
            fn = jax.jit(lambda x: syrk_3d_limited(x, plan_b, mesh3,
                                                   TB_AXIS, REP_AXIS))
        else:
            plan_b = make_2d_plan(c, n1, n2 // p2)
            spec = jax.ShapeDtypeStruct(
                (p1, p2, c, plan_b.nb, plan_b.w), jnp.float32)
            fn = jax.jit(lambda x: syrk_3d(x, plan_b, mesh3,
                                           TB_AXIS, REP_AXIS))
        hlo = fn.lower(spec).compile().as_text()
        words = analyze_hlo(hlo).collective_wire_bytes / 4.0
        model = n1 * n2 / (c * p2) + n1 * n1 / (2 * p1)
        row.update(measured_words=words, model_W=model,
                   ratio=round(words / model, 3),
                   within_2x=bool(words <= 2.0 * model))
        if M is not None:
            lb = memory_dependent_parallel_lower_bound(n1, n2, Ptot, M, 1)
            row["memdep_bound"] = max(lb, 0.0)
    # wall-clock through the public route (packed fill: the wire format)
    run = jax.jit(lambda x: blas.syrk(x, fill="packed", mesh=mesh, M=M))
    jax.block_until_ready(run(A))          # compile
    jax.block_until_ready(run(A))          # dedicated warmup rep
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(A))
        times.append(time.perf_counter() - t0)
    row.update(wall_s=float(statistics.median(times)), reps=reps,
               timer="median")
    rows.append(row)
print(json.dumps(rows))
"""


def rows(grid: str = "full") -> List[dict]:
    sweep = _SWEEP_FULL if grid == "full" else _SWEEP_SMALL
    cfg = {"shape": list(_SHAPE), "sweep": list(sweep), "reps": 7}
    env = dict(os.environ)
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={_SHAPE[2]}"
    env["JAX_PLATFORMS"] = "cpu"  # fake devices: never the chip
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", _CHILD, json.dumps(cfg)],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def main(grid: str = "full") -> List[dict]:
    data = rows(grid)
    print(f"{'M':>6s}{'route':>12s}{'c':>3s}{'p2':>4s}{'b':>4s}"
          f"{'measured':>10s}{'model W':>10s}{'ratio':>7s}"
          f"{'memdep LB':>11s}{'wall ms':>9s}")
    for d in data:
        mw = d.get("measured_words")
        cells = [f"{str(d['M']):>6s}", f"{d['route']:>12s}",
                 f"{d.get('c', '-'):>3}", f"{d.get('p2', '-'):>4}",
                 f"{d.get('b', '-'):>4}"]
        if mw is not None:
            lb = d.get("memdep_bound")
            cells += [f"{mw:10.0f}", f"{d['model_W']:10.0f}",
                      f"{d['ratio']:7.2f}",
                      f"{lb:11.0f}" if lb is not None else f"{'-':>11s}"]
        else:
            cells += [f"{'-':>10s}", f"{'-':>10s}", f"{'-':>7s}",
                      f"{'-':>11s}"]
        print("".join(cells) + f"{d['wall_s']*1e3:9.2f}")
    bad = [d for d in data if d.get("within_2x") is False]
    assert not bad, f"measured wire exceeds 2x the §IX model: {bad}"
    if grid == "full":
        out = os.path.join(ROOT, "BENCH_memdep.json")
    else:
        os.makedirs(os.path.join(ROOT, "artifacts"), exist_ok=True)
        out = os.path.join(ROOT, "artifacts", "BENCH_memdep_small.json")
    with open(out, "w") as f:
        json.dump(data, f, indent=1)
    print(f"[memdep] {len(data)} rows ({grid} grid) -> {out}")
    return data


if __name__ == "__main__":
    main()

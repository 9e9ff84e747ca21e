"""§Faults — ABFT checksum overhead on the packed mesh wire.

The resilience layer (distributed/resilience.py) verifies every packed
collective against the prefix-form SYRK identity
Σ_{j≤i} C[i,j] = a_i·(Σ_{j≤i} a_j) — an O(n) checksum word riding the
O(n²/2P) payload, so the check must be nearly free.  This suite measures exactly that: per mesh route, the median
wall-clock of the plain packed collective vs the ABFT-checked wrapper
(:func:`~repro.distributed.resilience.checked_syrk`), with the
overhead ratio landing in the gated row.

  * the n=2048 / P=8 SYRK rows (1d + ring wires) are the acceptance
    line: ``checked/plain − 1 ≤ 5%`` (``check_faults_gate``);
  * 2d / 3d / 3d-limited rows track the c(c+1) wire family;
  * one repair row times the full detect → localize → recompute cycle
    under an injected single-device bitflip (not gated — it pays a
    deliberate recompute — but recorded so repair cost is visible in
    the trajectory).

Rows land in repo-root BENCH_faults.json (full grid, the cross-PR
trajectory) or artifacts/BENCH_faults_small.json (CI smoke, 8 fake
devices via XLA_FLAGS=--xla_force_host_platform_device_count=8).
"""
from __future__ import annotations

import json
import os
import statistics
import time
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (route, n1, n2, route_kwargs_builder) grids; the 1d n=2048 row is
#: the gated acceptance point from the ISSUE
_GRID_FULL = ((("1d",), 2048, 512), (("ring",), 2048, 512),
              (("2d",), 1024, 256), (("3d", "3d-limited"), 1024, 256))
_GRID_SMALL = ((("1d",), 2048, 512), (("ring",), 1024, 256),
               (("2d",), 512, 128))


def _median(fn, repeats: int) -> float:
    fn()                                       # compile
    fn()                                       # dedicated warmup rep
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(statistics.median(times))


def _paired(fn_plain, fn_checked, repeats: int):
    """Interleaved timing of the plain/checked pair.  The gated
    quantity is a few-percent overhead on a ~100ms collective, well
    inside run-to-run drift of back-to-back medians — so time the two
    sides in adjacent reps and take the median of the *per-pair*
    overhead ratios, which cancels any drift common to both."""
    for fn in (fn_plain, fn_checked):
        fn()                                   # compile
        fn()                                   # dedicated warmup rep
    plain, checked = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn_plain()
        plain.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_checked()
        checked.append(time.perf_counter() - t0)
    ratios = sorted(c / p for p, c in zip(plain, checked))
    return (float(statistics.median(plain)),
            float(statistics.median(checked)),
            float(statistics.median(ratios)) - 1.0)


def main(grid: str = "full", repeats: int = 9) -> List[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.compat import make_mesh
    from repro.distributed import faults
    from repro.distributed.resilience import checked_syrk, route_runner

    ndev = jax.device_count()
    if ndev < 8:
        print(f"[faults] needs 8 devices (have {ndev}) — no rows "
              "(run with XLA_FLAGS=--xla_force_host_platform_device_"
              "count=8)")
        return []
    mesh8 = make_mesh((8,), ("x",))
    mesh6 = make_mesh((6,), ("x",))
    route_kw = {
        "1d": dict(mesh=mesh8, axis="x"),
        "ring": dict(mesh=mesh8, axis="x"),
        "2d": dict(mesh=mesh6, axis="x", c=2),
        "3d": dict(mesh=mesh8, c=2, p2=1),
        "3d-limited": dict(mesh=mesh8, c=2, p2=1, chunk=128),
    }
    rng = np.random.default_rng(9)
    rows = []
    for routes, n1, n2 in (_GRID_FULL if grid == "full" else _GRID_SMALL):
        a = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        for route in routes:
            kw = route_kw[route]
            run = route_runner("syrk", route, **kw)
            plain_s, checked_s, overhead = _paired(
                lambda: jax.block_until_ready(run(a)),
                lambda: jax.block_until_ready(checked_syrk(a, route=route,
                                                           **kw)[0]),
                repeats)
            row = {
                "op": "syrk", "route": route, "n1": n1, "n2": n2,
                "devices": int(np.prod(list(kw["mesh"].shape.values()))),
                "backend": jax.default_backend(),
                "plain_s": plain_s, "checked_s": checked_s,
                "overhead": round(overhead, 4),
                "reps": repeats, "timer": "paired-median",
            }
            rows.append(row)
            print(f"[faults] syrk {route:>10} n={n1:<5} plain "
                  f"{plain_s*1e3:7.2f}ms  checked {checked_s*1e3:7.2f}ms"
                  f"  overhead {row['overhead']*100:+.2f}%")

    # repair cost under an injected bitflip: detect -> localize ->
    # recompute (times=1 per call, so every timed rep pays one full
    # detect+retry cycle) — recorded, not gated
    n1, n2 = (1024, 256) if grid == "full" else (512, 128)
    a = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)

    def repair_once():
        with faults.inject(faults.FaultSpec(
                site="collective:syrk", kind="bitflip", device=5),
                seed=1):
            out, rep = checked_syrk(a, route="1d", backoff=0.0,
                                    **route_kw["1d"])
        assert rep.detected and rep.action == "retry"
        return jax.block_until_ready(out)

    repair_s = _median(repair_once, repeats)
    rows.append({"op": "syrk", "route": "1d+repair", "n1": n1, "n2": n2,
                 "devices": 8, "backend": jax.default_backend(),
                 "checked_s": repair_s, "reps": repeats,
                 "timer": "median"})
    print(f"[faults] syrk 1d detect+recompute n={n1}: "
          f"{repair_s*1e3:7.2f}ms")

    if grid == "full":
        out = os.path.join(ROOT, "BENCH_faults.json")
    else:
        os.makedirs(os.path.join(ROOT, "artifacts"), exist_ok=True)
        out = os.path.join(ROOT, "artifacts", "BENCH_faults_small.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"[faults] {len(rows)} rows ({grid} grid) -> {out}")
    return rows


if __name__ == "__main__":
    main()

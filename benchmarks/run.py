"""Benchmark orchestrator — one suite per paper table.

    PYTHONPATH=src python -m benchmarks.run [--only seq,parallel,...]

Suites:
  seq       Cor 3-5   sequential reads vs bounds (exact constants)
  parallel  Cor 10-12 1D/2D/3D collective words vs bounds
  memdep    Cor 6-8   limited-memory tradeoff (Algs 16-18)
  kernels   Pallas kernels: correctness + triangular-tiling traffic
  roofline  40-cell dry-run roofline table (reads artifacts/*.jsonl)
  persist   packed-native checkpoints: bytes + save/restore wall-clock
  serve     serving load test: Gram/whitening cache on vs off
            (tokens/s + p99, gated by check_serve_gate)
  faults    ABFT checksum overhead per packed mesh route (needs 8 fake
            devices; <=5% on the largest 1d SYRK row, gated by
            check_faults_gate)

Each suite prints its table and the JSON rows land in
artifacts/bench_<suite>.json for EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SUITES = ("seq", "parallel", "memdep", "kernels", "roofline", "persist",
          "serve", "faults")

#: fixed fwd+bwd shape grid for the BENCH_blas.json trajectory — the
#: original four rows stay byte-identical in (op, n1, n2, fill) so
#: wall-clock rows remain comparable across PRs; the added rows cover
#: the packed fill, the beta-accumulate epilogue, and >=1024 shapes
#: where the ~2x storage win is visible in the movement columns.
#: Each entry: (op, n1, n2, fill, accumulate).
_BLAS_GRID = (
    ("syrk", 128, 256, "tril", False),
    ("syrk", 256, 128, "tril", False),
    ("syr2k", 128, 256, "tril", False),
    ("symm", 128, 128, None, False),
    # packed + accumulate epilogues
    ("syrk", 128, 256, "packed", False),
    ("syrk", 128, 256, "packed", True),
    ("syr2k", 128, 256, "packed", False),
    # large points (>=1024): storage-bound regime
    ("syrk", 1024, 1024, "tril", False),
    ("syrk", 1024, 1024, "packed", False),
    ("syrk", 1024, 1024, "packed", True),
    ("syr2k", 1024, 512, "packed", False),
    ("symm", 1024, 512, None, False),
)

_LARGE_N1 = 1024

#: mesh-route grid: (op, n1, n2, fill, expected_route, devices).  The
#: 1d/2d rows are the CI smoke set (12 fake devices cover them); the 3d
#: row needs the full 12-device p1×p2 embed and only runs on the full
#: grid.  Shapes are chosen so plan_route really picks the named
#: schedule (asserted into the row, not assumed).
_BLAS_MESH_GRID = (
    ("syrk", 64, 256, "packed", "1d", 4),
    ("syr2k", 64, 256, "packed", "1d", 4),
    ("symm", 64, 256, None, "1d", 4),
    ("syrk", 96, 12, "packed", "2d", 6),
    ("symm", 96, 12, None, "2d", 6),
    ("syrk", 24, 8, "packed", "3d", 12),
    ("syrk", 256, 256, "packed", "ring", 4),
    ("syr2k", 256, 256, "packed", "ring", 4),
)


def _tril_words(n: int) -> int:
    return n * (n + 1) // 2


def _movement_estimate(op, n1, n2, fill, accumulate):
    """Analytic words-moved / peak-live estimate for one call (f32
    words; x4 for bytes).  Output words follow the storage format:
    packed moves ~n²/2 — the paper's symmetric-storage bound — while
    tril/full move the dense n².  The packed Pallas path has no dense
    intermediate, so peak-live is inputs + packed output."""
    if op == "symm":
        in_w = _tril_words(n1) + n1 * n2      # packed A tiles + dense B
        out_w = n1 * n2
        dense_out = n1 * n2
    else:
        m = 1 if op == "syrk" else 2
        in_w = m * n1 * n2
        out_w = _tril_words(n1) if fill == "packed" else n1 * n1
        dense_out = n1 * n1
    if accumulate:
        in_w += out_w                          # the streamed C0
    return {
        "moved_words": in_w + out_w,
        "out_words": out_w,
        "dense_out_words": dense_out,
        "peak_live_words": in_w + out_w,
        "storage_saving": round(dense_out / out_w, 3),
    }


def _median_timer(fn, args, repeats: int):
    """Median wall-clock over ``repeats`` timed reps, after one compile
    call and one *dedicated warmup rep* per variant.

    min-of-3-with-shared-warmup let several rows report
    ``fwd_bwd_s < fwd_s`` (the first post-compile call still pays
    allocator/cache effects and min() then keyed on one lucky rep);
    median over >=5 warmed reps makes the cross-PR trajectory
    trustworthy.  Returns the median in seconds."""
    import statistics
    import jax

    jax.block_until_ready(fn(*args))          # compile
    jax.block_until_ready(fn(*args))          # dedicated warmup rep
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return float(statistics.median(times))


def bench_blas_fwd_bwd(repeats: int = 7, grid: str = "full"):
    """Wall-clock of blas forward and value_and_grad over a fixed shape
    grid, plus analytic bytes-moved / peak-live columns; rows land in
    repo-root BENCH_blas.json so the bench trajectory accumulates
    across PRs.  ``grid="small"`` keeps only the sub-1024 rows (the CI
    smoke configuration)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import blas
    from repro.compat import make_mesh

    rng = np.random.default_rng(0)
    rows = []
    for op, n1, n2, fill, accumulate in _BLAS_GRID:
        if grid == "small" and n1 >= _LARGE_N1:
            continue
        a = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        s = jnp.asarray(rng.standard_normal((n1, n1)), jnp.float32)
        kw = {} if fill is None else dict(fill=fill)
        if op == "syrk":
            if accumulate:
                c0 = blas.syrk(b, **kw)
                fwd = jax.jit(lambda x, c: blas.syrk(x, c=c, **kw))
                loss = jax.jit(jax.value_and_grad(
                    lambda x, c: blas.syrk(x, c=c, **kw).sum(),
                    argnums=(0, 1)))
                args = (a, c0)
            else:
                fwd = jax.jit(lambda x: blas.syrk(x, **kw))
                loss = jax.jit(jax.value_and_grad(
                    lambda x: blas.syrk(x, **kw).sum()))
                args = (a,)
        elif op == "syr2k":
            fwd = jax.jit(lambda x, y: blas.syr2k(x, y, **kw))
            loss = jax.jit(jax.value_and_grad(
                lambda x, y: blas.syr2k(x, y, **kw).sum(),
                argnums=(0, 1)))
            args = (a, b)
        else:
            fwd = jax.jit(lambda x, y: blas.symm(x, y))
            loss = jax.jit(jax.value_and_grad(
                lambda x, y: blas.symm(x, y).sum(), argnums=(0, 1)))
            args = (s, b)

        row = {
            "op": op, "n1": n1, "n2": n2,
            "fill": fill or "n/a", "accumulate": accumulate,
            "backend": jax.default_backend(),
            "fwd_s": _median_timer(fwd, args, repeats),
            "fwd_bwd_s": _median_timer(loss, args, repeats),
            "reps": repeats, "timer": "median",
        }
        row.update(_movement_estimate(op, n1, n2, fill, accumulate))
        rows.append(row)
    if grid == "full":
        out = os.path.join(ROOT, "BENCH_blas.json")
    else:
        # the committed repo-root file is the full-grid cross-PR
        # trajectory; a small-grid (CI smoke) run must not truncate it
        os.makedirs(os.path.join(ROOT, "artifacts"), exist_ok=True)
        out = os.path.join(ROOT, "artifacts", "BENCH_blas_small.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"[blas fwd+bwd] {len(rows)} rows ({grid} grid) -> {out}")
    return rows


def _mesh_movement_estimate(op, n1, n2, fill, path, P):
    """Analytic wire words (collective traffic) and per-device peak-live
    words for one mesh-routed call (f32 words; ×4 for bytes).

    ``wire_out_words`` is what the symmetric result/operand moves across
    the mesh boundary: the packed triangle (~n²/2) on every packed
    route, versus the n² a dense gather (the pre-packed-wire
    ``assemble_sym``) used to move.  ``per_device_words`` is the owned
    share: operand column/row shards plus the ~n²/(2P) extended
    triangle block — the paper's per-processor memory bound."""
    m = 1 if op == "syrk" else 2
    L = _tril_words(n1)
    packed_out = L if (fill == "packed" or op == "symm") else n1 * n1
    if path == "1d":
        wire = int((1 - 1 / P) * L) * (2 if op == "symm" else 1)
        per_dev = m * n1 * n2 // P + L
    elif path == "2d":
        import math
        c = int((math.isqrt(4 * P + 1) - 1) // 2)      # P = c(c+1)
        nb = -(-n1 // (c * c))
        T = c * (c - 1) // 2
        wire = int(m * (n1 * n2 / c) * (1 - 1 / P)) + L
        per_dev = (T + 1) * nb * nb + m * c * nb * (-(-n2 // (c + 1)))
    elif path == "ring":
        from repro.core.dispatch import ring_nb, ring_working_set
        nb = ring_nb(n1, P)
        # floor(P/2) shifts of the m operand row block(s) + the packed
        # result gather — the 1d-route collective scale
        wire = m * (P // 2) * nb * n2 + L
        per_dev = int(ring_working_set(n1, n2, P, m))
    else:                                              # 3d
        wire = int(m * n1 * n2 / (P ** 0.5)) + L
        per_dev = _tril_words(n1) // P + m * n1 * n2 // P
    return {
        "wire_out_words": packed_out,
        "dense_wire_words": n1 * n1,
        "collective_words": wire,
        "per_device_peak_live_words": per_dev,
        "wire_saving": round(n1 * n1 / packed_out, 3),
    }


def bench_blas_mesh(repeats: int = 7, grid: str = "full"):
    """Wall-clock + wire-traffic rows for the packed mesh routes.

    Needs fake (or real) devices: rows whose mesh does not fit the
    available device count are skipped with a note.  ``grid="small"``
    keeps the 1d/2d rows (the CI smoke set, 12 fake devices via
    XLA_FLAGS=--xla_force_host_platform_device_count).  Rows land in
    BENCH_blas_mesh.json (repo root, full grid) or
    artifacts/BENCH_blas_mesh_small.json (small grid)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import blas
    from repro.compat import make_mesh

    ndev = jax.device_count()
    rng = np.random.default_rng(1)
    rows = []
    for op, n1, n2, fill, path, need in _BLAS_MESH_GRID:
        if grid == "small" and path == "3d":
            continue
        if ndev < need:
            print(f"[blas mesh] skip {op}[{n1}x{n2}] {path}: needs "
                  f"{need} devices, have {ndev}")
            continue
        mesh = make_mesh((need,), ("x",))
        a = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        kw = {} if fill is None else dict(fill=fill)
        if op == "syrk":
            fwd = jax.jit(lambda x: blas.syrk(x, mesh=mesh, **kw))
            loss = jax.jit(jax.value_and_grad(
                lambda x: blas.syrk(x, mesh=mesh, **kw).sum()))
            args = (a,)
        elif op == "syr2k":
            fwd = jax.jit(lambda x, y: blas.syr2k(x, y, mesh=mesh, **kw))
            loss = jax.jit(jax.value_and_grad(
                lambda x, y: blas.syr2k(x, y, mesh=mesh, **kw).sum(),
                argnums=(0, 1)))
            args = (a, b)
        else:
            tt = blas.TriTiles.from_tril(
                jnp.tril(jnp.asarray(rng.standard_normal((n1, n1)),
                                     jnp.float32)), 16)
            fwd = jax.jit(lambda t, y: blas.symm(
                blas.TriTiles(t, n1, 16), y, mesh=mesh))
            loss = jax.jit(jax.value_and_grad(
                lambda t, y: blas.symm(blas.TriTiles(t, n1, 16), y,
                                       mesh=mesh).sum(), argnums=(0, 1)))
            args = (tt.tiles, b)
        planned = blas.plan_route(op, n1, n2, mesh=mesh)

        from repro.analysis.hlo_cost import analyze_hlo
        hc = analyze_hlo(fwd.lower(*args).compile().as_text())
        ch = planned.choice
        row = {
            "op": op, "n1": n1, "n2": n2, "fill": fill or "tritiles",
            "devices": need, "route": planned.path,
            "route_expected": path,
            # the planner's grid choice, recorded so a re-plan drift
            # (different case / c / p2 / chunk at the same shape) shows
            # up in the trajectory diff, not just in wall-clock
            "case": ch.case if ch is not None else None,
            "c": ch.c if ch is not None else None,
            "p2": ch.p2 if ch is not None else None,
            "chunk": ch.b if ch is not None else None,
            "backend": jax.default_backend(),
            # per-device HLO cost of the compiled forward (SPMD: every
            # device runs this module once)
            "flops": hc.flops,
            "collective_permutes":
                hc.collective_counts.get("collective-permute", 0),
            "fwd_s": _median_timer(fwd, args, repeats),
            "fwd_bwd_s": _median_timer(loss, args, repeats),
            "reps": repeats, "timer": "median",
        }
        row.update(_mesh_movement_estimate(op, n1, n2, fill,
                                           planned.path, need))
        rows.append(row)
    if not rows:
        print("[blas mesh] no rows (single device?) — nothing written")
        return rows
    if grid == "full":
        out = os.path.join(ROOT, "BENCH_blas_mesh.json")
    else:
        os.makedirs(os.path.join(ROOT, "artifacts"), exist_ok=True)
        out = os.path.join(ROOT, "artifacts", "BENCH_blas_mesh_small.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"[blas mesh] {len(rows)} rows ({grid} grid) -> {out}")
    return rows


def check_packed_gate(rows, threshold: float = 2.0) -> bool:
    """The bench-regression gate: at the largest shape(s) where both a
    packed and a tril row of the same (op, n1, n2, accumulate) exist,
    packed ``fwd_bwd_s`` must stay within ``threshold``× of tril's.

    Every comparable pair at the maximal n1·n2 is checked and the gate
    fails on the WORST ratio (a single max-by-area pick let a
    regression in one op hide behind a healthy tie-mate on the small
    grid).  This is the regression the slice-granular converters fixed
    (packed backward was ~30× tril at n=1024 under the element-table
    converters); the gate keeps it fixed.  Returns True when the gate
    passes (or no comparable pair exists).  Mesh-row files (no
    tril/packed pairs) hit the skip path gracefully."""
    by_key = {(r["op"], r["n1"], r["n2"], r.get("accumulate", False),
               r["fill"]): r for r in rows}
    pairs = []
    for (op, n1, n2, acc, fill), r in by_key.items():
        if fill != "packed":
            continue
        tril = by_key.get((op, n1, n2, acc, "tril"))
        if tril is not None:
            pairs.append((n1 * n2, r, tril))
    if not pairs:
        print("[gate] no packed/tril row pair to compare — skipping")
        return True
    top = max(area for area, _, _ in pairs)
    ok = True
    for _, packed, tril in (p for p in pairs if p[0] == top):
        ratio = packed["fwd_bwd_s"] / tril["fwd_bwd_s"]
        verdict = "OK" if ratio <= threshold else "FAIL"
        ok = ok and ratio <= threshold
        print(f"[gate] {packed['op']}[{packed['n1']}x{packed['n2']}] "
              f"acc={packed.get('accumulate', False)} packed fwd_bwd "
              f"{packed['fwd_bwd_s']*1e3:.2f}ms vs tril "
              f"{tril['fwd_bwd_s']*1e3:.2f}ms: ratio {ratio:.2f} "
              f"(threshold {threshold}) {verdict}")
    return ok


def check_serve_gate(rows) -> bool:
    """Serving-cache regression gate: the cache_on row (async packed
    Gram/whitening cache) must not serve worse than the cache_off row
    (from-scratch Gram + eigh per request on the hot loop) — tokens/s
    not lower AND p99 latency not higher (2% slack for timer noise on
    tokens/s; p99 is the headline and gets none).  Also trips if the
    prefill bucket ladder compiled mid-serve (compiles beyond the
    precompiled ladder).  Skips gracefully when either row is missing."""
    by_mode = {r.get("mode"): r for r in rows}
    on, off = by_mode.get("cache_on"), by_mode.get("cache_off")
    if on is None or off is None:
        print("[serve gate] need cache_on and cache_off rows — skipping")
        return True
    ok = True
    tps_ratio = on["tokens_per_s"] / off["tokens_per_s"]
    verdict = "OK" if tps_ratio >= 0.98 else "FAIL"
    ok = ok and tps_ratio >= 0.98
    print(f"[serve gate] tokens/s cache_on {on['tokens_per_s']:.1f} vs "
          f"cache_off {off['tokens_per_s']:.1f}: ratio {tps_ratio:.3f} "
          f"(threshold >= 0.98) {verdict}")
    p99_ratio = on["p99_latency_s"] / off["p99_latency_s"]
    verdict = "OK" if p99_ratio <= 1.0 else "FAIL"
    ok = ok and p99_ratio <= 1.0
    print(f"[serve gate] p99 cache_on {on['p99_latency_s']:.2f}s vs "
          f"cache_off {off['p99_latency_s']:.2f}s: ratio {p99_ratio:.3f} "
          f"(threshold <= 1.0) {verdict}")
    for r in (on, off):
        ladder = len(r.get("bucket_ladder", []))
        extra = r["prefill_compiles"] - ladder
        verdict = "OK" if extra <= 0 else "FAIL"
        ok = ok and extra <= 0
        print(f"[serve gate] {r['mode']} prefill compiles "
              f"{r['prefill_compiles']} vs ladder {ladder}: "
              f"mid-serve compiles {max(extra, 0)} {verdict}")
    return ok


def check_faults_gate(rows, threshold: float = 0.05) -> bool:
    """ABFT overhead gate: on the largest-n1 plain-vs-checked 1d SYRK
    row, the checksum must cost ≤ ``threshold`` of the plain collective
    (the O(n) word riding the O(n²/2P) payload — the ISSUE's 5% line).
    Repair rows (deliberate recomputes) are informational only.  Skips
    gracefully when no comparable row exists (too few devices)."""
    cand = [r for r in rows if r.get("route") == "1d"
            and "overhead" in r]
    if not cand:
        print("[faults gate] no 1d plain/checked row — skipping")
        return True
    row = max(cand, key=lambda r: r["n1"])
    ok = row["overhead"] <= threshold
    print(f"[faults gate] syrk 1d n={row['n1']} P={row['devices']} "
          f"checksum overhead {row['overhead']*100:+.2f}% "
          f"(threshold {threshold*100:.0f}%) {'OK' if ok else 'FAIL'}")
    return ok


def check_ring_flops_gate(n1: int = 2048, n2: int = 512) -> bool:
    """Computation-optimality gate for the ring route (compile-only, no
    timed reps): per-device HLO flops of ring SYRK at P=8 must stay
    ≤ 0.6× the 2d route's (c=2) at the same shape, and ring SYR2K
    ≤ 0.6× the 2d family's 2-pass rank-2k model (2× its SYRK flops;
    the shipped 2d syr2k one-dots its block-diagonal g + gᵀ — a saving
    the ring's slot 0 applies identically — so the measured-vs-measured
    syr2k ratio sits near the structural 16/24 floor and is tripwired
    at 0.7 instead).  Needs ≥ 8 devices; skips gracefully below."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.analysis.hlo_cost import analyze_hlo
    from repro.blas import meshpath
    from repro.compat import make_mesh

    if jax.device_count() < 8:
        print("[ring gate] needs 8 devices — skipping")
        return True
    rng = np.random.default_rng(5)
    A = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
    mesh8 = make_mesh((8,), ("x",))
    mesh6 = make_mesh((6,), ("x",))

    def flops(fn, *xs):
        return analyze_hlo(jax.jit(fn).lower(*xs).compile().as_text()).flops

    rf = flops(lambda x: meshpath.syrk_ring_packed(x, mesh8, "x"), A)
    tf = flops(lambda x: meshpath.syrk_2d_sharded(
        x, 2, mesh6, "x").to_packed(), A)
    rf2 = flops(lambda x, y: meshpath.syr2k_ring_packed(
        x, y, mesh8, "x"), A, B)
    tf2 = flops(lambda x, y: meshpath.syr2k_2d_sharded(
        x, y, 2, mesh6, "x").to_packed(), A, B)
    checks = [("syrk ring/2d", rf / tf, 0.6),
              ("syr2k ring/2-pass-2d", rf2 / (2 * tf), 0.6),
              ("syr2k ring/2d", rf2 / tf2, 0.7)]
    ok = True
    for name, ratio, thr in checks:
        verdict = "OK" if ratio <= thr else "FAIL"
        ok = ok and ratio <= thr
        print(f"[ring gate] {name} per-device flops ratio "
              f"{ratio:.4f} (threshold {thr}) {verdict}")
    return ok


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: "
                         + ",".join(SUITES) + ",blas,blas_mesh ('blas' = "
                         "the BENCH_blas.json fwd+bwd grid + mesh rows; "
                         "'blas_mesh' = only the mesh rows and the ring "
                         "flop gate)")
    ap.add_argument("--grid", default="full", choices=("full", "small"),
                    help="blas grid size: 'small' drops the >=1024 rows "
                         "(CI smoke)")
    ap.add_argument("--mesh", default="on", choices=("on", "off", "only"),
                    help="mesh-route rows need fake devices "
                         "(XLA_FLAGS=--xla_force_host_platform_device_"
                         "count=12), which contaminates single-device "
                         "timings — run the two grids in SEPARATE "
                         "processes: '--mesh off' (no flags) for the "
                         "single-device grid, '--mesh only' (with flags) "
                         "for the mesh rows")
    ap.add_argument("--gate", action="store_true",
                    help="bench-regression gate: fail if packed "
                         "fwd_bwd_s exceeds the threshold x tril at the "
                         "largest comparable shape of the grid just run")
    ap.add_argument("--gate-threshold", type=float, default=2.0)
    ap.add_argument("--check-gate", default=None, metavar="JSON",
                    help="apply the gate to an existing rows file and "
                         "exit (no benchmarks are run)")
    args = ap.parse_args()
    if args.gate and args.mesh == "only":
        ap.error("--gate needs the single-device grid; it cannot run "
                 "with --mesh only (use --check-gate on an existing "
                 "rows file instead)")
    if args.check_gate:
        with open(args.check_gate) as f:
            rows = json.load(f)
        # dispatch on the rows file: each suite gates a different thing
        base = os.path.basename(args.check_gate)
        if "faults" in base:
            ok = check_faults_gate(rows)
        elif "serve" in base:
            ok = check_serve_gate(rows)
        else:
            ok = check_packed_gate(rows, args.gate_threshold)
        sys.exit(0 if ok else 1)
    tokens = args.only.split(",") if args.only else None
    chosen = list(tokens) if tokens else list(SUITES)
    chosen = [c for c in chosen if c not in ("blas", "blas_mesh")]
    if args.mesh == "only":
        chosen = []
    # 'blas_mesh' selects only the mesh rows (+ the ring flop gate);
    # without --only both blas grids run as before
    run_blas = tokens is None or "blas" in tokens
    run_mesh = tokens is None or "blas" in tokens or "blas_mesh" in tokens

    os.makedirs(os.path.join(ROOT, "artifacts"), exist_ok=True)
    failures = 0
    if args.mesh != "only" and run_blas:
        try:
            rows = bench_blas_fwd_bwd(grid=args.grid)  # the trajectory
            if args.gate and not check_packed_gate(rows,
                                                   args.gate_threshold):
                print("[blas fwd+bwd] bench-regression gate FAILED")
                failures += 1
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"[blas fwd+bwd] FAILED: {e}")
            failures += 1
    if args.mesh != "off" and run_mesh:
        try:
            bench_blas_mesh(grid=args.grid)     # packed mesh wire rows
            if not check_ring_flops_gate():
                print("[blas mesh] ring flop gate FAILED")
                failures += 1
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"[blas mesh] FAILED: {e}")
            failures += 1
    for name in chosen:
        mod = __import__(f"benchmarks.bench_{'seq_bounds' if name == 'seq' else 'parallel_comm' if name == 'parallel' else name}",  # noqa: E501
                         fromlist=["main"])
        print("\n" + "=" * 72)
        print(f"suite: {name}")
        print("=" * 72)
        t0 = time.time()
        try:
            # memdep's M-sweep, persist's n-sweep, and serve's request
            # grid have their own small/full grids (CI smoke writes
            # artifacts/, full runs the repo-root trajectory)
            rows = mod.main(grid=args.grid) \
                if name in ("memdep", "persist", "serve", "faults") \
                else mod.main()
            out = os.path.join(ROOT, "artifacts", f"bench_{name}.json")
            with open(out, "w") as f:
                json.dump(rows, f, indent=1, default=str)
            print(f"[{name}] {len(rows) if rows is not None else 0} rows "
                  f"in {time.time()-t0:.1f}s -> {out}")
            if name == "serve" and not check_serve_gate(rows):
                print("[serve] serve gate FAILED")
                failures += 1
            if name == "faults" and not check_faults_gate(rows):
                print("[faults] ABFT overhead gate FAILED")
                failures += 1
        except Exception as e:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            print(f"[{name}] FAILED: {e}")
            failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

"""Multi-device correctness checks for the parallel algorithms.

Run as a subprocess with a fake device count (tests must NOT set
XLA_FLAGS globally — see dryrun rules), e.g.::

    XLA_FLAGS=--xla_force_host_platform_device_count=12 \
        python tests/dist_checks.py --suite 2d --c 3

Prints ``OK <suite>`` on success; nonzero exit on failure.
"""
import argparse
import os
import sys

import numpy as np


def _mesh(shape, names):
    from repro.compat import make_mesh
    return make_mesh(shape, names)


def check_1d(P: int) -> None:
    import jax.numpy as jnp

    from repro.core.onedim import (pack_for_1d_symm, symm_1d, syr2k_1d,
                                   syrk_1d, unpack_1d_result)
    rng = np.random.default_rng(0)
    n1, n2 = 24, 8 * P
    A = rng.standard_normal((n1, n2)).astype(np.float32)
    B = rng.standard_normal((n1, n2)).astype(np.float32)
    mesh = _mesh((P,), ("x",))

    out = np.asarray(syrk_1d(jnp.asarray(A), mesh))
    got = unpack_1d_result(out, n1)
    np.testing.assert_allclose(got, np.tril(A @ A.T), rtol=2e-4, atol=2e-4)

    out = np.asarray(syr2k_1d(jnp.asarray(A), jnp.asarray(B), mesh))
    got = unpack_1d_result(out, n1)
    np.testing.assert_allclose(got, np.tril(A @ B.T + B @ A.T), rtol=2e-4,
                               atol=2e-4)

    S = rng.standard_normal((n1, n1)).astype(np.float32)
    S = np.tril(S) + np.tril(S, -1).T
    packed = pack_for_1d_symm(S, P)
    got = np.asarray(symm_1d(jnp.asarray(packed), jnp.asarray(B), n1, mesh))
    np.testing.assert_allclose(got, S @ B, rtol=2e-4, atol=2e-4)
    print(f"OK 1d P={P}")


def check_2d(c: int) -> None:
    import jax.numpy as jnp

    from repro.core.twodim import (assemble_sym, collect_rows, distribute_rows,
                                   distribute_sym, make_2d_plan, symm_2d,
                                   syr2k_2d, syrk_2d)
    P = c * (c + 1)
    rng = np.random.default_rng(1)
    n1, n2 = 4 * c * c, 3 * (c + 1)
    plan = make_2d_plan(c, n1, n2)
    A = rng.standard_normal((n1, n2)).astype(np.float32)
    B = rng.standard_normal((n1, n2)).astype(np.float32)
    mesh = _mesh((P,), ("x",))

    # the schedules are batch-native: (P, K, ...) with a stack of K = 1
    dist = distribute_rows(A, plan)
    assert np.allclose(collect_rows(dist, plan), A)
    a_dist = jnp.asarray(dist)[:, None]
    off, diag = syrk_2d(a_dist, plan, mesh)
    got = assemble_sym(np.asarray(off[:, 0]), np.asarray(diag[:, 0]), plan)
    np.testing.assert_allclose(got, np.tril(A @ A.T), rtol=2e-4, atol=2e-4)

    b_dist = jnp.asarray(distribute_rows(B, plan))[:, None]
    off, diag = syr2k_2d(a_dist, b_dist, plan, mesh)
    got = assemble_sym(np.asarray(off[:, 0]), np.asarray(diag[:, 0]), plan)
    np.testing.assert_allclose(got, np.tril(A @ B.T + B @ A.T), rtol=2e-4,
                               atol=2e-4)

    S = rng.standard_normal((n1, n1)).astype(np.float32)
    S = np.tril(S) + np.tril(S, -1).T
    s_off, s_diag = distribute_sym(S, plan)
    c_dist = symm_2d(jnp.asarray(s_off)[:, None], jnp.asarray(s_diag)[:, None],
                     b_dist, plan, mesh)
    got = collect_rows(np.asarray(c_dist[:, 0]), plan)
    np.testing.assert_allclose(got, S @ B, rtol=2e-4, atol=2e-4)
    print(f"OK 2d c={c} P={P}")


def check_3d(c: int, p2: int, nsteps: int) -> None:
    import jax.numpy as jnp

    from repro.blas.meshpath import (_chunk_cols_3d_jnp, _collect_cols_3d_jnp,
                                     _flat_from_sharded, _sharded_from_flat,
                                     collect_rows_3d_jnp,
                                     distribute_rows_3d_jnp)
    from repro.core.packing import ShardedTriTiles
    from repro.core.threedim import (symm_3d, symm_3d_limited, syr2k_3d,
                                     syr2k_3d_limited, syrk_3d,
                                     syrk_3d_limited)
    from repro.core.twodim import make_2d_plan

    p1 = c * (c + 1)
    rng = np.random.default_rng(2)
    n1 = 2 * c * c
    n2 = 2 * (c + 1) * p2 * max(nsteps, 1)
    n2s = n2 // p2
    A = rng.standard_normal((n1, n2)).astype(np.float32)
    B = rng.standard_normal((n1, n2)).astype(np.float32)
    S = rng.standard_normal((n1, n1)).astype(np.float32)
    S = np.tril(S) + np.tril(S, -1).T
    mesh = _mesh((p1, p2), ("tb", "rep"))

    if nsteps == 1:
        # the schedules are batch-native: a stack of K = 1
        plan = make_2d_plan(c, n1, n2s)
        a_dist = distribute_rows_3d_jnp(jnp.asarray(A)[None], plan, p2)
        out = syrk_3d(a_dist, plan, mesh)
        got = np.asarray(_sharded_from_flat(out, plan, n1, c).to_tril()[0])
        np.testing.assert_allclose(got, np.tril(A @ A.T), rtol=2e-4,
                                   atol=2e-4)
        b_dist = distribute_rows_3d_jnp(jnp.asarray(B)[None], plan, p2)
        out = syr2k_3d(a_dist, b_dist, plan, mesh)
        got = np.asarray(_sharded_from_flat(out, plan, n1, c).to_tril()[0])
        np.testing.assert_allclose(got, np.tril(A @ B.T + B @ A.T),
                                   rtol=2e-4, atol=2e-4)
        # SYMM 3D: triangle blocks in, column slices out
        st = ShardedTriTiles.from_tril(jnp.tril(jnp.asarray(S))[None], c)
        c_dist = symm_3d(_flat_from_sharded(st, p2), b_dist, plan, mesh)
        got = np.asarray(collect_rows_3d_jnp(c_dist, plan, p2)[0])
        np.testing.assert_allclose(got, S @ B, rtol=2e-4, atol=2e-4)
        print(f"OK 3d c={c} p2={p2}")
    else:
        # limited-memory variants (Algs 16-18): streamed b-column chunks
        bw = n2s // nsteps
        plan_b = make_2d_plan(c, n1, bw)
        a_ch = _chunk_cols_3d_jnp(jnp.asarray(A), plan_b, p2, nsteps)
        out = syrk_3d_limited(a_ch, plan_b, mesh)
        got = np.asarray(_sharded_from_flat(out, plan_b, n1, c).to_tril())
        np.testing.assert_allclose(got, np.tril(A @ A.T), rtol=2e-4,
                                   atol=2e-4)

        b_ch = _chunk_cols_3d_jnp(jnp.asarray(B), plan_b, p2, nsteps)
        out = syr2k_3d_limited(a_ch, b_ch, plan_b, mesh)
        got = np.asarray(_sharded_from_flat(out, plan_b, n1, c).to_tril())
        np.testing.assert_allclose(got, np.tril(A @ B.T + B @ A.T),
                                   rtol=2e-4, atol=2e-4)

        st = ShardedTriTiles.from_tril(jnp.tril(jnp.asarray(S)), c)
        c_out = symm_3d_limited(_flat_from_sharded(st, p2), b_ch, plan_b,
                                mesh)
        got = np.asarray(_collect_cols_3d_jnp(c_out, plan_b, p2, n2))
        np.testing.assert_allclose(got, S @ B, rtol=2e-4, atol=2e-4)
        print(f"OK 3d-limited c={c} p2={p2} nsteps={nsteps}")


def check_blas() -> None:
    """repro.blas mesh routing: each regime picks its comm-optimal path
    and matches the dense oracle (12 fake devices)."""
    import jax
    import jax.numpy as jnp

    from repro import blas
    rng = np.random.default_rng(7)

    def tri(x):
        return np.tril(np.asarray(x, np.float64)).astype(np.float32)

    # --- 1D: n2 >> n1, small P (Thm 9 case 1)
    mesh4 = _mesh((4,), ("x",))
    A = jnp.asarray(rng.standard_normal((16, 1024)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((16, 1024)), jnp.float32)
    r = blas.plan_route("syrk", 16, 1024, mesh=mesh4)
    assert r.path == "1d", r
    got = np.asarray(blas.syrk(A, mesh=mesh4))
    np.testing.assert_allclose(got, tri(np.asarray(A) @ np.asarray(A).T),
                               rtol=3e-4, atol=3e-4)
    got = np.asarray(blas.syr2k(A, B, mesh=mesh4))
    want = np.asarray(A) @ np.asarray(B).T
    np.testing.assert_allclose(got, np.tril(want + want.T), rtol=3e-4,
                               atol=3e-4)
    S = rng.standard_normal((16, 16)).astype(np.float32)
    sym = np.tril(S) + np.tril(S, -1).T
    got = np.asarray(blas.symm(jnp.asarray(S), B, mesh=mesh4))
    np.testing.assert_allclose(got, sym @ np.asarray(B), rtol=3e-4,
                               atol=3e-4)

    # --- 2D: n1 >> n2, P = c(c+1) = 6 (case 2)
    mesh6 = _mesh((6,), ("x",))
    A2 = jnp.asarray(rng.standard_normal((36, 6)), jnp.float32)
    r = blas.plan_route("syrk", 36, 6, mesh=mesh6)
    assert r.path == "2d" and r.choice.c == 2, r
    got = np.asarray(blas.syrk(A2, mesh=mesh6))
    np.testing.assert_allclose(got, tri(np.asarray(A2) @ np.asarray(A2).T),
                               rtol=3e-4, atol=3e-4)
    S2 = rng.standard_normal((36, 36)).astype(np.float32)
    sym2 = np.tril(S2) + np.tril(S2, -1).T
    B2 = jnp.asarray(rng.standard_normal((36, 6)), jnp.float32)
    got = np.asarray(blas.symm(jnp.asarray(S2), B2, mesh=mesh6))
    np.testing.assert_allclose(got, sym2 @ np.asarray(B2), rtol=3e-4,
                               atol=3e-4)

    # --- 3D: square-ish, P = 12 = 6 * 2 (case 3)
    mesh12 = _mesh((12,), ("x",))
    A3 = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    B3 = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    r = blas.plan_route("syrk", 16, 8, mesh=mesh12)
    assert r.path == "3d" and (r.choice.p1, r.choice.p2) == (6, 2), r
    got = np.asarray(blas.syrk(A3, mesh=mesh12))
    np.testing.assert_allclose(got, tri(np.asarray(A3) @ np.asarray(A3).T),
                               rtol=3e-4, atol=3e-4)
    got = np.asarray(blas.syr2k(A3, B3, mesh=mesh12))
    want = np.asarray(A3) @ np.asarray(B3).T
    np.testing.assert_allclose(got, np.tril(want + want.T), rtol=3e-4,
                               atol=3e-4)
    S3 = rng.standard_normal((16, 16)).astype(np.float32)
    sym3 = np.tril(S3) + np.tril(S3, -1).T
    got = np.asarray(blas.symm(jnp.asarray(S3), B3, mesh=mesh12))
    np.testing.assert_allclose(got, sym3 @ np.asarray(B3), rtol=3e-4,
                               atol=3e-4)

    # --- infeasible grids fall back without wrong answers
    mesh5 = _mesh((5,), ("x",))        # prime, no c(c+1) fit for 2d data
    A4 = jnp.asarray(rng.standard_normal((16, 10)), jnp.float32)
    got = np.asarray(blas.syrk(A4, mesh=mesh5))
    np.testing.assert_allclose(got, tri(np.asarray(A4) @ np.asarray(A4).T),
                               rtol=3e-4, atol=3e-4)

    # --- multi-axis mesh routes over the named axis (gram/muon pattern)
    mesh_dm = _mesh((3, 4), ("data", "model"))
    got = np.asarray(blas.syrk(A, mesh=mesh_dm, axis="model"))
    np.testing.assert_allclose(got, tri(np.asarray(A) @ np.asarray(A).T),
                               rtol=3e-4, atol=3e-4)
    print("OK blas")


def check_blas_grad() -> None:
    """jax.grad through the mesh routes (8 fake devices): gradients match
    the dense route for every op/fill, the backward of a mesh-routed
    SYRK demonstrably executes a mesh-routed SYMM (Route capture + HLO
    collective inspection, not just numerics), and muon/gram chains
    differentiate end-to-end on the 1D path."""
    import jax
    import jax.numpy as jnp

    from repro import blas
    rng = np.random.default_rng(11)
    TOL = dict(rtol=1e-4, atol=1e-5)
    mesh = _mesh((8,), ("x",))
    A = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    S = jnp.asarray(rng.standard_normal((16, 16)), jnp.float32)
    # fixed linear weights -> identical cotangents on every route, so the
    # parity tolerance measures the backward op itself, not forward
    # accumulation-order noise amplified through a nonlinearity
    W = {"tril": jnp.asarray(rng.standard_normal((16, 16)), jnp.float32),
         "full": jnp.asarray(rng.standard_normal((16, 16)), jnp.float32),
         "packed": jnp.asarray(rng.standard_normal(16 * 17 // 2),
                               jnp.float32)}

    def cmp(tree_a, tree_b):
        for x, y in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), **TOL)

    for fill in ("tril", "full", "packed"):
        lm = jax.grad(lambda x: jnp.sum(
            W[fill] * blas.syrk(x, fill=fill, mesh=mesh)))(A)
        ld = jax.grad(lambda x: jnp.sum(
            W[fill] * blas.syrk(x, fill=fill)))(A)
        cmp(lm, ld)
        lm = jax.grad(lambda x, y: jnp.sum(
            W[fill] * blas.syr2k(x, y, fill=fill, mesh=mesh)),
            argnums=(0, 1))(A, B)
        ld = jax.grad(lambda x, y: jnp.sum(
            W[fill] * blas.syr2k(x, y, fill=fill)), argnums=(0, 1))(A, B)
        cmp(lm, ld)
        print(f"  grad parity 1d vs dense: syrk/syr2k fill={fill}")
    WB = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    lm = jax.grad(lambda x, y: jnp.sum(
        WB * blas.symm(x, y, mesh=mesh)), argnums=(0, 1))(S, B)
    ld = jax.grad(lambda x, y: jnp.sum(
        WB * blas.symm(x, y)), argnums=(0, 1))(S, B)
    cmp(lm, ld)
    print("  grad parity 1d vs dense: symm")

    # nonlinear loss: forward accumulation noise propagates, so compare
    # at the forward tolerance of the mesh paths
    lm = jax.grad(lambda x: jnp.sum(jnp.sin(blas.syrk(x, mesh=mesh))))(A)
    ld = jax.grad(lambda x: jnp.sum(jnp.sin(blas.syrk(x))))(A)
    for x, y in zip(jax.tree.leaves(lm), jax.tree.leaves(ld)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=2e-3, atol=2e-4)
    print("  grad parity 1d vs dense: nonlinear loss")

    # jit'd grad agrees too (route pinned across fwd/bwd traces)
    gj = jax.jit(jax.grad(lambda x: jnp.sum(
        W["tril"] * blas.syrk(x, mesh=mesh))))(A)
    cmp(gj, jax.grad(lambda x: jnp.sum(W["tril"] * blas.syrk(x)))(A))
    print("  grad parity under jit")

    # batched operands on a mesh (stacked packed triangles on the 1D
    # wire) still differentiate and match the meshless gradient for
    # every fill (fixed linear weights, as above: a squared loss feeds
    # the forward's accumulation-order noise into the cotangent)
    Ab = jnp.asarray(rng.standard_normal((2, 16, 64)), jnp.float32)
    for fill in ("tril", "full", "packed"):
        Wb = jnp.stack([W[fill], -W[fill]])
        gm = jax.grad(lambda x: jnp.sum(
            Wb * blas.syrk(x, fill=fill, mesh=mesh)))(Ab)
        gd = jax.grad(lambda x: jnp.sum(
            Wb * blas.syrk(x, fill=fill)))(Ab)
        cmp(gm, gd)
    print("  grad parity for batched operands on the mesh")

    # the backward of a 1d syrk IS a 1d symm: Route capture ...
    with blas.capture_routes() as log:
        jax.grad(lambda x: jnp.sum(blas.syrk(x, mesh=mesh)))(A)
    planned = [(r.op, r.path) for r in log]
    assert ("syrk", "1d") in planned and ("symm", "1d") in planned, planned
    # ... and collective inspection of the backward HLO alone: the 1D
    # SYMM all-gathers the packed triangle; nothing reduce-scatters
    # (no forward SYRK replay hides in the backward).
    _, vjp = jax.vjp(lambda x: blas.syrk(x, mesh=mesh), A)
    bwd_hlo = jax.jit(vjp).lower(jnp.ones((16, 16), jnp.float32)).as_text()
    assert "all_gather" in bwd_hlo, "backward symm must all-gather"
    assert "reduce_scatter" not in bwd_hlo, \
        "backward must not replay the forward reduce-scatter"
    print("  backward of 1d syrk is a 1d symm (Route + HLO collectives)")

    # 2d route grads (P=6, c=2)
    mesh6 = _mesh((6,), ("x",))
    A2 = jnp.asarray(rng.standard_normal((36, 6)), jnp.float32)
    W2 = jnp.asarray(rng.standard_normal((36, 36)), jnp.float32)
    assert blas.plan_route("syrk", 36, 6, mesh=mesh6).path == "2d"
    cmp(jax.grad(lambda x: jnp.sum(W2 * blas.syrk(x, mesh=mesh6)))(A2),
        jax.grad(lambda x: jnp.sum(W2 * blas.syrk(x)))(A2))
    with blas.capture_routes() as log:
        jax.grad(lambda x: jnp.sum(blas.syrk(x, mesh=mesh6)))(A2)
    assert ("symm", "2d") in [(r.op, r.path) for r in log]
    print("  grad parity 2d vs dense: syrk (backward symm routed 2d)")

    # end-to-end integration: NS iteration and the decorrelation
    # penalty differentiate through the mesh-routed chain
    from repro.optim.gram import decorrelation_penalty
    from repro.optim.muon import ns_iteration_reference

    def cmp_loose(tree_a, tree_b):
        for x, y in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=2e-3, atol=2e-4)

    g1 = jax.grad(lambda x: decorrelation_penalty(x, mesh=mesh,
                                                  axis="x"))(A)
    g2 = jax.grad(lambda x: decorrelation_penalty(x))(A)
    cmp_loose(g1, g2)
    g1 = jax.grad(lambda x: jnp.sum(
        ns_iteration_reference(x, mesh=mesh, axis="x") ** 2))(A)
    g2 = jax.grad(lambda x: jnp.sum(ns_iteration_reference(x) ** 2))(A)
    cmp_loose(g1, g2)
    print("  muon NS + gram decorrelation differentiate on the 1d path")
    print("OK blas_grad")


#: call wrappers re-emit their inner jaxpr's outputs — counting them
#: would double-count a single materialization
_WRAPPER_PRIMS = ("custom_vjp", "custom_jvp", "pjit", "closed_call",
                  "core_call", "remat")


def _square_vars_on_wire(jaxpr, n):
    """All producing eqn outputs shaped (…, n, n) OUTSIDE shard_map
    bodies.  The mesh packed-wire contract is about the distributed
    data path: everything that crosses a device boundary or lives at
    the GSPMD level must be packed (~n²/2 words).  What happens inside
    a shard_map body is the algorithm's own per-device working set —
    e.g. the 1D schedules' local Gram / local unpack (Algs 7/9 do
    exactly that, in the regime where n₁ is the small dimension) — so
    bodies are excluded; the 2D/3D bodies only ever touch nb×nb
    blocks anyway."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if "shard_map" in name:
                continue                      # don't recurse into bodies
            if not any(w in name for w in _WRAPPER_PRIMS):
                for v in eqn.outvars:
                    sh = tuple(getattr(v.aval, "shape", ()))
                    if len(sh) >= 2 and sh[-1] == n and sh[-2] == n:
                        found.append((name, sh))
            for val in eqn.params.values():
                if hasattr(val, "jaxpr"):
                    walk(val.jaxpr)
                elif hasattr(val, "eqns"):
                    walk(val)

    walk(jaxpr.jaxpr)
    return found


def check_mesh_packed() -> None:
    """The packed triangle-block mesh wire (12 fake devices): packed ==
    dense parity for syrk/syr2k/symm on 1d/2d/3d (incl. batched stacks
    and non-multiple-of-bm n1), jaxpr proof that fill="packed" mesh
    routes move no n×n dense intermediate on the wire, and grad parity
    with packed cotangents end to end."""
    import jax
    import jax.numpy as jnp

    from repro import blas
    from repro.core.packing import ShardedTriTiles, TriTiles, tril_size

    rng = np.random.default_rng(21)
    TOL = dict(rtol=3e-4, atol=3e-4)

    def tril_np(x):
        return np.tril(np.asarray(x, np.float64)).astype(np.float32)

    def packed_np(x):
        t = tril_np(x)
        return t[np.tril_indices(t.shape[0])]

    def sym_np(s):
        return np.tril(s) + np.tril(s, -1).T

    # ---- 1d (P=4): packed fill end to end --------------------------------
    mesh4 = _mesh((4,), ("x",))
    A = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    assert blas.plan_route("syrk", 16, 64, mesh=mesh4).path == "1d"
    np.testing.assert_allclose(
        np.asarray(blas.syrk(A, fill="packed", mesh=mesh4)),
        packed_np(np.asarray(A) @ np.asarray(A).T), **TOL)
    g = np.asarray(A) @ np.asarray(B).T
    np.testing.assert_allclose(
        np.asarray(blas.syr2k(A, B, fill="packed", mesh=mesh4)),
        packed_np(g + g.T), **TOL)
    S = rng.standard_normal((16, 16)).astype(np.float32)
    tt = TriTiles.from_tril(jnp.tril(jnp.asarray(S)), 8)
    np.testing.assert_allclose(
        np.asarray(blas.symm(tt, B, mesh=mesh4)),
        sym_np(S) @ np.asarray(B), **TOL)
    print("  1d packed parity: syrk/syr2k/symm(TriTiles)")

    for op, fn in [("syrk", lambda x: blas.syrk(x, fill="packed",
                                                mesh=mesh4)),
                   ("syr2k", lambda x: blas.syr2k(x, x, fill="packed",
                                                  mesh=mesh4))]:
        jx = jax.make_jaxpr(fn)(A)
        sq = _square_vars_on_wire(jx, 16)
        assert not sq, f"dense on the packed 1d {op} wire: {sq}"
    jx = jax.make_jaxpr(
        lambda t, y: blas.symm(TriTiles(t, 16, 8), y, mesh=mesh4))(
            tt.tiles, B)
    assert not _square_vars_on_wire(jx, 16)
    jx = jax.make_jaxpr(jax.grad(
        lambda x: blas.syrk(x, fill="packed", mesh=mesh4).sum()))(A)
    assert not _square_vars_on_wire(jx, 16), \
        "packed 1d syrk backward densified the cotangent on the wire"
    print("  1d packed wire is dense-free (jaxpr, fwd + bwd)")

    # ---- batched stacks on the 1d wire -----------------------------------
    Ab = jnp.asarray(rng.standard_normal((3, 16, 64)), jnp.float32)
    Bb = jnp.asarray(rng.standard_normal((3, 16, 64)), jnp.float32)
    r = blas.plan_route("syrk", 16, 64, batch=True, mesh=mesh4)
    assert r.path == "1d", f"batched mesh call must ride the 1D wire: {r}"
    got = np.asarray(blas.syrk(Ab, mesh=mesh4))
    want = np.stack([tril_np(np.asarray(x) @ np.asarray(x).T) for x in Ab])
    np.testing.assert_allclose(got, want, **TOL)
    got = np.asarray(blas.syr2k(Ab, Bb, fill="packed", mesh=mesh4))
    for i in range(3):
        gi = np.asarray(Ab[i]) @ np.asarray(Bb[i]).T
        np.testing.assert_allclose(got[i], packed_np(gi + gi.T), **TOL)
    Sb = rng.standard_normal((3, 16, 16)).astype(np.float32)
    got = np.asarray(blas.symm(jnp.asarray(Sb), Bb, mesh=mesh4))
    for i in range(3):
        np.testing.assert_allclose(got[i], sym_np(Sb[i]) @ np.asarray(Bb[i]),
                                   **TOL)
    ttb = TriTiles.from_tril(jnp.tril(jnp.asarray(Sb)), 8)
    got = np.asarray(blas.symm(ttb, Bb, mesh=mesh4))
    for i in range(3):
        np.testing.assert_allclose(got[i], sym_np(Sb[i]) @ np.asarray(Bb[i]),
                                   **TOL)
    # the stack moves ONE collective pair, not k of them and not a
    # dense all-reduce: packed words only on the wire
    jx = jax.make_jaxpr(lambda x: blas.syrk(x, fill="packed",
                                            mesh=mesh4))(Ab)
    assert not _square_vars_on_wire(jx, 16)
    # batched grad parity (fwd route + packed cotangent both stacked)
    gm = jax.grad(lambda x: jnp.sum(
        blas.syrk(x, fill="packed", mesh=mesh4) ** 2))(Ab)
    gd = jax.grad(lambda x: jnp.sum(blas.syrk(x, fill="packed") ** 2))(Ab)
    np.testing.assert_allclose(np.asarray(gm), np.asarray(gd), rtol=2e-3,
                               atol=2e-4)
    print("  batched stacks: parity + dense-free wire + grads (1d)")

    # ---- 2d (P=6, c=2): ShardedTriTiles wire -----------------------------
    mesh6 = _mesh((6,), ("x",))
    for n1 in (36, 34):                 # 34: non-multiple of bm and nb
        A2 = jnp.asarray(rng.standard_normal((n1, 6)), jnp.float32)
        B2 = jnp.asarray(rng.standard_normal((n1, 6)), jnp.float32)
        assert blas.plan_route("syrk", n1, 6, mesh=mesh6).path == "2d"
        np.testing.assert_allclose(
            np.asarray(blas.syrk(A2, fill="packed", mesh=mesh6)),
            packed_np(np.asarray(A2) @ np.asarray(A2).T), **TOL)
        g2 = np.asarray(A2) @ np.asarray(B2).T
        np.testing.assert_allclose(
            np.asarray(blas.syr2k(A2, B2, fill="packed", mesh=mesh6)),
            packed_np(g2 + g2.T), **TOL)
        S2 = rng.standard_normal((n1, n1)).astype(np.float32)
        tt2 = TriTiles.from_tril(jnp.tril(jnp.asarray(S2)), 8)
        assert blas.plan_route("symm", n1, 6, mesh=mesh6).path == "2d"
        np.testing.assert_allclose(
            np.asarray(blas.symm(tt2, B2, mesh=mesh6)),
            sym_np(S2) @ np.asarray(B2), **TOL)
        jx = jax.make_jaxpr(lambda x: blas.syrk(x, fill="packed",
                                                mesh=mesh6))(A2)
        assert not _square_vars_on_wire(jx, n1), \
            f"2d packed syrk wire densified (n1={n1})"
        jx = jax.make_jaxpr(
            lambda t, y: blas.symm(TriTiles(t, n1, 8), y, mesh=mesh6))(
                tt2.tiles, B2)
        assert not _square_vars_on_wire(jx, n1)
        jx = jax.make_jaxpr(jax.grad(
            lambda x: blas.syrk(x, fill="packed", mesh=mesh6).sum()))(A2)
        assert not _square_vars_on_wire(jx, n1)
    print("  2d packed parity + dense-free wire (n1=36 and ragged 34)")

    # backward of a packed 2d syrk runs its symm on the 2d packed wire
    A2 = jnp.asarray(rng.standard_normal((36, 6)), jnp.float32)
    with blas.capture_routes() as log:
        gm = jax.grad(lambda x: jnp.sum(
            blas.syrk(x, fill="packed", mesh=mesh6)))(A2)
    assert ("symm", "2d") in [(r.op, r.path) for r in log]
    gd = jax.grad(lambda x: jnp.sum(blas.syrk(x, fill="packed")))(A2)
    np.testing.assert_allclose(np.asarray(gm), np.asarray(gd), **TOL)
    # symm with a TriTiles primal gets dA back as TriTiles via a
    # packed-fill SYR2K that itself rides the 2d wire
    S2 = rng.standard_normal((36, 36)).astype(np.float32)
    tt2 = TriTiles.from_tril(jnp.tril(jnp.asarray(S2)), 8)
    B2 = jnp.asarray(rng.standard_normal((36, 6)), jnp.float32)
    with blas.capture_routes() as log:
        gt = jax.grad(lambda t: jnp.sum(
            blas.symm(TriTiles(t, 36, 8), B2, mesh=mesh6) ** 2))(tt2.tiles)
    assert ("syr2k", "2d") in [(r.op, r.path) for r in log]
    gtd = jax.grad(lambda t: jnp.sum(
        blas.symm(TriTiles(t, 36, 8), B2) ** 2))(tt2.tiles)
    np.testing.assert_allclose(np.asarray(gt), np.asarray(gtd), rtol=2e-3,
                               atol=2e-4)
    print("  2d grads: packed cotangents stay on the wire")

    # ---- 3d (P=12 = 6 x 2): flat shards -> ShardedTriTiles ---------------
    mesh12 = _mesh((12,), ("x",))
    A3 = jnp.asarray(rng.standard_normal((24, 8)), jnp.float32)
    B3 = jnp.asarray(rng.standard_normal((24, 8)), jnp.float32)
    assert blas.plan_route("syrk", 24, 8, mesh=mesh12).path == "3d"
    np.testing.assert_allclose(
        np.asarray(blas.syrk(A3, fill="packed", mesh=mesh12)),
        packed_np(np.asarray(A3) @ np.asarray(A3).T), **TOL)
    g3 = np.asarray(A3) @ np.asarray(B3).T
    np.testing.assert_allclose(
        np.asarray(blas.syr2k(A3, B3, fill="packed", mesh=mesh12)),
        packed_np(g3 + g3.T), **TOL)
    S3 = rng.standard_normal((24, 24)).astype(np.float32)
    tt3 = TriTiles.from_tril(jnp.tril(jnp.asarray(S3)), 8)
    assert blas.plan_route("symm", 24, 8, mesh=mesh12).path == "3d"
    np.testing.assert_allclose(
        np.asarray(blas.symm(tt3, B3, mesh=mesh12)),
        sym_np(S3) @ np.asarray(B3), **TOL)
    jx = jax.make_jaxpr(lambda x: blas.syrk(x, fill="packed",
                                            mesh=mesh12))(A3)
    assert not _square_vars_on_wire(jx, 24)
    jx = jax.make_jaxpr(
        lambda t, y: blas.symm(TriTiles(t, 24, 8), y, mesh=mesh12))(
            tt3.tiles, B3)
    assert not _square_vars_on_wire(jx, 24)
    gm = jax.grad(lambda x: jnp.sum(
        blas.syrk(x, fill="packed", mesh=mesh12)))(A3)
    gd = jax.grad(lambda x: jnp.sum(blas.syrk(x, fill="packed")))(A3)
    np.testing.assert_allclose(np.asarray(gm), np.asarray(gd), **TOL)
    jx = jax.make_jaxpr(jax.grad(
        lambda x: blas.syrk(x, fill="packed", mesh=mesh12).sum()))(A3)
    assert not _square_vars_on_wire(jx, 24)
    # TriTiles symm backward: dA rides a 3d-routed packed syr2k home
    with blas.capture_routes() as log:
        gt = jax.grad(lambda t: jnp.sum(
            blas.symm(TriTiles(t, 24, 8), B3, mesh=mesh12) ** 2))(tt3.tiles)
    assert ("syr2k", "3d") in [(r.op, r.path) for r in log]
    gtd = jax.grad(lambda t: jnp.sum(
        blas.symm(TriTiles(t, 24, 8), B3) ** 2))(tt3.tiles)
    np.testing.assert_allclose(np.asarray(gt), np.asarray(gtd), rtol=2e-3,
                               atol=2e-4)
    print("  3d packed parity + dense-free wire + grads")

    # ---- each op unbatched and batched through its route's one schedule --
    from repro.blas import meshpath
    from repro.core.packing import pack_tril

    def packed_of(x):
        return np.asarray(x.to_packed() if isinstance(x, ShardedTriTiles)
                          else x)

    for path, mesh_, n1, n2 in (("1d", mesh4, 16, 64), ("2d", mesh6, 34, 6),
                                ("3d", mesh12, 24, 8)):
        wire = meshpath.WIRES[path]
        Ab = jnp.asarray(rng.standard_normal((3, n1, n2)), jnp.float32)
        Bb = jnp.asarray(rng.standard_normal((3, n1, n2)), jnp.float32)
        Sb = rng.standard_normal((3, n1, n1)).astype(np.float32)
        routes = {}
        for op in ("syrk", "syr2k", "symm"):
            r = blas.plan_route(op, n1, n2, batch=True, mesh=mesh_)
            assert r.path == path and r.reason.startswith("batched:"), r
            routes[op] = r
        # the stack equals its slices run one at a time (stacks of one)
        whole = packed_of(wire.syrk(Ab, mesh_, routes["syrk"]))
        whole2 = packed_of(wire.syr2k(Ab, Bb, mesh_, routes["syr2k"]))
        Sp = pack_tril(jnp.tril(jnp.asarray(Sb)))
        whole_s = np.asarray(wire.symm(Sp, Bb, mesh_, routes["symm"]))
        for i in range(3):
            a, b = np.asarray(Ab[i]), np.asarray(Bb[i])
            one = packed_of(wire.syrk(Ab[i], mesh_, routes["syrk"]))
            np.testing.assert_allclose(whole[i], one, **TOL)
            np.testing.assert_allclose(one, packed_np(a @ a.T), **TOL)
            one = packed_of(wire.syr2k(Ab[i], Bb[i], mesh_, routes["syr2k"]))
            np.testing.assert_allclose(whole2[i], one, **TOL)
            np.testing.assert_allclose(one, packed_np(a @ b.T + b @ a.T),
                                       **TOL)
            one = np.asarray(wire.symm(Sp[i], Bb[i], mesh_, routes["symm"]))
            np.testing.assert_allclose(whole_s[i], one, **TOL)
            np.testing.assert_allclose(one, sym_np(Sb[i]) @ b, **TOL)
        # the blas surface on the stack: parity, dense-free wire, grads
        got = np.asarray(blas.syrk(Ab, fill="packed", mesh=mesh_))
        np.testing.assert_allclose(got, whole, **TOL)
        ttb = TriTiles.from_tril(jnp.tril(jnp.asarray(Sb)), 8)
        np.testing.assert_allclose(np.asarray(blas.symm(ttb, Bb, mesh=mesh_)),
                                   whole_s, **TOL)
        jx = jax.make_jaxpr(lambda x: blas.syrk(x, fill="packed",
                                                mesh=mesh_))(Ab)
        assert not _square_vars_on_wire(jx, n1), \
            f"batched {path} syrk wire densified"
        jx = jax.make_jaxpr(
            lambda t, y: blas.symm(TriTiles(t, n1, 8), y, mesh=mesh_))(
                ttb.tiles, Bb)
        assert not _square_vars_on_wire(jx, n1), \
            f"batched {path} symm wire densified"
        with blas.capture_routes() as log:
            gm = jax.grad(lambda x: jnp.sum(
                blas.syrk(x, fill="packed", mesh=mesh_) ** 2))(Ab)
        assert ("symm", path) in [(r.op, r.path) for r in log], log
        gd = jax.grad(lambda x: jnp.sum(blas.syrk(x, fill="packed") ** 2))(Ab)
        np.testing.assert_allclose(np.asarray(gm), np.asarray(gd),
                                   rtol=2e-3, atol=2e-4)
    print("  1d/2d/3d: each op unbatched == batched through one schedule; "
          "batched wire dense-free + grads")

    # ---- ShardedTriTiles round-trips against the mesh outputs ------------
    st = meshpath.syrk_2d_sharded(A2, 2, mesh6, "x")
    assert isinstance(st, ShardedTriTiles) and (st.n, st.c) == (36, 2)
    np.testing.assert_allclose(
        np.asarray(st.to_packed()),
        packed_np(np.asarray(A2) @ np.asarray(A2).T), **TOL)
    np.testing.assert_allclose(
        np.asarray(st.to_tritiles(8).to_tril()),
        tril_np(np.asarray(A2) @ np.asarray(A2).T), **TOL)
    st3 = meshpath.syrk_3d_sharded(A3, 2, 2, mesh12)
    np.testing.assert_allclose(
        np.asarray(st3.to_packed()),
        packed_np(np.asarray(A3) @ np.asarray(A3).T), **TOL)
    print("  ShardedTriTiles: mesh outputs round-trip to packed/TriTiles")

    # ---- bf16 packed Gram state on the mesh wire -------------------------
    from repro.optim.gram import GramMonitor, packed_gram
    X = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    gbf = packed_gram(X, mesh4, axis="x", out_dtype=jnp.bfloat16)
    assert gbf.dtype == jnp.bfloat16 and gbf.shape == (tril_size(16),)
    gf = np.asarray(packed_gram(X, mesh4, axis="x"))
    np.testing.assert_allclose(np.asarray(gbf, np.float32), gf, rtol=2e-2,
                               atol=2e-2)
    mon = GramMonitor(mesh=mesh4, axis="x", out_dtype=jnp.bfloat16)
    mon.update("w", X)
    mon.update("w", X)
    assert mon._state["w"].dtype == jnp.bfloat16
    tt_g = mon.tritiles("w", bm=8)
    assert tt_g.dtype == jnp.bfloat16 and tt_g.n == 16
    np.testing.assert_allclose(np.asarray(tt_g.to_packed(), np.float32),
                               gf, rtol=2e-2, atol=2e-2)
    print("  bf16 packed Gram EMA on the 1d wire (state + TriTiles exit)")
    print("OK mesh_packed")


def _shardmap_scan_peaks(jaxpr):
    """Max words of any eqn output inside each lax.scan body that lives
    inside a shard_map body — the per-device live working set of the
    streamed loop.  Scans at the GSPMD level (layout converters) are
    excluded: they shuffle owned data, they are not the stream."""
    peaks = []

    def walk(j, inside):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name == "scan" and inside:
                words = 1
                for e2 in eqn.params["jaxpr"].jaxpr.eqns:
                    for v in e2.outvars:
                        sh = tuple(getattr(v.aval, "shape", ()))
                        words = max(words,
                                    int(np.prod(sh, dtype=np.int64))
                                    if sh else 1)
                peaks.append(words)
            nested = inside or "shard_map" in name
            for val in eqn.params.values():
                if hasattr(val, "jaxpr") and hasattr(val.jaxpr, "eqns"):
                    walk(val.jaxpr, nested)
                elif hasattr(val, "eqns"):
                    walk(val, nested)

    walk(jaxpr.jaxpr, False)
    return peaks


def check_memdep() -> None:
    """The §IX memory-dependent wire (12 fake devices): a small budget M
    forces the 3d-limited route (Route capture, not just planning), the
    streamed Algs 16-18 match the dense oracle for every op/fill (incl.
    ragged n1 and ShardedTriTiles operands), the packed wire stays
    dense-free fwd+bwd, the scan body's live set is O(chunk) — not
    O(n2/p2) — and a huge budget reproduces the memory-independent
    plans exactly."""
    import jax
    import jax.numpy as jnp

    from repro import blas
    from repro.blas import meshpath
    from repro.core.packing import ShardedTriTiles
    from repro.core.threedim import syrk_3d_limited
    from repro.core.twodim import make_2d_plan

    rng = np.random.default_rng(33)
    TOL = dict(rtol=3e-4, atol=3e-4)

    def tril_np(x):
        return np.tril(np.asarray(x, np.float64)).astype(np.float32)

    def packed_np(x):
        t = tril_np(x)
        return t[np.tril_indices(t.shape[0])]

    def sym_np(s):
        return np.tril(s) + np.tril(s, -1).T

    mesh = _mesh((12,), ("x",))
    M = 60                                  # words/device -> 3d-limited
    n2 = 32

    # ---- routing: M forces the streamed route, and it executes ----------
    r = blas.plan_route("syrk", 24, n2, mesh=mesh, M=M)
    assert r.path == "3d-limited" and r.M == M, r
    assert (r.choice.c, r.choice.p2) == (2, 2) and r.choice.b >= 1, r
    assert "b=" in r.describe() and "W_IX" in r.describe(), r.describe()

    for n1 in (24, 22):                     # 22: ragged (nb padding)
        A = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        B = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        assert blas.plan_route("syrk", n1, n2, mesh=mesh,
                               M=M).path == "3d-limited"
        with blas.capture_routes() as log:
            got = np.asarray(blas.syrk(A, mesh=mesh, M=M))
        assert [(x.op, x.path) for x in log] == [("syrk", "3d-limited")]
        np.testing.assert_allclose(
            got, tril_np(np.asarray(A) @ np.asarray(A).T), **TOL)
        np.testing.assert_allclose(
            np.asarray(blas.syrk(A, fill="packed", mesh=mesh, M=M)),
            packed_np(np.asarray(A) @ np.asarray(A).T), **TOL)
        g = np.asarray(A) @ np.asarray(B).T
        np.testing.assert_allclose(
            np.asarray(blas.syr2k(A, B, mesh=mesh, M=M)),
            tril_np(g + g.T), **TOL)
        np.testing.assert_allclose(
            np.asarray(blas.syr2k(A, B, fill="packed", mesh=mesh, M=M)),
            packed_np(g + g.T), **TOL)
        S = rng.standard_normal((n1, n1)).astype(np.float32)
        with blas.capture_routes() as log:
            got = np.asarray(blas.symm(jnp.asarray(S), B, mesh=mesh, M=M))
        assert ("symm", "3d-limited") in [(x.op, x.path) for x in log]
        np.testing.assert_allclose(got, sym_np(S) @ np.asarray(B), **TOL)
    print("  streamed == dense: syrk/syr2k/symm, tril+packed, ragged n1")

    # ---- fill="sharded" output feeds a limited symm without repacking ----
    A = jnp.asarray(rng.standard_normal((24, n2)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((24, n2)), jnp.float32)
    st = blas.syrk(A, fill="sharded", mesh=mesh, M=M)
    assert isinstance(st, ShardedTriTiles) and (st.n, st.c) == (24, 2)
    np.testing.assert_allclose(
        np.asarray(st.to_tril()),
        tril_np(np.asarray(A) @ np.asarray(A).T), **TOL)
    with blas.capture_routes() as log:
        got = np.asarray(blas.symm(st, B, mesh=mesh, M=M))
    assert ("symm", "3d-limited") in [(x.op, x.path) for x in log]
    want = sym_np(tril_np(np.asarray(A) @ np.asarray(A).T))
    np.testing.assert_allclose(got, want @ np.asarray(B), rtol=2e-3,
                               atol=2e-3)
    print("  fill=sharded round-trips and rides the limited symm")

    # ---- batched operands ignore M (stacked 1d wire, unchanged) ---------
    Ab = jnp.asarray(rng.standard_normal((2, 24, 48)), jnp.float32)
    rb = blas.plan_route("syrk", 24, 48, batch=True, mesh=mesh, M=M)
    assert rb.path == "1d", rb
    got = np.asarray(blas.syrk(Ab, mesh=mesh, M=M))
    want = np.stack([tril_np(np.asarray(x) @ np.asarray(x).T) for x in Ab])
    np.testing.assert_allclose(got, want, **TOL)
    print("  batched stacks stay on the 1d wire under a budget")

    # ---- packed wire dense-free, fwd + bwd ------------------------------
    jx = jax.make_jaxpr(lambda x: blas.syrk(x, fill="packed", mesh=mesh,
                                            M=M))(A)
    assert not _square_vars_on_wire(jx, 24), "limited syrk wire densified"
    jx = jax.make_jaxpr(jax.grad(
        lambda x: blas.syrk(x, fill="packed", mesh=mesh, M=M).sum()))(A)
    assert not _square_vars_on_wire(jx, 24), \
        "limited syrk backward densified the cotangent on the wire"
    print("  3d-limited packed wire is dense-free (jaxpr, fwd + bwd)")

    # ---- the scan body's live set is O(chunk), not O(n2/p2) -------------
    c, p2, b = r.choice.c, r.choice.p2, r.choice.b
    bw, nsteps = meshpath._limited_steps(n2, p2, b)
    plan_b = make_2d_plan(c, 24, bw)
    mesh3 = meshpath._mesh_3d(mesh, c * (c + 1), p2)
    a_ch = meshpath._chunk_cols_3d_jnp(A, plan_b, p2, nsteps)
    jx = jax.make_jaxpr(
        lambda x: syrk_3d_limited(x, plan_b, mesh3,
                                  meshpath.TB_AXIS, meshpath.REP_AXIS))(a_ch)
    peaks = _shardmap_scan_peaks(jx)
    assert peaks, "limited route lost its streaming scan"
    panel_words = c * plan_b.nb * (n2 // p2)    # unchunked per-device slice
    assert max(peaks) < panel_words, (peaks, panel_words)
    print(f"  scan-body peak {max(peaks)}w < owned panel {panel_words}w "
          f"(nsteps={nsteps})")

    # ---- grads ride the limited wire and match dense --------------------
    W = jnp.asarray(rng.standard_normal((24, 24)), jnp.float32)
    with blas.capture_routes() as log:
        gm = jax.grad(lambda x: jnp.sum(
            W * blas.syrk(x, mesh=mesh, M=M)))(A)
    planned = [(x.op, x.path) for x in log]
    assert ("syrk", "3d-limited") in planned \
        and ("symm", "3d-limited") in planned, planned
    gd = jax.grad(lambda x: jnp.sum(W * blas.syrk(x)))(A)
    np.testing.assert_allclose(np.asarray(gm), np.asarray(gd), rtol=1e-4,
                               atol=1e-5)
    gm = jax.grad(lambda x, y: jnp.sum(
        blas.syr2k(x, y, mesh=mesh, M=M) ** 2), argnums=(0, 1))(A, B)
    gd = jax.grad(lambda x, y: jnp.sum(
        blas.syr2k(x, y) ** 2), argnums=(0, 1))(A, B)
    for x, y in zip(gm, gd):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=2e-3,
                                   atol=2e-4)
    S = jnp.asarray(rng.standard_normal((24, 24)), jnp.float32)
    gm = jax.grad(lambda s, y: jnp.sum(
        blas.symm(s, y, mesh=mesh, M=M) ** 2), argnums=(0, 1))(S, B)
    gd = jax.grad(lambda s, y: jnp.sum(
        blas.symm(s, y) ** 2), argnums=(0, 1))(S, B)
    for x, y in zip(gm, gd):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=2e-3,
                                   atol=2e-4)
    print("  grad parity vs dense (backward symm routed 3d-limited)")

    # ---- a huge budget reproduces the memory-independent plans ----------
    for op, n1_, n2_ in (("syrk", 24, 8), ("syrk", 16, 1024),
                         ("symm", 36, 6)):
        r_big = blas.plan_route(op, n1_, n2_, mesh=mesh, M=1 << 40)
        r_off = blas.plan_route(op, n1_, n2_, mesh=mesh, M=None)
        assert (r_big.path, r_big.choice) == (r_off.path, r_off.choice), \
            (r_big, r_off)
    print("  huge M == memory-independent plans")
    print("OK memdep")


def check_persist() -> None:
    """Packed-native persistence + elasticity (12 fake devices):
    ShardedTriTiles state written on the P=8 world's wire (c=2)
    restores bit-exactly at P′=6 (same c) and P′=12 (c=3) through the
    block-granular converters — batched and ragged-n included — with a
    jaxpr proof that the re-shard path materializes no dense n×n;
    packed bf16 checkpoint bytes ≤ 0.30× dense f32 for every symmetric
    leaf of a Gram-EMA/Muon state; straggler replacement rebuilds one
    device's shard from the packed words; and the per-shard int8
    all-reduce (dense + packed-symmetric) matches the mean."""
    import json
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from repro.core.packing import (PackedTriangle, ShardedTriTiles,
                                    pack_tril, tril_size)
    from repro.distributed import (checkpoint_bytes, compressed_allreduce,
                                   compressed_allreduce_sym,
                                   rebuild_replacement_shard,
                                   reshard_tritiles, restore_checkpoint,
                                   save_checkpoint, wire_c)
    from repro.distributed.elastic import spec_tree_like

    rng = np.random.default_rng(42)
    assert wire_c(8) == 2 and wire_c(6) == 2 and wire_c(12) == 3

    # ---- elastic re-shard P=8 -> P'=6 / P'=12 ---------------------------
    for n, batch in ((24, ()), (22, ()), (24, (3,))):
        dense = rng.standard_normal(batch + (n, n)).astype(np.float32)
        packed = pack_tril(jnp.tril(jnp.asarray(dense)))
        st8 = ShardedTriTiles.from_packed(packed, n, wire_c(8))
        st6 = reshard_tritiles(st8, wire_c(6))
        assert st6 is st8           # same wire (c=2): layout-stable
        st12 = reshard_tritiles(st8, wire_c(12))
        assert st12.c == 3
        np.testing.assert_array_equal(np.asarray(st12.to_packed()),
                                      np.asarray(packed))
        ref = ShardedTriTiles.from_packed(packed, n, 3)
        np.testing.assert_array_equal(np.asarray(st12.off),
                                      np.asarray(ref.off))
        np.testing.assert_array_equal(np.asarray(st12.diag),
                                      np.asarray(ref.diag))
        jx = jax.make_jaxpr(lambda s: reshard_tritiles(s, 3))(st8)
        sq = _square_vars_on_wire(jx, n)
        assert not sq, f"dense n×n on the re-shard path (n={n}): {sq}"
    print("  re-shard P=8->6/12 bit-exact (ragged + batched), "
          "dense-free jaxpr")

    # ---- disk round-trip restoring onto a different device count --------
    n = 24
    dense = rng.standard_normal((n, n)).astype(np.float32)
    packed = pack_tril(jnp.tril(jnp.asarray(dense)))
    st8 = ShardedTriTiles.from_packed(packed, n, 2)
    tmp = tempfile.mkdtemp()
    try:
        # f32 words kept on disk -> the elastic restore is bit-exact
        save_checkpoint(tmp, 1, {"acc": st8}, packed_dtype=None)
        like = {"acc": ShardedTriTiles.from_packed(
            jnp.zeros_like(packed), n, 3)}
        _, back = restore_checkpoint(tmp, like)
        assert back["acc"].c == 3
        np.testing.assert_array_equal(np.asarray(back["acc"].to_packed()),
                                      np.asarray(packed))
        # the converter path is the jaxpr-audited from_packed above; the
        # restore adds only the host->device copy of the packed words
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print("  checkpoint saved on the c=2 wire restores bit-exact at c=3")

    # ---- bytes: packed bf16 <= 0.30x dense f32 for symmetric leaves -----
    from repro.optim import muon as muon_mod
    from repro.optim.gram import GramMonitor
    from repro.optim.muon import Muon

    mon = GramMonitor()
    X = jnp.asarray(rng.standard_normal((32, 128)), jnp.float32)
    mon.update("w", X)
    opt = Muon(gram_decay=0.9)
    params = {"w": jnp.zeros((32, 64), jnp.float32)}
    mst = opt.init(params)
    g = {"w": jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)}
    _, mst = opt.update(g, mst, params)
    tmp = tempfile.mkdtemp()
    try:
        save_checkpoint(tmp, 1, {"gram": mon.state_dict(),
                                 "muon": muon_mod.state_dict(mst)})
        with open(os.path.join(tmp, "step_00000001",
                               "manifest.json")) as f:
            man = json.load(f)
        packed_leaves = {k: m for k, m in man["leaves"].items()
                         if "packed" in m}
        assert len(packed_leaves) >= 2, list(man["leaves"])
        for k, m in packed_leaves.items():
            nn = m["packed"]["n"]
            ratio = m["bytes"] / (nn * nn * 4)
            assert ratio <= 0.30, (k, ratio)
        total = checkpoint_bytes(tmp)
        print(f"  packed bf16 leaves <= 0.30x dense f32 "
              f"({len(packed_leaves)} leaves, total {total['total']} B)")
        # restore round-trips into the packed state dicts
        like = {"gram": {kk: PackedTriangle(jnp.zeros_like(vv.vec), vv.n)
                         for kk, vv in mon.state_dict().items()},
                "muon": jax.eval_shape(lambda: muon_mod.state_dict(mst))}
        _, back = restore_checkpoint(tmp, like)
        mon2 = GramMonitor()
        mon2.load_state_dict(back["gram"])
        np.testing.assert_allclose(
            np.asarray(mon2._state["w"], np.float32),
            np.asarray(mon._state["w"], np.float32), rtol=1e-2, atol=1e-2)
        mst2 = muon_mod.load_state_dict(back["muon"])
        np.testing.assert_allclose(
            np.asarray(mst2.gram["w"].vec, np.float32),
            np.asarray(mst.gram["w"].vec, np.float32), rtol=1e-2,
            atol=1e-2)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print("  Gram-EMA / Muon packed state dicts round-trip the manifest")

    # ---- straggler replacement: one shard from the packed words ---------
    st = ShardedTriTiles.from_packed(packed, n, 2)
    for k in (0, 3, 5):
        off, diag = rebuild_replacement_shard(packed, n, 2, k)
        np.testing.assert_array_equal(np.asarray(off), np.asarray(st.off[k]))
        np.testing.assert_array_equal(np.asarray(diag),
                                      np.asarray(st.diag[k]))
    jx = jax.make_jaxpr(
        lambda p: rebuild_replacement_shard(p, n, 2, 3))(packed)
    assert not _square_vars_on_wire(jx, n), \
        "replacement-shard rebuild densified"
    print("  straggler replacement rebuilds one shard, dense-free")

    # ---- packed-aware spec trees ----------------------------------------
    specs = spec_tree_like({"s": st, "x": jnp.ones(3)}, shard_axis="x")
    assert isinstance(specs["s"], ShardedTriTiles)
    assert specs["s"].off == jax.sharding.PartitionSpec("x")
    print("  spec_tree_like emits packed-format spec subtrees")

    # ---- per-shard int8 all-reduce on the 12-device mesh ----------------
    mesh = _mesh((12,), ("x",))
    x = jnp.asarray(rng.standard_normal(768), jnp.float32)
    out = np.asarray(compressed_allreduce(x, mesh, axis="x", block=64))
    np.testing.assert_allclose(out, np.asarray(x),
                               atol=float(np.max(np.abs(out))) / 40)
    S = rng.standard_normal((n, n)).astype(np.float32)
    S = (S + S.T) / 2
    got = np.asarray(compressed_allreduce_sym(jnp.asarray(S), mesh,
                                              axis="x", block=64))
    np.testing.assert_allclose(got, S, atol=float(np.max(np.abs(S))) / 30)
    np.testing.assert_array_equal(got, got.T)
    pt = PackedTriangle.from_dense(jnp.asarray(S))
    gp = compressed_allreduce_sym(pt, mesh, axis="x", block=64)
    assert isinstance(gp, PackedTriangle) and \
        gp.vec.shape == (tril_size(n),)
    print("  per-shard int8 all-reduce: dense + sym + packed parity")
    print("OK persist")


def check_ring() -> None:
    """The cyclic-shift ring route (run with 6 or 8 fake devices):
    dense == ring parity for syrk/syr2k/symm at odd and even P incl.
    ragged n1 and batched stacks (packed, full and tril fills, dense-A
    symm), jaxpr proof that the dense fills and dense-A symm convert
    with no gather or scatter, jaxpr proof that the packed ring wire
    moves no n×n dense intermediate forward or backward, compiled-HLO
    proof the wire is exactly ⌊P/2⌋ collective-permutes, backward-symm
    Route capture, and (8+ devices) the computation-optimality gate:
    ring per-device HLO flops ≤ 0.6× the 2d route's at n1=2048."""
    import jax
    import jax.numpy as jnp

    from repro import blas
    from repro.analysis.hlo_cost import analyze_hlo
    from repro.blas import meshpath
    from repro.core.packing import pack_tril

    ndev = len(jax.devices())
    rng = np.random.default_rng(11)
    TOL = dict(rtol=3e-4, atol=3e-4)

    def pk(x):
        return np.asarray(pack_tril(jnp.tril(
            jnp.asarray(x) @ jnp.swapaxes(jnp.asarray(x), -1, -2))))

    # ---- parity: odd and even P, ragged n1, batched stacks -------------
    cases = [(2, 64, 64, None), (2, 65, 64, None), (3, 96, 96, None),
             (3, 100, 96, None), (4, 128, 128, 3),
             (ndev, 32 * ndev, 32 * ndev, None)]
    for P, n1, n2, k in cases:
        mesh = _mesh((P,), ("x",))
        assert blas.plan_route("syrk", n1, n2, batch=k is not None,
                               mesh=mesh).path == "ring", (P, n1, n2, k)
        shape = (k, n1, n2) if k else (n1, n2)
        A = rng.standard_normal(shape).astype(np.float32)
        B = rng.standard_normal(shape).astype(np.float32)
        got = np.asarray(blas.syrk(A, fill="packed", mesh=mesh))
        np.testing.assert_allclose(got, pk(A), **TOL)
        got = np.asarray(blas.syr2k(A, B, fill="packed", mesh=mesh))
        prod = A @ np.swapaxes(B, -1, -2)
        want = np.asarray(pack_tril(jnp.asarray(
            np.tril(prod + np.swapaxes(prod, -1, -2)))))
        np.testing.assert_allclose(got, want, **TOL)
        # the dense fills, built from the slot stack in whole blocks
        gram = A @ np.swapaxes(A, -1, -2)
        two = prod + np.swapaxes(prod, -1, -2)
        for fill, keep in [("full", lambda x: x), ("tril", np.tril)]:
            got = np.asarray(blas.syrk(A, fill=fill, mesh=mesh))
            np.testing.assert_allclose(got, keep(gram), **TOL)
            got = np.asarray(blas.syr2k(A, B, fill=fill, mesh=mesh))
            np.testing.assert_allclose(got, keep(two), **TOL)
        S = rng.standard_normal(shape[:-2] + (n1, n1)).astype(np.float32)
        S[..., 0, n1 - 1] = np.nan        # a dense A's upper half is unread
        got = np.asarray(blas.symm(S, B, mesh=mesh))
        sym = np.tril(S) + np.swapaxes(np.tril(S, -1), -1, -2)
        np.testing.assert_allclose(got, sym @ B, **TOL)
    print(f"  dense == ring parity at P in {sorted({c[0] for c in cases})} "
          "(ragged + batched; packed, full and tril fills, dense-A symm)")

    # ---- the dense fills and dense-A SYMM convert in whole blocks ------
    def gathers_scatters(jx):
        """gather / scatter eqns outside the shard_map bodies (the ring
        schedule's own per-device work is the compile guard's to see)."""
        found = []

        def walk(j):
            for eqn in j.eqns:
                if "shard_map" in eqn.primitive.name:
                    continue
                if eqn.primitive.name.startswith(("gather", "scatter")):
                    found.append(eqn.primitive.name)
                for val in eqn.params.values():
                    if hasattr(val, "jaxpr"):
                        walk(val.jaxpr)
                    elif hasattr(val, "eqns"):
                        walk(val)

        walk(jx.jaxpr)
        return found

    for P, n1, n2, k in [(2, 65, 64, None), (3, 100, 96, 2),
                         (4, 128, 128, 3)]:
        mesh = _mesh((P,), ("x",))
        shape = (k, n1, n2) if k else (n1, n2)
        A = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        S = jnp.asarray(rng.standard_normal(shape[:-2] + (n1, n1)),
                        jnp.float32)
        for fn, ops in [
                (lambda x: blas.syrk(x, fill="full", mesh=mesh), (A,)),
                (lambda x: blas.syrk(x, fill="tril", mesh=mesh), (A,)),
                (lambda x: blas.syr2k(x, x, fill="full", mesh=mesh), (A,)),
                (lambda s, b: blas.symm(s, b, mesh=mesh), (S, A))]:
            with blas.capture_routes() as log:
                jx = jax.make_jaxpr(fn)(*ops)
            assert [r.path for r in log] == ["ring"], log
            assert not gathers_scatters(jx), (P, n1, gathers_scatters(jx))
    print("  ring dense fills and dense-A symm: no gather or scatter")

    # ---- the wire is exactly floor(P/2) collective-permutes ------------
    for P, n1, n2 in [(2, 96, 64), (3, 129, 96), (ndev, 32 * ndev,
                                                  32 * ndev)]:
        mesh = _mesh((P,), ("x",))
        A = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        B = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        assert blas.plan_route("syrk", n1, n2, mesh=mesh).path == "ring"
        for fn, ops in [(lambda x: blas.syrk(x, fill="packed", mesh=mesh),
                         (A,)),
                        (lambda x, y: blas.syr2k(x, y, fill="packed",
                                                 mesh=mesh), (A, B))]:
            hlo = jax.jit(fn).lower(*ops).compile().as_text()
            counts = analyze_hlo(hlo).collective_counts
            got = counts.get("collective-permute", 0)
            assert got == P // 2, (P, counts)
    print("  syrk/syr2k ring wire is exactly floor(P/2) ppermutes "
          f"(P=2, 3, {ndev})")

    # ---- dense-free wire, forward and backward -------------------------
    for P, n1, n2 in [(2, 96, 64), (3, 129, 96)]:
        mesh = _mesh((P,), ("x",))
        A = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((n1 * (n1 + 1) // 2,)),
                        jnp.float32)
        jx = jax.make_jaxpr(
            lambda x: blas.syrk(x, fill="packed", mesh=mesh))(A)
        assert not _square_vars_on_wire(jx, n1), \
            f"ring fwd densified at P={P}"
        jx = jax.make_jaxpr(jax.grad(lambda x: jnp.vdot(
            w, blas.syrk(x, fill="packed", mesh=mesh))))(A)
        assert not _square_vars_on_wire(jx, n1), \
            f"ring bwd densified at P={P}"
    print("  fill='packed' ring wire is dense-free forward and backward")

    # ---- grad parity; the backward SYMM stays on the ring --------------
    mesh = _mesh((ndev,), ("x",))
    n1 = n2 = 32 * ndev
    A = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((n1 * (n1 + 1) // 2,)), jnp.float32)

    def loss(x):
        return jnp.vdot(w, blas.syrk(x, fill="packed", mesh=mesh))

    g = jax.grad(loss)(A)
    gd = jax.grad(lambda x: jnp.vdot(w, pack_tril(jnp.tril(x @ x.T))))(A)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gd), **TOL)
    with blas.capture_routes() as log:
        jax.grad(loss)(A)
    planned = [(r.op, r.path) for r in log]
    assert ("syrk", "ring") in planned and ("symm", "ring") in planned, \
        planned
    print("  grad parity vs dense; backward symm routed ring")

    # ---- computation optimality: ring flops <= 0.6x the 2d route's ----
    if ndev >= 8:
        n1, n2 = 2048, 512
        A = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        mesh8 = _mesh((8,), ("x",))
        ring_hlo = jax.jit(
            lambda x: meshpath.syrk_ring_packed(x, mesh8, "x")
        ).lower(A).compile().as_text()
        mesh6 = _mesh((6,), ("x",))
        two_hlo = jax.jit(
            lambda x: meshpath.syrk_2d_sharded(x, 2, mesh6, "x").to_packed()
        ).lower(A).compile().as_text()
        rf, tf = analyze_hlo(ring_hlo).flops, analyze_hlo(two_hlo).flops
        assert rf <= 0.6 * tf, (rf, tf, rf / tf)
        B2 = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
        ring2 = jax.jit(lambda x, y: meshpath.syr2k_ring_packed(
            x, y, mesh8, "x")).lower(A, B2).compile().as_text()
        two2 = jax.jit(lambda x, y: meshpath.syr2k_2d_sharded(
            x, y, 2, mesh6, "x").to_packed()).lower(A, B2).compile().as_text()
        rf2, tf2 = analyze_hlo(ring2).flops, analyze_hlo(two2).flops
        # the 2d rank-2k schedule runs 2 GEMM passes over the exchanged
        # row blocks — 2× its SYRK flops on the off-diagonal blocks,
        # the redundancy the ring halves.  (The shipped 2d syr2k
        # additionally one-dots its block-diagonal g + gᵀ, an
        # orthogonal saving the ring's slot 0 applies identically, so
        # the measured 2d syr2k lands below 2× and the measured ratio
        # sits near the 16/24 structural floor — tripwired at 0.7.)
        assert rf2 <= 0.6 * (2 * tf), (rf2, tf, rf2 / (2 * tf))
        assert rf2 <= 0.7 * tf2, (rf2, tf2, rf2 / tf2)
        print(f"  per-device HLO flops: ring/2d = {rf / tf:.4f} (syrk) "
              f"<= 0.6, syr2k {rf2 / (2 * tf):.4f} <= 0.6 of the "
              f"2-pass model ({rf2 / tf2:.4f} of measured 2d syr2k)")
    print("OK ring")


def check_faults() -> None:
    """Chaos suite (8 fake devices): ABFT detection + repair parity on
    all four mesh routes (1d/ring/2d/3d + 3d-limited) for injected
    single-device payload corruption, shard repair from a trusted
    reference, checkpoint chaos (transient-fault commit + crash-window
    ``.old`` recovery, both crc-verified), serving under injected
    refresh failures (decode tokens bit-identical to the fault-free
    run, breaker holds last-good, zero unhandled executor exceptions),
    and the end-to-end device-kill -> elastic-resume recovery driver."""
    import shutil
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp

    from repro.distributed import faults
    from repro.distributed.checkpoint import (restore_checkpoint,
                                              save_checkpoint,
                                              verify_restored)
    from repro.distributed.resilience import (checked_symm, checked_syr2k,
                                              checked_syrk)

    rng = np.random.default_rng(55)
    n1, n2 = 64, 64
    A = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((n1, n2)), jnp.float32)
    S = rng.standard_normal((n1, n1)).astype(np.float32)
    from repro.core.packing import pack_tril
    Sp = pack_tril(jnp.tril(jnp.asarray(S)))
    mesh8 = _mesh((8,), ("x",))
    mesh6 = _mesh((6,), ("x",))

    # (route, kwargs, wire world) — every mesh route of meshpath.py
    routes = [
        ("1d", dict(mesh=mesh8, axis="x"), 8),
        ("ring", dict(mesh=mesh8, axis="x"), 8),
        ("2d", dict(mesh=mesh6, axis="x", c=2), 6),
        ("3d", dict(mesh=mesh8, c=2, p2=1), 6),
        ("3d-limited", dict(mesh=mesh8, c=2, p2=1, chunk=16), 6),
    ]

    # ---- ABFT: corrupt one device's band -> detect, localize, repair ----
    for route, kw, world in routes:
        out0, rep0 = checked_syrk(A, route=route, **kw)
        assert not rep0.detected, (route, rep0)
        for kind, dev in (("bitflip", world - 1), ("nan", 2)):
            with faults.inject(faults.FaultSpec(
                    site="collective:syrk", kind=kind, device=dev),
                    seed=3) as inj:
                out, rep = checked_syrk(A, route=route, **kw)
            assert inj.events, (route, kind)
            assert rep.detected and rep.action == "retry", (route, rep)
            assert rep.primary == dev, (route, kind, dev, rep)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(out0))
        # shard repair from a trusted reference: no recompute needed
        if route != "symm":
            with faults.inject(faults.FaultSpec(
                    site="collective:syrk", kind="bitflip", device=1),
                    seed=3):
                out, rep = checked_syrk(A, route=route,
                                        reference=out0,
                                        c=kw.get("c", 2), **{
                                            k: v for k, v in kw.items()
                                            if k != "c"})
            # rep.devices now lists patched shards in c(c+1) wire
            # numbering (not the route's row-band world)
            assert rep.action == "rebuild" and rep.devices, rep
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(out0))
    print("  ABFT syrk: detect + localize + repair parity on "
          f"{[r for r, _, _ in routes]}")

    # syr2k + symm coverage (1d and 2d wires)
    for route, kw, world in (routes[0], routes[2]):
        o0, _ = checked_syr2k(A, B, route=route, **kw)
        with faults.inject(faults.FaultSpec(
                site="collective:syr2k", kind="bitflip",
                device=world - 2), seed=5):
            o1, rep = checked_syr2k(A, B, route=route, **kw)
        assert rep.detected and rep.primary == world - 2, rep
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o0))
        c0, _ = checked_symm(Sp, B, route=route, **kw)
        with faults.inject(faults.FaultSpec(
                site="collective:symm", kind="nan", device=1), seed=5):
            c1, rep = checked_symm(Sp, B, route=route, **kw)
        assert rep.detected and rep.primary == 1, rep
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    print("  ABFT syr2k/symm: post-repair parity on 1d + 2d")

    # ---- checkpoint chaos ----------------------------------------------
    tree = {"w": jnp.asarray(rng.standard_normal((8, 8)), jnp.float32),
            "b": jnp.arange(5, dtype=jnp.int32)}
    tmp = tempfile.mkdtemp()
    try:
        # transient fsync + rename faults are absorbed by with_retries
        with faults.inject(
                faults.FaultSpec(site="ckpt:fsync", kind="error",
                                 times=2),
                faults.FaultSpec(site="ckpt:rename", kind="error",
                                 times=1)) as inj:
            save_checkpoint(tmp, 1, tree, blocking=True)
        assert len(inj.events) == 3, inj.events
        step, back = restore_checkpoint(tmp, jax.eval_shape(lambda: tree))
        vr = verify_restored(tmp, back, step=step)
        assert step == 1 and not vr["mismatches"], vr
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.asarray(tree["w"]))
        # crash window: the replace's second rename fails persistently
        # (final already moved to .old) -> next restore recovers .old
        tree2 = {"w": tree["w"] + 1, "b": tree["b"]}
        try:
            with faults.inject(faults.FaultSpec(
                    site="ckpt:rename", kind="error", skip=1, times=0)):
                save_checkpoint(tmp, 1, tree2, blocking=True)
            raise AssertionError("replace save must fail in the window")
        except faults.FaultError:
            pass
        assert not os.path.isdir(os.path.join(tmp, "step_00000001")), \
            "crash window must leave no final dir"
        step, back = restore_checkpoint(tmp, jax.eval_shape(lambda: tree))
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.asarray(tree["w"]))
        vr = verify_restored(tmp, back, step=step)
        assert not vr["mismatches"], vr
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    print("  checkpoint: transient faults absorbed; crash-window .old "
          "recovered, crc-verified")

    # ---- serving: decode parity + breaker under refresh failures --------
    from repro.configs import get_smoke_config
    from repro.launch.serve import Server, synthetic_requests
    from repro.launch.serving_cache import ServingGramCache
    from repro.models.model import init_params

    unhandled: list = []
    prev_hook = threading.excepthook
    threading.excepthook = lambda a: unhandled.append(a)

    def run_serve():
        cfg = get_smoke_config("stablelm-1.6b")
        params = init_params(cfg, jax.random.key(0))
        cache = ServingGramCache(refresh_stride=1, refresh_retries=1,
                                 refresh_backoff=0.01,
                                 breaker_threshold=2,
                                 breaker_cooldown_s=60.0)
        reqs = synthetic_requests(6, cfg.vocab, 0, tenants=2)
        srv = Server(cfg, params, slots=2, s_max=64, max_new=8,
                     eos_id=-1, whiten="cache", gram_cache=cache)
        queue = list(reqs)
        steps = 0
        while queue or any(r is not None for r in srv.live):
            while queue:
                s = srv.free_slot()
                if s is None:
                    break
                srv.admit(queue.pop(0), s)
            srv.step()
            steps += 1
            if steps > 6 * 8 + 16:
                break
        cache.drain()
        return [list(r.generated) for r in reqs], cache

    try:
        toks0, cache0 = run_serve()
        with faults.inject(faults.FaultSpec(
                site="serve:refresh", kind="error", times=0)):
            toks1, cache1 = run_serve()
    finally:
        threading.excepthook = prev_hook
    assert toks1 == toks0, "decode tokens changed under refresh chaos"
    assert all(len(t) == 8 for t in toks1), toks1
    st = cache1.snapshot_stats()
    assert st["failed_refreshes"] > 0 and st["stale"], st
    assert st["pending"] == 0
    assert not unhandled, f"unhandled executor exceptions: {unhandled}"
    assert cache0.snapshot_stats()["failed_refreshes"] == 0
    print(f"  serving: decode bit-identical under chaos "
          f"({st['failed_refreshes']} failed refreshes, breaker open on "
          f"{st['stale']}, 0 unhandled)")

    # ---- end-to-end: device kill mid-train -> elastic resume ------------
    from repro.launch.recovery import run_recovery
    out = run_recovery("/tmp/repro_faults_recovery", devices=8,
                       devices_after=6, steps=8, kill_step=4,
                       ckpt_every=2, timeout=900)
    assert out["killed"] and out["completed"], out
    assert out["resumed_step"] == 4 and out["mismatches"] == 0, out
    shutil.rmtree("/tmp/repro_faults_recovery", ignore_errors=True)
    print(f"  recovery: kill@4 on 8 devices -> resume on 6, "
          f"{out['verified_leaves']} leaves bit-exact, completed")
    print("OK faults")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", required=True,
                    choices=["1d", "2d", "3d", "3d-limited", "blas",
                             "blas_grad", "mesh_packed", "memdep",
                             "persist", "ring", "faults"])
    ap.add_argument("--P", type=int, default=4)
    ap.add_argument("--c", type=int, default=2)
    ap.add_argument("--p2", type=int, default=2)
    ap.add_argument("--nsteps", type=int, default=2)
    args = ap.parse_args()
    if args.suite == "1d":
        check_1d(args.P)
    elif args.suite == "2d":
        check_2d(args.c)
    elif args.suite == "3d":
        check_3d(args.c, args.p2, 1)
    elif args.suite == "blas":
        check_blas()
    elif args.suite == "blas_grad":
        check_blas_grad()
    elif args.suite == "mesh_packed":
        check_mesh_packed()
    elif args.suite == "memdep":
        check_memdep()
    elif args.suite == "persist":
        check_persist()
    elif args.suite == "ring":
        check_ring()
    elif args.suite == "faults":
        check_faults()
    else:
        check_3d(args.c, args.p2, args.nsteps)


if __name__ == "__main__":
    main()

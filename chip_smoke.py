"""Bring-up check of the main path on a TPU, through the user entry points.

    python chip_smoke.py             # one chip: kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: the mesh routes and a
                                     # muon-syrk train step on a (1, 4) mesh

Everything runs in this one process, which holds the chip(s) for the
whole run and starts no child that touches JAX.  Every check raises on
failure, so any failed phase exits non-zero; there is no CPU fallback.
The last line of stdout is one JSON object:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

One chip, in order:
  * kernels — ``repro.blas`` SYRK / SYR2K / SYMM at 2048x5632 (the
    Newton–Schulz Gram and update shape of stablelm-1.6b's d_ff
    weights), bf16 and f32, forward and ``jax.grad``: every planned
    route is ``pallas``, the compiled program holds a
    ``tpu_custom_call``, and results match a plain f32 ``jnp``
    reference under ``default_matmul_precision("highest")``;
  * train — ``repro.launch.train.train`` at stablelm-1.6b width with
    ``--optimizer muon``: finite loss, one ``train_step`` compile, the
    weight shapes routed to ``pallas``;
  * serve — ``repro.launch.serve.serve`` at full width with
    ``--whiten cache``: every request completes, the whitening cache
    refreshed at least once with no failed refresh and no eigh
    fallback, and its d_model-sized products ran on ``pallas``.

Four chips: ``repro.blas`` on a ``("model",)`` mesh of 4 at one shape
that plans ``1d`` and one that plans ``ring`` (forward and grad,
against a one-chip f32 reference), then a few ``muon-syrk`` train
steps at full width and depth.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parent

#: run at full width and depth (24 layers).  The one-chip Muon step fits:
#: the v5e compiler puts it near 14.1 of 15.75 GB of HBM (it reports
#: 15.94 GB at 28 layers and 17.78 GB at 32, and refuses those)
ARCH = "stablelm-1.6b"
KERNEL_SHAPE = (2048, 5632)
#: max|out - ref| / max|ref| per (operand dtype, fwd/grad).  An indexing
#: or tiling fault is O(1).  bf16 operands multiply exactly into the f32
#: accumulator, so their forward reads 0-3.5e-7 on a TPU v5e: 1e-5 sees a
#: kernel that rounds its output or accumulates in bf16 (~2e-3).  bf16
#: grads are rounded to bf16 (3.1e-3-3.2e-3), and f32 operands multiply
#: at the MXU's bf16 precision (fwd 1.9e-4-2.6e-3, grad 2.2e-3-2.6e-3,
#: kernels and mesh routes alike)
TOL = {("bfloat16", "fwd"): 1e-5, ("bfloat16", "grad"): 5e-3,
       ("float32", "fwd"): 5e-3, ("float32", "grad"): 5e-3}


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def rel_err(got, want) -> float:
    import jax
    import numpy as np
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        require(g.shape == w.shape, f"shape {g.shape} != {w.shape}")
        require(bool(np.all(np.isfinite(g))), "non-finite output")
        worst = max(worst, float(np.max(np.abs(g - w)) / np.max(np.abs(w))))
    return worst


def device_phase(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    require(d.platform == "tpu", f"no TPU: JAX found {d.platform!r}")
    require(len(devs) == chips, f"wanted {chips} chips, found {len(devs)}")
    stats = d.memory_stats() or {}
    print(f"[device] bytes_limit={stats.get('bytes_limit')}")
    return devs


def _cases():
    """op -> (blas call, plain f32 reference, operand names)."""
    import jax.numpy as jnp
    from repro import blas

    def sym(s):
        return jnp.tril(s) + jnp.tril(s, -1).T

    return {
        "syrk": (lambda a, **kw: blas.syrk(a, fill="full", **kw),
                 lambda a: a @ a.T, ("a",)),
        "syr2k": (lambda a, b, **kw: blas.syr2k(a, b, fill="full", **kw),
                  lambda a, b: a @ b.T + b @ a.T, ("a", "b")),
        "symm": (lambda s, b, **kw: blas.symm(s, b, **kw),
                 lambda s, b: sym(s) @ b, ("s", "b")),
    }


def _operands(n1: int, n2: int, seed: int):
    import jax
    k = jax.random.split(jax.random.key(seed), 5)
    ops = {"a": jax.random.normal(k[0], (n1, n2)),
           "b": jax.random.normal(k[1], (n1, n2)),
           "s": jax.random.normal(k[2], (n1, n1))}
    # fixed loss weights: the gradient is then one backward op per input
    weights = {"syrk": jax.random.normal(k[3], (n1, n1)),
               "syr2k": jax.random.normal(k[3], (n1, n1)),
               "symm": jax.random.normal(k[4], (n1, n2))}
    return ops, weights


def _reference(ref, w, args):
    """Forward and grads of the plain f32 reference at full precision."""
    import jax
    import jax.numpy as jnp
    args = [x.astype(jnp.float32) for x in args]
    argnums = tuple(range(len(args)))
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(ref)(*args)
        grads = jax.jit(jax.grad(lambda *xs: jnp.sum(w * ref(*xs)),
                                 argnums))(*args)
    return fwd, grads


def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.blas import routing

    n1, n2 = KERNEL_SHAPE
    t_phase = time.perf_counter()
    ops, weights = _operands(n1, n2, seed=0)
    for dtype in (jnp.bfloat16, jnp.float32):
        for op, (call, ref, names) in _cases().items():
            args = [ops[n].astype(dtype) for n in names]
            w = weights[op]
            argnums = tuple(range(len(args)))
            fwd = call
            grad = jax.grad(lambda *xs: jnp.sum(w * call(*xs)), argnums)
            want = _reference(ref, w, args)
            for what, fn, expect in (("fwd", fwd, want[0]),
                                     ("grad", grad, want[1])):
                t0 = time.perf_counter()
                with routing.capture_routes() as log:
                    lowered = jax.jit(fn).lower(*args)
                compiled = lowered.compile()
                t_compile = time.perf_counter() - t0
                paths = sorted({(r.op, r.path, r.tiles) for r in log})
                require(bool(log) and all(r.path == "pallas" for r in log),
                        f"{op} {what}: routes {paths}")
                require("tpu_custom_call" in compiled.as_text(),
                        f"{op} {what}: no tpu_custom_call in the program")
                got = jax.block_until_ready(compiled(*args))
                err = rel_err(got, expect)
                print(f"[kernels] {op:5s} {jnp.dtype(dtype).name:8s} "
                      f"{what:4s} routes={paths} tpu_custom_call=yes "
                      f"rel_err={err:.3e} compile={t_compile:.1f}s "
                      f"at {time.perf_counter() - t_phase:.1f}s")
                tol = TOL[jnp.dtype(dtype).name, what]
                require(err <= tol, f"{op} {what} {jnp.dtype(dtype).name}: "
                        f"rel_err {err:.3e} > {tol}")


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def train_phase(argv, *, pallas: bool) -> dict:
    """``pallas``: the weight shapes must plan the Pallas route (one
    chip); on a mesh they plan the mesh wires instead."""
    from repro.blas import routing
    from repro.launch import train

    args = train.build_argparser().parse_args(argv)
    print(f"[train] {' '.join(argv)}")
    with routing.capture_routes() as log:
        out = train.train(args)
    for key in ("first_loss", "final_loss"):
        require(out[key] is not None and math.isfinite(out[key]),
                f"train {key} = {out[key]}")
    require(out["step_compiles"] == 1,
            f"train_step compiled {out['step_compiles']} times")
    routes = sorted({(r.op, r.n1, r.n2, r.path) for r in log})
    print(f"[train] routes: {routes}")
    if pallas:
        # stacked norm vectors (n_layers, d) plan dense below this n1
        big = [p for op, n1, n2, p in routes if n1 >= routing.PALLAS_MIN_N1]
        require(bool(big) and all(p == "pallas" for p in big),
                "weight shapes off the Pallas route")
    else:
        # a Pallas kernel inside a multi-device step cannot be partitioned
        require(bool(routes) and all(p != "pallas" for *_, p in routes),
                "a meshless Pallas route inside the mesh step")
    print(f"[train] step_s={out['step_s']} "
          f"step_compiles={out['step_compiles']}")
    return out


def serve_phase(argv) -> dict:
    from repro.blas import routing
    from repro.configs import get_config, get_smoke_config
    from repro.launch import serve

    args = serve.build_argparser().parse_args(argv)
    get = get_smoke_config if args.smoke else get_config
    d_model = get(args.arch).d_model
    print(f"[serve] {' '.join(argv)}")
    with routing.capture_routes() as log:
        out = serve.serve(args)
    require(out["completed"] == args.requests,
            f"{out['completed']} of {args.requests} requests completed")
    cache = out["cache"]
    require(cache["refreshes"] >= 1 and cache["factors_ready"] >= 1,
            f"no whitening refresh landed: {cache}")
    require(cache["failed_refreshes"] == 0,
            f"{cache['failed_refreshes']} whitening refreshes failed")
    require(cache["ns_fallbacks"] == 0,
            f"{cache['ns_fallbacks']} Newton–Schulz refreshes fell back "
            "to eigh")
    routes = sorted({(r.op, r.n1, r.n2, r.path) for r in log})
    print(f"[serve] routes: {routes}")
    require(all(p == "pallas" for op, n1, n2, p in routes
                if n1 >= routing.PALLAS_MIN_N1),
            f"serving products off the Pallas route: {routes}")
    require(any(n1 == d_model and p == "pallas"
                for op, n1, n2, p in routes),
            "the whitening products never reached the Pallas route")
    return out


def mesh_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro import blas
    from repro.blas import routing
    from repro.compat import make_mesh

    mesh = make_mesh((4,), ("model",))
    chip0 = jax.devices()[0]
    for (n1, n2), expect in (((512, 8192), "1d"), ((2048, 2048), "ring")):
        ops, weights = _operands(n1, n2, seed=1)
        for op, (call, ref, names) in _cases().items():
            print(blas.explain(op, n1, n2, mesh=mesh, grad=True))
            args = [ops[n] for n in names]
            w = weights[op]
            want = _reference(ref, w, [jax.device_put(x, chip0)
                                       for x in args])
            argnums = tuple(range(len(args)))
            fwd = lambda *xs: call(*xs, mesh=mesh)       # noqa: E731
            grad = jax.grad(lambda *xs: jnp.sum(w * call(*xs, mesh=mesh)),
                            argnums)
            for what, fn, expect_out in (("fwd", fwd, want[0]),
                                         ("grad", grad, want[1])):
                with routing.capture_routes() as log:
                    got = jax.block_until_ready(jax.jit(fn)(*args))
                paths = sorted({(r.op, r.path) for r in log})
                require(bool(log) and all(r.path == expect for r in log),
                        f"{op}[{n1}x{n2}] {what}: wanted {expect}, "
                        f"planned {paths}")
                err = rel_err(got, expect_out)
                print(f"[mesh] {op:5s} {n1}x{n2} {what:4s} routes={paths} "
                      f"rel_err_vs_one_chip={err:.3e}")
                tol = TOL[args[0].dtype.name, what]
                require(err <= tol, f"{op}[{n1}x{n2}] {what}: rel_err "
                        f"{err:.3e} > {tol}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase on four chips")
    args = ap.parse_args(argv)

    devs = device_phase(args.chips)
    require((REPO / "src" / "repro").is_dir(),
            f"no repro sources under {REPO / 'src'}")
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.compile_cache import enable_compilation_cache
    print(f"[cache] {enable_compilation_cache()}")

    t0 = time.perf_counter()
    if args.chips == 1:
        kernel_phase()
        print(f"[kernels] done at {time.perf_counter() - t0:.1f}s")
        train_phase(["--arch", ARCH, "--full", "--optimizer", "muon",
                     "--steps", "4", "--global-batch", "8",
                     "--seq-len", "512", "--log-every", "1"], pallas=True)
        print(f"[train] peak_bytes_in_use={_peak_bytes(devs[0])} "
              f"at {time.perf_counter() - t0:.1f}s")
        gc.collect()            # the train state must leave HBM first
        out = serve_phase(["--arch", ARCH, "--full", "--whiten", "cache",
                           "--requests", "8", "--slots", "4",
                           "--max-new", "16", "--s-max", "128"])
        print(f"[serve] tokens_per_s={out['tokens_per_s']:.1f} "
              f"startup_s={out['startup_s']:.1f} cache={out['cache']} "
              f"peak_bytes_in_use={_peak_bytes(devs[0])} "
              f"at {time.perf_counter() - t0:.1f}s")
    else:
        mesh_phase()
        print(f"[mesh] blas routes done at {time.perf_counter() - t0:.1f}s")
        train_phase(["--arch", ARCH, "--full", "--optimizer", "muon-syrk",
                     "--max-model", "4", "--steps", "3", "--global-batch", "8",
                     "--seq-len", "512", "--log-every", "1"],
                    pallas=False)
        for d in devs:
            print(f"[memory] device {d.id}: peak_bytes_in_use="
                  f"{_peak_bytes(d)}")
        print(f"[train] done at {time.perf_counter() - t0:.1f}s")

    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()

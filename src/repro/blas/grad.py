"""Custom VJPs for :mod:`repro.blas` — backward passes that are
themselves communication-optimal symmetric ops.

Without this layer, differentiability depended on which backend
``plan_route`` picked: the dense jnp path differentiates out of the
box, while the Pallas triangular kernels raise ``NotImplementedError``
under ``jax.grad`` and the shard_map schedules fall back to whatever
XLA derives for their collectives.  The paper closes the loop for us:
the cotangents of the three kernels are again the three kernels
(Al Daas et al. 2024; Beaumont et al., symmetric-kernel I/O analysis),
so the backward rules below are expressed as ``repro.blas`` calls and
re-enter ``plan_route`` — gradients get the triangular Pallas kernels
or the 1D/2D/3D mesh schedules on their own merits, with the forward
:class:`~repro.blas.routing.Route` pinned so both traces agree under
``jit``.

Math (f32 cotangent Ḡ; ``sym(M) = tril(M) + strict_tril(M)ᵀ`` is what
``blas.symm`` reads; with the alpha/beta epilogue
``C = α·op(A[,B]) + β·C₀``):

  SYRK   C = α·A·Aᵀ + β·C₀        dA = α·(Ḡ + Ḡᵀ)·A        — one SYMM
  SYR2K  C = α·(A·Bᵀ + B·Aᵀ)+β·C₀ dA = α·(Ḡ + Ḡᵀ)·B,
                                  dB = α·(Ḡ + Ḡᵀ)·A        — two SYMMs
  SYMM   C = sym(A)·B             dB = sym(A)·Ḡ             — one SYMM
                                  dA = tril(Ḡ·Bᵀ + B·Ḡᵀ), diag halved
                                                 — a tril-projected SYR2K
  and dC₀ = β·(fill-projection of Ḡ) — elementwise, no extra movement.

Fill handling: a "tril"/"packed" primal only exposes the lower
triangle, so its cotangent L enters the SYMM as the tril-valid operand
L with the *diagonal doubled* (sym(L + diag L) = L + Lᵀ); a "full"
primal exposes both mirrors and contributes tril(Ḡ) + triu(Ḡ)ᵀ.

Packed cotangents stay packed on every route: every mesh wire takes
the packed triangle as its SYMM operand (the 1D all-gather, the ring
slot stacks, a pure scatter into the 2D/3D extended triangle-block
shards; :data:`~repro.blas.meshpath.WIRES`), and the Pallas route
converts to a :class:`~repro.core.packing.TriTiles` via the
slice-granular gather converter and flows into the packed-operand
SYMM kernel — no direction densifies an n×n intermediate and no
direction performs an element-granular gather/scatter.  The diagonal
doubling/halving of the packed cotangent algebra is a *fused kernel
prologue/epilogue* on the Pallas route (``_diag_scale`` threads into
the SYMM body's VMEM symmetrize and the SYR2K epilogue) — the
standalone ``_packed_diag_scale`` elementwise pass survives only on
the mesh/dense wires, where it is cast to the cotangent dtype.  A
SYMM whose primal A was TriTiles also gets its dA back as TriTiles
(via a packed-fill SYR2K, itself packed on the mesh wire).

Residuals are the operands only — nothing symmetric is stored or
recomputed, so backward memory matches forward operand memory and the
backward communication volume obeys the same Thm 9 bounds as a forward
call of the corresponding op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.packing import (ShardedTriTiles, TriTiles, tril_size,
                            unpack_tril)
from . import routing

#: backward ops per forward op: (cotangent name, blas op that computes it)
COTANGENT_OPS = {
    "syrk": (("A", "symm"),),
    "syr2k": (("A", "symm"), ("B", "symm")),
    "symm": (("A", "syr2k"), ("B", "symm")),
}


# --------------------------------------------------------------------------
# cotangent shape algebra
# --------------------------------------------------------------------------
def _packed_diag_scale(n1: int, value: float, dtype=np.float32
                       ) -> np.ndarray:
    """Packed-tril mask that is ``value`` on the diagonal slots, 1 off,
    in ``dtype`` — callers pass the cotangent's dtype so a bf16 packed
    cotangent is never silently upcast by the multiply.  Only the
    mesh/dense wires still use this pass; the Pallas route fuses the
    same scaling into the kernel prologue/epilogue (``_diag_scale``)."""
    scale = np.ones(tril_size(n1), np.dtype(dtype))
    i = np.arange(n1)
    scale[i * (i + 3) // 2] = value
    return scale


def scale_matrix_diag(x: jax.Array, fill: str, n1: int, scale: float
                      ) -> jax.Array:
    """``x`` with its matrix-diagonal entries scaled — the ONE
    elementwise diag-scale used by every non-fused call site (cotangent
    doubling/halving, output-diag epilogue fallback, dense operand
    pre-scale).  ``fill="packed"`` uses the packed-slot mask, any other
    fill the eye mask; both masks are built in x's dtype so bf16 never
    silently upcasts."""
    if scale == 1.0:
        return x
    if fill == "packed":
        return x * jnp.asarray(_packed_diag_scale(n1, scale, x.dtype))
    return x * (1.0 + (scale - 1.0) * jnp.eye(n1, dtype=x.dtype))


def sym_cotangent(g: jax.Array, fill: str, n1: int) -> jax.Array:
    """Fill-shaped cotangent -> tril-valid Lhat with
    sym(Lhat) = dL/d(full symmetric C).

    tril/packed primals never expose the upper triangle, so any
    cotangent there belongs to structural zeros and is projected away;
    their diagonal is doubled because C_ii depends on the operands
    through a single exposed entry while sym() feeds it twice.
    """
    if fill == "full":
        return jnp.tril(g) + jnp.triu(g).swapaxes(-1, -2)
    if fill == "packed":
        return scale_matrix_diag(
            unpack_tril(g, n1, diag=True, symmetric=False), "tril", n1, 2.0)
    return scale_matrix_diag(jnp.tril(g), "tril", n1, 2.0)


def _c_cotangent(g: jax.Array, fill: str, beta: float) -> jax.Array:
    """dC₀ for ``C = α·op(...) + β·C₀``: beta times the fill-projection
    of Ḡ.  Only tril(C₀) is read, so the upper triangle gets zero; a
    "full" primal exposes each off-diagonal C₀ entry through both
    mirrors."""
    g = g.astype(jnp.float32)
    if fill == "packed":
        return beta * g
    if fill == "tril":
        return beta * jnp.tril(g)
    return beta * (jnp.tril(g) + jnp.tril(g.swapaxes(-1, -2), -1))


def _scale(x, alpha: float):
    return x if alpha == 1.0 else alpha * x


# --------------------------------------------------------------------------
# backward rules (all expressed as repro.blas calls)
# --------------------------------------------------------------------------
def _bwd_kwargs(route: routing.Route, mesh, interpret):
    """kwargs that let the backward blas call re-enter plan_route on the
    forward call's terms (mesh/axis for mesh routes, interpret for the
    single-device side; tiles come from the pin)."""
    if mesh is not None:
        return dict(mesh=mesh, axis=route.axis)
    return dict(interpret=interpret)


def _packed_mesh_symm(g_packed: jax.Array, other: jax.Array, n1: int,
                      route: routing.Route, mesh) -> jax.Array:
    """Packed-fill cotangent × operand on a mesh route: double the
    packed diagonal and feed the packed triangle straight onto whichever
    mesh wire the backward SYMM plans (:data:`~repro.blas.meshpath.
    WIRES`).  The cotangent stays in a packed layout end to end (no
    dense round-trip).  Returns None when the backward SYMM routes dense
    (GSPMD fallback)."""
    from . import meshpath
    br = routing.plan_route("symm", n1, other.shape[-1],
                            dtype=jnp.float32, batch=other.ndim > 2,
                            mesh=mesh, axis=route.axis)
    wire = meshpath.wire(br)
    if wire is None:
        return None
    lp = g_packed * jnp.asarray(
        _packed_diag_scale(n1, 2.0, g_packed.dtype))
    return wire.symm(lp, other, mesh, br)


def _packed_cotangent_tiles(g_packed: jax.Array, n1: int,
                            route: routing.Route) -> TriTiles:
    """Packed-fill cotangent on the Pallas route: one slice-granular
    gather into TriTiles; it then feeds the packed-operand SYMM
    kernel(s), whose fused prologue (``_diag_scale=2.0``) applies the
    diagonal doubling in VMEM — the cotangent never becomes an n×n
    dense array and no standalone elementwise scale pass runs."""
    bm = route.tiles[0]               # every Pallas route carries tiles
    return TriTiles.from_packed(g_packed, n1, bm)


def _syrk_bwd(g: jax.Array, a: jax.Array, *, fill: str, alpha: float,
              route: routing.Route, mesh, interpret) -> jax.Array:
    from . import api
    n1 = a.shape[-2]
    if isinstance(g, ShardedTriTiles):
        # a "sharded" primal's cotangent arrives as the same pytree; its
        # packed words flow onto the packed mesh wire like a packed fill
        g, fill = g.astype(jnp.float32).to_packed(), "packed"
    g = g.astype(jnp.float32)
    with routing.pinned(route):
        if fill == "packed" and mesh is not None:
            da = _packed_mesh_symm(g, a, n1, route, mesh)
            if da is not None:
                return _scale(da, alpha)
        if fill == "packed" and route.path == "pallas":
            at = _packed_cotangent_tiles(g, n1, route)
            return _scale(api.symm(at, a, interpret=interpret,
                                   _diag_scale=2.0), alpha)
        return _scale(api.symm(sym_cotangent(g, fill, n1), a,
                               **_bwd_kwargs(route, mesh, interpret)),
                      alpha)


def _syr2k_bwd(g: jax.Array, a: jax.Array, b: jax.Array, *, fill: str,
               alpha: float, route: routing.Route, mesh, interpret,
               diag_scale: float = 1.0):
    from . import api
    n1 = a.shape[-2]
    if isinstance(g, ShardedTriTiles):
        g, fill = g.astype(jnp.float32).to_packed(), "packed"
    g = g.astype(jnp.float32)
    # VJP of an output-diag-scaled rank update: scale the cotangent
    g = scale_matrix_diag(g, fill, n1, diag_scale)
    kw = _bwd_kwargs(route, mesh, interpret)
    with routing.pinned(route):
        if fill == "packed" and mesh is not None:
            da = _packed_mesh_symm(g, b, n1, route, mesh)
            if da is not None:
                db = _packed_mesh_symm(g, a, n1, route, mesh)
                return _scale(da, alpha), _scale(db, alpha)
        if fill == "packed" and route.path == "pallas":
            at = _packed_cotangent_tiles(g, n1, route)   # one gather
            da = api.symm(at, b, interpret=interpret, _diag_scale=2.0)
            db = api.symm(at, a, interpret=interpret, _diag_scale=2.0)
            return _scale(da, alpha), _scale(db, alpha)
        lhat = sym_cotangent(g, fill, n1)
        return (_scale(api.symm(lhat, b, **kw), alpha),
                _scale(api.symm(lhat, a, **kw), alpha))


def _symm_bwd(g: jax.Array, a, b: jax.Array, *,
              route: routing.Route, mesh, interpret,
              diag_scale: float = 1.0):
    from . import api
    g = g.astype(jnp.float32)
    kw = _bwd_kwargs(route, mesh, interpret)
    with routing.pinned(route):
        db = api.symm(a, g, _diag_scale=diag_scale, **kw)
        # only tril(A) is read, so dA lives in the lower triangle; the
        # diagonal is exposed once (vs twice for off-diag mirror pairs)
        # — the halving (×diag_scale/2) is fused into the SYR2K kernel
        # epilogue on the Pallas route, elementwise elsewhere
        if isinstance(a, ShardedTriTiles):
            # dA stays on the mesh: tril-projected SYR2K in packed fill,
            # scattered back into the mesh-resident shard layout
            dp = api.syr2k(g, b, fill="packed",
                           _diag_scale=diag_scale / 2, **kw)
            return ShardedTriTiles.from_packed(dp, a.n, a.c), db
        if isinstance(a, TriTiles):
            # dA stays packed: tril-projected SYR2K in packed fill,
            # gathered back into the TriTiles layout
            dp = api.syr2k(g, b, fill="packed",
                           _diag_scale=diag_scale / 2, **kw)
            return TriTiles.from_packed(dp, a.n, a.bm), db
        dsyr = api.syr2k(g, b, fill="tril",
                         _diag_scale=diag_scale / 2, **kw)
    return dsyr, db


# --------------------------------------------------------------------------
# custom_vjp entry points (called by api.py with the planned Route)
# --------------------------------------------------------------------------
def _rank_update_call(execute, bwd_rule, n_ops: int, operands, c32, *,
                      fill: str, alpha: float, beta: float,
                      route: routing.Route, mesh, interpret, out_dtype
                      ) -> jax.Array:
    """One custom_vjp factory for both SYRK (n_ops=1) and SYR2K
    (n_ops=2), with or without the C0 accumulator: the primal is
    ``execute(*operands, c)``, residuals are always the operands only,
    and the C0 branch just appends the elementwise dC tail."""
    has_c = c32 is not None

    def prim(*ops):
        c = ops[n_ops] if has_c else None
        return execute(*ops[:n_ops], c, fill=fill, alpha=alpha,
                       beta=beta if has_c else 0.0, route=route, mesh=mesh,
                       interpret=interpret, out_dtype=out_dtype)

    @jax.custom_vjp
    def f(*ops):
        return prim(*ops)

    def fwd(*ops):
        return prim(*ops), ops[:n_ops]   # dC needs no residual at all

    def bwd(res, g):
        d_ops = bwd_rule(g, *res, fill=fill, alpha=alpha, route=route,
                         mesh=mesh, interpret=interpret)
        if has_c:
            return d_ops + (_c_cotangent(g, fill, beta),)
        return d_ops

    f.defvjp(fwd, bwd)
    return f(*operands, c32) if has_c else f(*operands)


def syrk_call(a32: jax.Array, c32, *, fill: str, alpha: float, beta: float,
              route: routing.Route, mesh, interpret,
              out_dtype=None) -> jax.Array:
    from . import api

    def bwd_rule(g, a, **kw):
        return (_syrk_bwd(g, a, **kw),)

    return _rank_update_call(api._execute_syrk, bwd_rule, 1, (a32,), c32,
                             fill=fill, alpha=alpha, beta=beta, route=route,
                             mesh=mesh, interpret=interpret,
                             out_dtype=out_dtype)


def syr2k_call(a32: jax.Array, b32: jax.Array, c32, *, fill: str,
               alpha: float, beta: float, route: routing.Route, mesh,
               interpret, out_dtype=None,
               diag_scale: float = 1.0) -> jax.Array:
    from . import api
    execute = api._execute_syr2k if diag_scale == 1.0 else \
        functools.partial(api._execute_syr2k, diag_scale=diag_scale)
    bwd_rule = _syr2k_bwd if diag_scale == 1.0 else \
        functools.partial(_syr2k_bwd, diag_scale=diag_scale)
    return _rank_update_call(execute, bwd_rule, 2,
                             (a32, b32), c32, fill=fill, alpha=alpha,
                             beta=beta, route=route, mesh=mesh,
                             interpret=interpret, out_dtype=out_dtype)


def symm_call(a32, b32: jax.Array, *, route: routing.Route,
              mesh, interpret, out_dtype=None,
              diag_scale: float = 1.0,
              b_layout: str = "replicated") -> jax.Array:
    """``a32`` is a dense tril-valid array or a TriTiles — both are
    pytrees, so one custom_vjp covers them; a TriTiles primal gets its
    dA back as TriTiles (packed end to end).  ``diag_scale`` is the
    fused cotangent prologue: the kernel consumes the operand as
    sym(A) with the matrix diagonal scaled (2.0 turns a tril-exposed
    packed cotangent L into L + Lᵀ in VMEM).  ``b_layout`` only shapes
    the primal's staging (sharded-B pin); cotangent layouts are planned
    on their own terms, so it is not propagated to the backward rule."""
    from . import api

    def prim(a, b):
        return api._execute_symm(a, b, route=route, mesh=mesh,
                                 interpret=interpret, out_dtype=out_dtype,
                                 diag_scale=diag_scale, b_layout=b_layout)

    @jax.custom_vjp
    def f(a, b):
        return prim(a, b)

    def fwd(a, b):
        return prim(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        return _symm_bwd(g, a, b, route=route, mesh=mesh,
                         interpret=interpret, diag_scale=diag_scale)

    f.defvjp(fwd, bwd)
    return f(a32, b32)

"""The public symmetric-BLAS surface: ``syrk`` / ``syr2k`` / ``symm``.

One entry point per computation; every call is routed to the best
execution path for its (shape, dtype, mesh) by
:func:`repro.blas.routing.plan_route`:

  dense   — fused jnp (tiny shapes, CPU, GSPMD fallback);
  pallas  — triangular flat-grid TPU kernels (kernels/*.py), tiles from
            the autotuner;
  1d/ring/2d/3d/3d-limited — the mesh schedules when a mesh is
            present, looked up in :data:`~repro.blas.meshpath.WIRES`
            (the executors here never branch on a mesh path).

Contracts shared by all paths:
  * accumulation is always f32; ``out_dtype=None`` (default) returns the
    f32 accumulation instead of silently downcasting to the input dtype;
  * leading batch dimensions are supported (vmapped over the packed-tile
    kernels / dense path; on a mesh the stack rides the planned wire's
    collective payloads, else GSPMD dense);
  * SYRK/SYR2K ``fill``: "tril" (dense lower-triangular, default),
    "full" (symmetrized dense), or "packed" (row-major packed lower
    triangle, the wire format of the 1D algorithms);
  * SYMM reads only the lower triangle of its symmetric operand, which
    may arrive dense *or* as a pre-packed
    :class:`~repro.core.packing.TriTiles` — the packed layout then flows
    straight into the kernel with no densification;
  * SYRK/SYR2K accept ``c``/``beta``/``alpha`` for chunked accumulation:
    ``C_out = alpha·op(A[,B]) + beta·C`` with ``c`` in the same fill
    format as the output (only its lower triangle is read).  On the
    Pallas route the scale-and-accumulate runs inside the kernel
    epilogue; elsewhere it is a fused jnp combine.

Packed-layout discipline (the paper's ~n²/2 storage bound): on the
Pallas route, ``fill="packed"`` and ``fill="tril"`` never materialize an
n×n dense intermediate — the kernels emit diagonal-masked packed tiles
(epilogue in-kernel) and the fill conversion is a cached-index gather
(packed) or the output assembly itself (tril).  The same discipline
holds on the 1D/2D/3D mesh routes: 2D/3D schedules emit
:class:`~repro.core.packing.ShardedTriTiles` extended triangle-block
shards and only the ~n²/2 packed words are ever gathered
(``fill="tril"/"full"`` unpacks once, at the exit); SYMM scatters a
pre-packed operand straight into the per-device shards, and a dense
one enters through one ``pack_tril(jnp.tril(a))``.  The ring route
keeps the packed triangle for ``fill="packed"`` / ``"sharded"`` and for
packed or tiled SYMM operands; its dense fills are built from the
gathered slot stack in whole nb×nb blocks, and a dense SYMM operand is
cut into the slot stack the same way, with no element-packed round trip
(:mod:`repro.core.ringpath`).
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from ..core.packing import (PackedTriangle, ShardedTriTiles, TriTiles,
                            pack_tril, pack_tril_tiles, packed_to_tiles,
                            pad2d, tiles_to_packed, tril_size, unpack_tril,
                            unpack_tril_tiles)
from ..kernels.symm import symm_tiles
from ..kernels.syr2k import syr2k_tiles
from ..kernels.syrk import syrk_tiles
from . import grad, meshpath
from .autotune import heuristic_tiles
from .routing import Route, pinned, plan_route

_FILLS = ("tril", "full", "packed", "sharded")


def _check_fill(fill: str) -> None:
    if fill not in _FILLS:
        raise ValueError(f"fill must be one of {_FILLS}, got {fill!r}")


def _check_sharded_fill(batch: bool, c) -> None:
    """fill="sharded" returns the mesh-resident ShardedTriTiles layout:
    no batch stacking and no fused accumulator on that exit."""
    if batch:
        raise ValueError('fill="sharded" does not support leading batch '
                         "dims")
    if c is not None:
        raise ValueError('fill="sharded" does not support an accumulator '
                         "c")


def _sharded_grid_c(route) -> int:
    """Tile grid parameter for a ShardedTriTiles built off-grid (1d /
    pallas / dense routes): reuse the planned c when it names a real
    triangle grid, else the smallest one."""
    if route.choice is not None and route.choice.c >= 2:
        return route.choice.c
    return 2


def _out(x: jax.Array, out_dtype) -> jax.Array:
    return x if out_dtype is None else x.astype(out_dtype)


# --------------------------------------------------------------------------
# fill conversions (all f32 in, f32 out)
# --------------------------------------------------------------------------
def _tril_to_fill(tril: jax.Array, fill: str) -> jax.Array:
    if fill == "tril":
        return tril
    if fill == "full":
        return tril + jnp.tril(tril, -1).swapaxes(-1, -2)
    return pack_tril(tril)


def _packed_to_fill(packed: jax.Array, n1: int, fill: str) -> jax.Array:
    if fill == "packed":
        return packed
    return unpack_tril(packed, n1, diag=True, symmetric=(fill == "full"))


def _tiles_to_fill(tiles: jax.Array, n1: int, bm: int, fill: str
                   ) -> jax.Array:
    """Kernel-emitted packed tiles (T, bm, bm), diagonal already masked
    in-epilogue, to the requested fill.  "packed" is a slice-granular
    scatter-add — no n×n dense intermediate; "tril"/"full" assemble the
    output from whole tiles (:func:`unpack_tril_tiles`: static slices
    of the tile axis on the grids the kernels' tiles give, no re-tril /
    re-pack fixups)."""
    if fill == "packed":
        return tiles_to_packed(tiles, n1)
    npad = -(-n1 // bm) * bm
    dense = unpack_tril_tiles(tiles, npad, bm, symmetric=(fill == "full"))
    return dense[..., :n1, :n1]


def _fill_to_tiles(c: jax.Array, n1: int, bm: int, fill: str) -> jax.Array:
    """Fill-format C -> packed (T, bm, bm) tiles for the in-kernel
    beta-accumulate.  Only the lower triangle is consumed: strictly-upper
    grid tiles are never gathered, and the epilogue's diagonal mask runs
    *after* the accumulate, so intra-tile upper garbage cannot leak."""
    if fill == "packed":
        return packed_to_tiles(c, n1, bm)
    return pack_tril_tiles(pad2d(c, bm, bm), bm)


def _combine_fill(base: jax.Array, c: Optional[jax.Array], alpha: float,
                  beta: float, fill: str) -> jax.Array:
    """Fused jnp epilogue for the non-Pallas routes:
    ``alpha·base + beta·tril-projection(c)`` in the fill's own layout."""
    if alpha != 1.0:
        base = alpha * base
    if c is None or beta == 0.0:
        return base
    if fill == "packed":
        return base + beta * c
    if fill == "tril":
        return base + beta * jnp.tril(c)
    return base + beta * (jnp.tril(c)
                          + jnp.tril(c, -1).swapaxes(-1, -2))


# --------------------------------------------------------------------------
# single-matrix executors
# --------------------------------------------------------------------------
def _syrk_dense(a32: jax.Array, fill: str) -> jax.Array:
    g = a32 @ a32.swapaxes(-1, -2)
    return g if fill == "full" else _tril_to_fill(jnp.tril(g), fill)


def _syr2k_dense(a32: jax.Array, b32: jax.Array, fill: str) -> jax.Array:
    g = a32 @ b32.swapaxes(-1, -2)
    g = g + g.swapaxes(-1, -2)
    return g if fill == "full" else _tril_to_fill(jnp.tril(g), fill)


def _symm_dense(a32: jax.Array, b32: jax.Array) -> jax.Array:
    sym = jnp.tril(a32) + jnp.tril(a32, -1).swapaxes(-1, -2)
    return sym @ b32


def _syrk_pallas(a32: jax.Array, c32: Optional[jax.Array], fill: str,
                 tiles: Tuple[int, int], interpret: Optional[bool],
                 alpha: float = 1.0, beta: float = 0.0,
                 out_dtype=jnp.float32) -> jax.Array:
    bm, bk = tiles
    n1 = a32.shape[0]
    ap = pad2d(a32, bm, bk)
    # same predicate the kernel epilogue uses — don't build tiles it drops
    c0 = _fill_to_tiles(c32, n1, bm, fill) \
        if c32 is not None and beta != 0.0 else None
    packed_tiles = syrk_tiles(ap, bm=bm, bk=bk, interpret=interpret,
                              c0=c0, alpha=alpha, beta=beta,
                              out_dtype=out_dtype)
    return _tiles_to_fill(packed_tiles, n1, bm, fill)


def _syr2k_pallas(a32: jax.Array, b32: jax.Array,
                  c32: Optional[jax.Array], fill: str,
                  tiles: Tuple[int, int], interpret: Optional[bool],
                  alpha: float = 1.0, beta: float = 0.0,
                  out_dtype=jnp.float32,
                  diag_scale: float = 1.0) -> jax.Array:
    bm, bk = tiles
    n1 = a32.shape[0]
    ap, bp = pad2d(a32, bm, bk), pad2d(b32, bm, bk)
    c0 = _fill_to_tiles(c32, n1, bm, fill) \
        if c32 is not None and beta != 0.0 else None
    packed_tiles = syr2k_tiles(ap, bp, bm=bm, bk=bk, interpret=interpret,
                               c0=c0, alpha=alpha, beta=beta,
                               out_dtype=out_dtype, diag_scale=diag_scale)
    return _tiles_to_fill(packed_tiles, n1, bm, fill)


def _symm_pallas(a32: jax.Array, b32: jax.Array, tiles: Tuple[int, int],
                 interpret: Optional[bool],
                 out_dtype=jnp.float32) -> jax.Array:
    """Dense tril-valid A: tile-pack the lower triangle (the upper half
    never reaches kernel HBM — strictly-upper grid tiles are not
    gathered and diagonal tiles are symmetrized from tril in VMEM).
    A diag_scale on a dense operand is pre-applied by the executor."""
    bm, bn = tiles
    n1, n2 = b32.shape
    ap = pad2d(a32, bm, bm)
    bp = pad2d(b32, bm, bn)
    packed = pack_tril_tiles(ap, bm)
    return symm_tiles(packed, bp, bm=bm, bn=bn, interpret=interpret,
                      out_dtype=out_dtype)[:n1, :n2]


def _symm_pallas_tiles(a_tiles: jax.Array, b32: jax.Array, n1: int,
                       bm: int, bn: int, interpret: Optional[bool],
                       out_dtype=jnp.float32,
                       diag_scale: float = 1.0) -> jax.Array:
    """Pre-packed TriTiles A: the packed tiles flow straight into the
    kernel — no dense rebuild anywhere on the path; ``diag_scale`` is
    the fused cotangent prologue (diagonal doubling in VMEM)."""
    n2 = b32.shape[-1]
    bp = pad2d(b32, bm, bn)
    return symm_tiles(a_tiles, bp, bm=bm, bn=bn, interpret=interpret,
                      out_dtype=out_dtype,
                      diag_scale=diag_scale)[:n1, :n2]


# --------------------------------------------------------------------------
# densify telemetry: the packed wire should make these unreachable
# --------------------------------------------------------------------------
_DENSIFY_WARNED = set()


def _warn_densify(op: str, path: str) -> None:
    """One-time warning (per op/route) when a packed TriTiles operand has
    to be rebuilt dense.  After the mesh packed wire this only fires on
    the GSPMD/jnp dense fallback — anywhere else it is a regression."""
    key = (op, path)
    if key in _DENSIFY_WARNED:
        return
    _DENSIFY_WARNED.add(key)
    warnings.warn(f"repro.blas: packed TriTiles operand of {op} densified "
                  f"on the {path!r} route — the packed wire does not cover "
                  "this path", stacklevel=3)


# --------------------------------------------------------------------------
# batching helpers
# --------------------------------------------------------------------------
def _apply_batched(fn, *arrays, trailing=None):
    """vmap ``fn`` over flattened leading batch dims (shared by all
    operands), or call directly for unbatched operands.  ``trailing``
    gives per-operand core ranks (default 2 each)."""
    ranks = trailing or (2,) * len(arrays)
    lead = arrays[0].shape[:arrays[0].ndim - ranks[0]]
    for x, r in zip(arrays[1:], ranks[1:]):
        if x.shape[:x.ndim - r] != lead:
            raise ValueError("operands must share leading batch dims: "
                             f"{[x.shape for x in arrays]}")
    if not lead:
        return fn(*arrays)
    flat = [x.reshape((-1,) + x.shape[x.ndim - r:])
            for x, r in zip(arrays, ranks)]
    out = jax.vmap(fn)(*flat)
    return out.reshape(lead + out.shape[1:])


# --------------------------------------------------------------------------
# per-route executors (primal bodies; grad.py wraps these in custom_vjp)
# --------------------------------------------------------------------------
def route_scope(route: Route) -> str:
    """``blas.<op>.<path family>``: the named scope every executor runs
    under, so a device op of the call names its op and route in its HLO
    metadata (``3d-limited`` is of the ``3d`` family)."""
    return f"blas.{route.op}.{route.path.split('-')[0]}"


def _scoped(execute):
    """Run a route executor under :func:`route_scope` of its route."""
    @functools.wraps(execute)
    def run(*args, route: Route, **kw):
        with jax.named_scope(route_scope(route)):
            return execute(*args, route=route, **kw)
    return run


def _scale_sharded(st: ShardedTriTiles, alpha: float) -> ShardedTriTiles:
    if alpha == 1.0:
        return st
    return ShardedTriTiles(alpha * st.off, alpha * st.diag, st.n, st.c)


@_scoped
def _execute_syrk(a32: jax.Array, c32: Optional[jax.Array], *, fill: str,
                  alpha: float, beta: float, route: Route, mesh,
                  interpret: Optional[bool],
                  out_dtype=None) -> jax.Array:
    n1 = a32.shape[-2]
    wire = meshpath.wire(route)
    if fill == "sharded" and not (wire and wire.sharded):
        # off-grid routes produce the packed triangle; one block-granular
        # scatter puts it into the mesh-resident layout
        # (already inside this route's scope)
        packed = _execute_syrk.__wrapped__(
            a32, None, fill="packed", alpha=alpha, beta=0.0, route=route,
            mesh=mesh, interpret=interpret, out_dtype=out_dtype)
        return ShardedTriTiles.from_packed(packed, n1,
                                           _sharded_grid_c(route))
    if wire is not None:
        if fill == "sharded":
            return _scale_sharded(wire.syrk(a32, mesh, route), alpha)
        if wire.syrk_dense is not None and fill != "packed":
            base = wire.syrk_dense(a32, mesh, route, fill == "full")
        else:
            packed = meshpath.as_packed(wire.syrk(a32, mesh, route))
            base = _packed_to_fill(packed, n1, fill)
        return _combine_fill(base, c32, alpha, beta, fill)
    if route.path == "pallas":
        fn = functools.partial(_syrk_pallas, fill=fill, tiles=route.tiles,
                               interpret=interpret, alpha=alpha, beta=beta,
                               out_dtype=out_dtype or jnp.float32)
        if c32 is None:
            return _apply_batched(lambda a: fn(a, None), a32)
        crank = 1 if fill == "packed" else 2
        return _apply_batched(fn, a32, c32, trailing=(2, crank))
    return _combine_fill(_syrk_dense(a32, fill), c32, alpha, beta, fill)


@_scoped
def _execute_syr2k(a32: jax.Array, b32: jax.Array,
                   c32: Optional[jax.Array], *, fill: str, alpha: float,
                   beta: float, route: Route, mesh,
                   interpret: Optional[bool],
                   out_dtype=None, diag_scale: float = 1.0) -> jax.Array:
    n1 = a32.shape[-2]
    # in-kernel on the Pallas route (Epilogue.diag_scale); elementwise
    # fallback on every other route
    post = functools.partial(grad.scale_matrix_diag, fill=fill, n1=n1,
                             scale=diag_scale)
    wire = meshpath.wire(route)
    if fill == "sharded" and not (wire and wire.sharded):
        packed = _execute_syr2k.__wrapped__(
            a32, b32, None, fill="packed", alpha=alpha, beta=0.0,
            route=route, mesh=mesh, interpret=interpret,
            out_dtype=out_dtype, diag_scale=diag_scale)
        return ShardedTriTiles.from_packed(packed, n1,
                                           _sharded_grid_c(route))
    if wire is not None:
        if fill == "sharded":
            st = wire.syr2k(a32, b32, mesh, route)
            if diag_scale != 1.0:
                p = grad.scale_matrix_diag(st.to_packed(), "packed", n1,
                                           diag_scale)
                st = ShardedTriTiles.from_packed(p, n1, st.c)
            return _scale_sharded(st, alpha)
        if wire.syr2k_dense is not None and fill != "packed":
            base = wire.syr2k_dense(a32, b32, mesh, route, fill == "full")
        else:
            packed = meshpath.as_packed(wire.syr2k(a32, b32, mesh, route))
            base = _packed_to_fill(packed, n1, fill)
        return post(_combine_fill(base, c32, alpha, beta, fill))
    if route.path == "pallas":
        fn = functools.partial(_syr2k_pallas, fill=fill, tiles=route.tiles,
                               interpret=interpret, alpha=alpha, beta=beta,
                               out_dtype=out_dtype or jnp.float32,
                               diag_scale=diag_scale)
        if c32 is None:
            return _apply_batched(lambda a, b: fn(a, b, None), a32, b32)
        crank = 1 if fill == "packed" else 2
        return _apply_batched(fn, a32, b32, c32, trailing=(2, 2, crank))
    return post(_combine_fill(_syr2k_dense(a32, b32, fill), c32, alpha,
                              beta, fill))


@_scoped
def _execute_symm(a32: Union[jax.Array, TriTiles, ShardedTriTiles],
                  b32: jax.Array, *,
                  route: Route, mesh, interpret: Optional[bool],
                  out_dtype=None, diag_scale: float = 1.0,
                  b_layout: str = "replicated") -> jax.Array:
    if isinstance(a32, ShardedTriTiles):
        return _execute_symm_sharded(a32, b32, route=route, mesh=mesh,
                                     interpret=interpret,
                                     out_dtype=out_dtype,
                                     diag_scale=diag_scale,
                                     b_layout=b_layout)
    if isinstance(a32, TriTiles):
        return _execute_symm_tiles(a32, b32, route=route, mesh=mesh,
                                   interpret=interpret,
                                   out_dtype=out_dtype,
                                   diag_scale=diag_scale,
                                   b_layout=b_layout)
    pin_b = b_layout == "sharded"
    if diag_scale != 1.0:
        # dense operand: sym_s(A) = sym(A with pre-scaled diagonal) —
        # one elementwise pass on an already-dense array
        a32 = grad.scale_matrix_diag(a32, "tril", a32.shape[-1],
                                     diag_scale)
    wire = meshpath.wire(route)
    if wire is not None:
        if wire.symm_dense is not None:
            return wire.symm_dense(a32, b32, mesh, route, pin_b)
        return wire.symm(pack_tril(jnp.tril(a32)), b32, mesh, route, pin_b)
    if route.path == "pallas":
        fn = functools.partial(_symm_pallas, tiles=route.tiles,
                               interpret=interpret,
                               out_dtype=out_dtype or jnp.float32)
        return _apply_batched(fn, a32, b32)
    return _apply_batched(_symm_dense, a32, b32)


def _execute_symm_tiles(a: TriTiles, b32: jax.Array, *, route: Route,
                        mesh, interpret: Optional[bool],
                        out_dtype=None, diag_scale: float = 1.0,
                        b_layout: str = "replicated") -> jax.Array:
    """SYMM with a pre-packed symmetric operand.  The packed layout
    survives every route: straight into the kernel on the Pallas route
    (where ``diag_scale`` — the cotangent prologue — runs in VMEM),
    the packed triangle onto every mesh wire (the diag scale stays an
    elementwise pass in the cotangent's own dtype there).  Only the
    GSPMD/jnp dense fallback rebuilds a dense matrix — and says so once
    via :func:`_warn_densify`."""
    n1 = a.n
    pin_b = b_layout == "sharded"

    def scaled_packed():
        return grad.scale_matrix_diag(a.to_packed(), "packed", n1,
                                      diag_scale)

    wire = meshpath.wire(route)
    if wire is not None:
        return wire.symm(scaled_packed(), b32, mesh, route, pin_b)
    if route.path == "pallas":
        bm = a.bm                      # the layout fixes the row tile
        bn = route.tiles[1]
        fn = functools.partial(_symm_pallas_tiles, n1=n1, bm=bm, bn=bn,
                               interpret=interpret,
                               out_dtype=out_dtype or jnp.float32,
                               diag_scale=diag_scale)
        return _apply_batched(fn, a.tiles, b32, trailing=(3, 2))
    _warn_densify("symm", route.path)
    return grad.scale_matrix_diag(a.to_full(), "full", n1,
                                  diag_scale) @ b32


def _execute_symm_sharded(st: ShardedTriTiles, b32: jax.Array, *,
                          route: Route, mesh, interpret: Optional[bool],
                          out_dtype=None, diag_scale: float = 1.0,
                          b_layout: str = "replicated") -> jax.Array:
    """SYMM whose symmetric operand is already mesh-resident as
    ShardedTriTiles: the grid wires consume the shards directly (no
    distribute step for A), repacking only when the planned grid's c
    differs from the layout's; the 1d and ring wires take its packed
    words; off the mesh it goes through the packed triangle."""
    n1 = st.n
    pin_b = b_layout == "sharded"
    if diag_scale != 1.0:
        p = grad.scale_matrix_diag(st.to_packed(), "packed", n1,
                                   diag_scale)
        st = ShardedTriTiles.from_packed(p, n1, st.c)
    wire = meshpath.wire(route)
    if wire is not None:
        return wire.symm(st, b32, mesh, route, pin_b)
    if route.path == "pallas":
        bm = route.tiles[0]           # every Pallas route carries tiles
        return _execute_symm_tiles(st.to_tritiles(bm), b32, route=route,
                                   mesh=mesh, interpret=interpret,
                                   out_dtype=out_dtype)
    _warn_densify("symm", route.path)
    return st.to_full() @ b32


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------
def _resolve_beta(c, beta) -> float:
    """``beta=None`` means 1.0 when an accumulator is given, else 0.0."""
    if beta is None:
        return 1.0 if c is not None else 0.0
    beta = float(beta)
    if beta != 0.0 and c is None:
        raise ValueError("beta != 0 requires an accumulator c")
    return beta


def _check_c(c, fill: str, n1: int, lead: Tuple[int, ...]) -> None:
    if c is None:
        return
    want = lead + ((tril_size(n1),) if fill == "packed" else (n1, n1))
    if tuple(c.shape) != want:
        raise ValueError(f"accumulator c for fill={fill!r} must have "
                         f"shape {want}, got {tuple(c.shape)}")


def syrk(a, *, out_dtype=None, fill: str = "tril", mesh=None,
         axis: Optional[str] = None, tile=None,
         interpret: Optional[bool] = None, c=None, alpha: float = 1.0,
         beta: Optional[float] = None, M="auto") -> jax.Array:
    """C = alpha·A·Aᵀ + beta·C₀ for A (..., n1, n2), routed per regime.

    ``fill``: "tril" (default), "full", "packed", or "sharded" — the
    last returns the mesh-resident
    :class:`~repro.core.packing.ShardedTriTiles` layout (no gather at
    all; feed it back into :func:`symm` to stay on the wire).
    Accumulates in f32; ``out_dtype=None`` returns f32.  ``c`` is an
    optional accumulator in the *same fill format* as the output (only
    its lower triangle is read); ``beta`` defaults to 1.0 when ``c`` is
    given — chunked Gram updates are
    ``g = syrk(x_chunk, fill="packed", c=g)``.  On the Pallas route the
    epilogue (diag mask, scale-accumulate, out_dtype) runs inside the
    kernel.  ``M`` is the per-device memory budget in f32 words for the
    §IX memory-dependent regime ("auto": device-HBM probe /
    ``REPRO_BLAS_MEMORY_WORDS`` env; None disables).
    Reverse-differentiable on every route: the VJP is a SYMM executed
    through the same router (see :mod:`repro.blas.grad`).
    """
    _check_fill(fill)
    a = jnp.asarray(a)
    n1, n2 = a.shape[-2:]
    if fill == "sharded":
        _check_sharded_fill(a.ndim > 2, c)
    beta = _resolve_beta(c, beta)
    c = None if c is None else jnp.asarray(c)
    _check_c(c, fill, n1, a.shape[:-2])
    route = plan_route("syrk", n1, n2, dtype=a.dtype, batch=a.ndim > 2,
                       mesh=mesh, axis=axis, tile=tile, interpret=interpret,
                       fill=fill, accumulate=c is not None, M=M)
    a32 = a.astype(jnp.float32)
    c32 = None if c is None else c.astype(jnp.float32)
    return _out(grad.syrk_call(a32, c32, fill=fill, alpha=alpha, beta=beta,
                               route=route, mesh=mesh, interpret=interpret,
                               out_dtype=out_dtype), out_dtype)


def syr2k(a, b, *, out_dtype=None, fill: str = "tril", mesh=None,
          axis: Optional[str] = None, tile=None,
          interpret: Optional[bool] = None, c=None, alpha: float = 1.0,
          beta: Optional[float] = None, M="auto",
          _diag_scale: float = 1.0) -> jax.Array:
    """C = alpha·(A·Bᵀ + B·Aᵀ) + beta·C₀ for A, B (..., n1, n2), routed
    per regime.  Accumulator / ``fill`` / ``M`` contract as
    :func:`syrk`.

    Reverse-differentiable on every route: the VJP is two SYMMs through
    the same router (see :mod:`repro.blas.grad`).

    ``_diag_scale`` (internal, used by the SYMM backward) scales the
    matrix diagonal of the output — fused into the kernel epilogue on
    the Pallas route, an elementwise pass in the output's dtype
    elsewhere; incompatible with an accumulator ``c``."""
    _check_fill(fill)
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.shape != b.shape:
        raise ValueError(f"syr2k operands must match: {a.shape} vs "
                         f"{b.shape}")
    if _diag_scale != 1.0 and c is not None:
        raise ValueError("_diag_scale is incompatible with an "
                         "accumulator c")
    n1, n2 = a.shape[-2:]
    if fill == "sharded":
        _check_sharded_fill(a.ndim > 2, c)
    beta = _resolve_beta(c, beta)
    c = None if c is None else jnp.asarray(c)
    _check_c(c, fill, n1, a.shape[:-2])
    route = plan_route("syr2k", n1, n2, dtype=a.dtype, batch=a.ndim > 2,
                       mesh=mesh, axis=axis, tile=tile, interpret=interpret,
                       fill=fill, accumulate=c is not None, M=M)
    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    c32 = None if c is None else c.astype(jnp.float32)
    return _out(grad.syr2k_call(a32, b32, c32, fill=fill, alpha=alpha,
                                beta=beta, route=route, mesh=mesh,
                                interpret=interpret, out_dtype=out_dtype,
                                diag_scale=_diag_scale), out_dtype)


def symm(a_sym, b, *, out_dtype=None, mesh=None,
         axis: Optional[str] = None, tile=None,
         interpret: Optional[bool] = None, M="auto",
         b_layout: str = "replicated",
         _diag_scale: float = 1.0) -> jax.Array:
    """C = sym(A)·B for tril-valid A (..., n1, n1) and B (..., n1, n2).

    ``a_sym`` may be a dense array — only its lower triangle is read
    (the upper half may hold garbage) — a pre-packed
    :class:`~repro.core.packing.TriTiles`, in which case the packed
    layout feeds the Pallas kernel or the packed mesh wire directly
    (1d all-gather, 2d/3d extended triangle-block scatter, the ring
    slot stacks, stacked wires when batched), a row-major
    :class:`~repro.core.packing.PackedTriangle` (e.g. a
    ``fill="packed"`` SYRK output or a
    :class:`~repro.optim.gram.GramMonitor` state leaf), which is
    re-tiled by one pure scatter and then follows the TriTiles
    contract, or a mesh-resident
    :class:`~repro.core.packing.ShardedTriTiles` (e.g. the
    ``fill="sharded"`` output of :func:`syrk`), which the grid routes
    consume without any distribute step for A — the symmetric matrix
    is never densified beyond each path's working set.
    ``M`` is the per-device memory budget in f32 words for the §IX
    memory-dependent regime (contract as :func:`syrk`).
    Reverse-differentiable on every route: dB is a SYMM and dA a
    tril-projected SYR2K through the same router (see
    :mod:`repro.blas.grad`); the dA cotangent is zero on the unread
    upper triangle (and arrives as TriTiles/ShardedTriTiles when A did).

    ``b_layout="sharded"`` declares that B already lives row-sharded
    ``P(axis)`` on the mesh: the ring/2d/3d wires then pin their staged
    B row blocks to that sharding instead of letting GSPMD replicate
    the operand before the shard_map (the 1d wire column-shards B and
    ignores the hint).  The backward pass is unaffected — cotangent
    layouts are planned on their own terms.

    ``_diag_scale`` (internal, the fused cotangent prologue) computes
    C = sym_s(A)·B with the matrix diagonal of sym(A) scaled by s —
    in the kernel's VMEM symmetrize on the Pallas route, so a packed
    backward cotangent needs no standalone doubling pass.
    """
    if b_layout not in ("replicated", "sharded"):
        raise ValueError(f"b_layout must be 'replicated' or 'sharded', "
                         f"got {b_layout!r}")
    b = jnp.asarray(b)
    n1, n2 = b.shape[-2:]
    if isinstance(a_sym, PackedTriangle):
        # row-major packed vec -> packed tiles: one pure scatter, no
        # dense intermediate; from here the TriTiles contract applies
        bm = tile[0] if isinstance(tile, tuple) else \
            heuristic_tiles("symm", a_sym.n, n2)[0]
        a_sym = TriTiles.from_packed(a_sym.vec, a_sym.n, bm)
    if isinstance(a_sym, ShardedTriTiles):
        if a_sym.n != n1 or b.ndim > 2:
            raise ValueError(f"symm shapes: ShardedTriTiles(n={a_sym.n}) "
                             f"vs b {b.shape} (no batch dims)")
        route = plan_route("symm", n1, n2, dtype=b.dtype, batch=False,
                           mesh=mesh, axis=axis, tile=tile,
                           interpret=interpret, fill="sharded", M=M)
        a32 = a_sym.astype(jnp.float32)
    elif isinstance(a_sym, TriTiles):
        if a_sym.n != n1 or a_sym.batch_shape != b.shape[:-2]:
            raise ValueError(f"symm shapes: TriTiles(n={a_sym.n}, "
                             f"batch={a_sym.batch_shape}) vs b {b.shape}")
        route = plan_route("symm", n1, n2, dtype=b.dtype, batch=b.ndim > 2,
                           mesh=mesh, axis=axis, tile=tile,
                           interpret=interpret, fill="tritiles", M=M)
        a32 = a_sym.astype(jnp.float32)
    else:
        a_sym = jnp.asarray(a_sym)
        if a_sym.shape[-2:] != (n1, n1):
            raise ValueError(f"symm shapes: a {a_sym.shape} vs b {b.shape}")
        route = plan_route("symm", n1, n2, dtype=b.dtype, batch=b.ndim > 2,
                           mesh=mesh, axis=axis, tile=tile,
                           interpret=interpret, M=M)
        a32 = a_sym.astype(jnp.float32)
    b32 = b.astype(jnp.float32)
    return _out(grad.symm_call(a32, b32, route=route, mesh=mesh,
                               interpret=interpret, out_dtype=out_dtype,
                               diag_scale=_diag_scale,
                               b_layout=b_layout), out_dtype)


def explain(op: str, n1: int, n2: int, *, dtype=jnp.float32, mesh=None,
            axis: Optional[str] = None, grad: bool = False,
            M="auto") -> str:
    """Human-readable routing decision for an (op, shape, mesh) triple.

    Mesh wires appear as ``1d`` (block-row all-gather), ``2d``/``3d``
    (extended triangle-block grids), ``3d-limited`` (§IX streamed
    chunks), or ``ring`` — the computation-optimal cyclic-shift
    schedule whose ``ring P=… nb=… shifts=…`` line shows the
    ``⌊P/2⌋``-shift plan that holds per-device flops near half the 2d
    route's on SYRK/SYR2K wires.
    ``M`` is the per-device memory budget in f32 words (contract as
    :func:`syrk`) — pass a small value to see where the §IX
    memory-dependent "3d-limited" route takes over, with its chunk and
    predicted word count.  With ``grad=True``, also shows one line per
    backward-pass op — the route each cotangent takes when ``jax.grad``
    flows through the call (planned under the forward Route pin, exactly
    as the VJP does, including the forward's resolved budget)."""
    from .grad import COTANGENT_OPS
    r = plan_route(op, n1, n2, dtype=dtype, mesh=mesh, axis=axis, M=M)
    if not grad:
        return r.describe()
    lines = [r.describe()]
    for wrt, bop in COTANGENT_OPS[op]:
        with pinned(r):
            br = plan_route(bop, n1, n2, dtype=jnp.float32, mesh=mesh,
                            axis=r.axis)
        lines.append(f"  d{wrt}: {br.describe()}")
    return "\n".join(lines)

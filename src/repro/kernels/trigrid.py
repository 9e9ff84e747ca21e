"""Shared triangular-grid scheduler for the Pallas symmetric kernels.

The three kernels (syrk / syr2k / symm) share one scheduling discipline
— DESIGN §3, the TPU adaptation of the paper's sequential algorithms —
and this module owns every piece of it so the per-kernel files reduce to
their MXU compute bodies:

  * **cached lookup tables** (`tri_coords`, `symm_lookup`): the O(nt²)
    Python loops that build the scalar-prefetched (i, j) / flat-index
    tables run once per grid size, not once per trace;
  * **grid-spec construction**: the flat lower-triangle grid of
    T = nt(nt+1)/2 steps for the rank-update kernels and the
    (nt, n2/bn, nt) packed-operand grid for SYMM, both driven by
    scalar-prefetch index maps;
  * **the interpret-mode default** (CPU ⇒ interpret);
  * **the fused epilogue**, run inside the kernel at the last
    contraction step: diagonal-tile masking, alpha/beta
    scale-and-accumulate against an existing packed C, the optional
    matrix-diagonal scale (the packed cotangent algebra's
    halving/doubling — see ``Epilogue.diag_scale`` and the SYMM body's
    ``diag_scale`` prologue), and the out_dtype cast — so no masking,
    scaling, or conversion happens post-hoc in XLA and the packed
    (T, bm, bm) tiles in HBM are final.

Accumulation always happens in an f32 VMEM scratch tile that stays
resident across the innermost contraction axis (the paper's
resident-triangle / streamed-panel structure); the HBM output is
written exactly once per tile, already masked/combined/cast.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: scoped VMEM Mosaic grants a kernel unless its compiler params ask
#: for more (TPU v5e)
DEFAULT_SCOPED_VMEM = 16 << 20
#: most a kernel of this module asks for: a v5e core has 128 MiB of
#: VMEM, and the rest stays with Mosaic's internal scratch
MAX_SCOPED_VMEM = 96 << 20


def rank_update_vmem(n_in: int, bm: int, bk: int, itemsize: int = 4,
                     accumulate: bool = False) -> int:
    """VMEM bytes one rank-update grid step holds: ``n_in`` streamed
    (bm, bk) panels and the (bm, bm) output tile (and C0 tile, if
    accumulating), each double-buffered, the f32 accumulator, and the
    body's f32 panel casts and product."""
    panels = n_in * bm * bk
    tiles = bm * bm * (2 if accumulate else 1)
    return (2 * (panels * itemsize + tiles * 4) + bm * bm * 4
            + panels * 4 + bm * bm * 4)


def sym_stream_vmem(bm: int, bn: int, itemsize: int = 4) -> int:
    """VMEM bytes one SYMM grid step holds: the (bm, bm) packed tile and
    the (bm, bn) panel and output block, each double-buffered, the f32
    accumulator, and the body's f32 tile fixups (transpose, tril,
    symmetrised, selected: four tile-sized temporaries) and product."""
    return (2 * ((bm * bm + bm * bn) * itemsize + bm * bn * 4)
            + bm * bn * 4 + 4 * bm * bm * 4 + bm * bn * 4)


def compiler_params(vmem: int) -> Optional[pltpu.CompilerParams]:
    """Compiler params for a kernel whose grid step holds ``vmem``
    bytes: none while Mosaic's default scoped VMEM holds it with room
    to spare, else a limit of twice that, up to ``MAX_SCOPED_VMEM``."""
    if 2 * vmem <= DEFAULT_SCOPED_VMEM:
        return None
    return pltpu.CompilerParams(
        vmem_limit_bytes=min(MAX_SCOPED_VMEM, 2 * vmem))


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """The shared interpret-mode default: interpret on CPU, compiled on
    accelerator backends, unless the caller pins it."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


# --------------------------------------------------------------------------
# cached lookup tables (one Python-loop build per grid size)
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def tri_coords(nt: int) -> Tuple[np.ndarray, np.ndarray]:
    """(imap, jmap) int32 row/col tile indices of the flat lower-triangle
    grid, row-major: step t computes output tile (imap[t], jmap[t]).
    Derived from the one canonical enumeration in core.packing."""
    from ..core.packing import tile_tril_coords
    coords = tile_tril_coords(nt)
    imap = np.ascontiguousarray(coords[:, 0], dtype=np.int32)
    jmap = np.ascontiguousarray(coords[:, 1], dtype=np.int32)
    imap.setflags(write=False)
    jmap.setflags(write=False)
    return imap, jmap


@functools.lru_cache(maxsize=None)
def symm_lookup(nt: int) -> Tuple[np.ndarray, np.ndarray]:
    """SYMM's packed-operand access tables, flattened over (i, k):
    ``flat`` is the tile index into the packed triangle
    (tri(max(i,k)) + min(i,k)) and ``mode`` the in-VMEM fixup
    (0: as-is, 1: transpose, 2: diagonal — symmetrize from tril)."""
    flat = np.zeros((nt, nt), np.int32)
    mode = np.zeros((nt, nt), np.int32)
    for i in range(nt):
        for k in range(nt):
            hi, lo = max(i, k), min(i, k)
            flat[i, k] = hi * (hi + 1) // 2 + lo
            mode[i, k] = 2 if i == k else (1 if k > i else 0)
    flat = flat.ravel()
    mode = mode.ravel()
    flat.setflags(write=False)
    mode.setflags(write=False)
    return flat, mode


# --------------------------------------------------------------------------
# fused epilogue
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Epilogue:
    """What happens to the f32 accumulator at the last contraction step,
    inside the kernel: ``out = mask_diag(alpha·acc + beta·C0)`` cast to
    ``out_dtype``.  ``accumulate=True`` means a packed-tile C0 array
    rides along as an extra streamed input.

    ``diag_scale`` scales the *matrix-diagonal* elements (the diagonal
    of grid-diagonal tiles) in the VMEM scratch before the cast — the
    fused half of the packed cotangent algebra: a SYMM backward's
    tril-projected SYR2K needs its diagonal halved
    (``diag_scale=0.5``), and fusing it here removes the standalone
    elementwise ``_packed_diag_scale`` pass over the packed output."""
    alpha: float = 1.0
    beta: float = 0.0
    accumulate: bool = False
    out_dtype: object = jnp.float32
    diag_scale: float = 1.0

    def apply(self, acc: jax.Array, c0: Optional[jax.Array],
              is_diag, bm: int) -> jax.Array:
        """acc (bm, bm) f32 -> epilogued (bm, bm) in out_dtype."""
        if self.alpha != 1.0:
            acc = self.alpha * acc
        if self.accumulate:
            acc = acc + self.beta * c0.astype(jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 1)
        keep = jnp.logical_or(jnp.logical_not(is_diag), rows >= cols)
        acc = jnp.where(keep, acc, 0.0)
        if self.diag_scale != 1.0:
            on_diag = jnp.logical_and(is_diag, rows == cols)
            acc = jnp.where(on_diag, self.diag_scale * acc, acc)
        return acc.astype(self.out_dtype)


# --------------------------------------------------------------------------
# rank-update scheduler (SYRK / SYR2K): flat triangular grid
# --------------------------------------------------------------------------
def _rank_update_kernel(im_ref, jm_ref, *refs, nk: int, bm: int, n_in: int,
                        body: Callable, ep: Epilogue):
    t = pl.program_id(0)
    k = pl.program_id(1)
    in_refs = refs[:n_in]
    c0_ref = refs[n_in] if ep.accumulate else None
    o_ref, acc_ref = refs[-2], refs[-1]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += body(*(r[...] for r in in_refs))

    @pl.when(k == nk - 1)
    def _epilogue():
        c0 = c0_ref[0] if ep.accumulate else None
        is_diag = im_ref[t] == jm_ref[t]
        o_ref[0] = ep.apply(acc_ref[...], c0, is_diag, bm)


def rank_update(body: Callable, operands: Sequence[jax.Array], rows: str, *,
                name: str, bm: int, bk: int,
                interpret: Optional[bool] = None,
                epilogue: Optional[Epilogue] = None,
                c0: Optional[jax.Array] = None) -> jax.Array:
    """Run a symmetric rank-update over the flat lower-triangle grid.

    ``operands``: (n1, n2) panels streamed as (bm, bk) blocks; ``rows``
    is one char per operand — 'i' streams row-block imap[t], 'j' streams
    jmap[t].  ``body(*panels) -> (bm, bm)`` f32 contribution of one
    contraction step; ``name`` names the kernel (``"syrk"``,
    ``"syr2k"``) in the jaxpr, the HLO and the profiler.  ``c0``: packed
    tiles (T, bm, bm) consumed by the epilogue's beta-accumulate.
    Returns packed tiles (T, bm, bm) in ``epilogue.out_dtype`` with
    diagonal tiles lower-masked — the final HBM layout, no post-hoc XLA
    fixup required.
    """
    ep = epilogue or Epilogue()
    interpret = resolve_interpret(interpret)
    n1, n2 = operands[0].shape
    assert len(rows) == len(operands)
    assert n1 % bm == 0 and n2 % bk == 0, (n1, n2, bm, bk)
    for x in operands[1:]:
        assert x.shape == (n1, n2), (x.shape, n1, n2)
    nt, nk = n1 // bm, n2 // bk
    imap, jmap = tri_coords(nt)
    T = len(imap)

    def row_spec(which: str) -> pl.BlockSpec:
        if which == "i":
            return pl.BlockSpec((bm, bk), lambda t, k, im, jm: (im[t], k))
        return pl.BlockSpec((bm, bk), lambda t, k, im, jm: (jm[t], k))

    tile_spec = pl.BlockSpec((1, bm, bm), lambda t, k, im, jm: (t, 0, 0))
    in_specs = [row_spec(w) for w in rows]
    inputs = list(operands)
    if ep.accumulate:
        assert c0 is not None and c0.shape == (T, bm, bm), \
            (None if c0 is None else c0.shape, T, bm)
        in_specs.append(tile_spec)
        inputs.append(c0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, nk),
        in_specs=in_specs,
        out_specs=tile_spec,
        scratch_shapes=[pltpu.VMEM((bm, bm), jnp.float32)],
    )
    kernel = functools.partial(_rank_update_kernel, nk=nk, bm=bm,
                               n_in=len(operands), body=body, ep=ep)
    itemsize = max(x.dtype.itemsize for x in operands)
    vmem = rank_update_vmem(len(operands), bm, bk, itemsize, ep.accumulate)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, bm, bm), ep.out_dtype),
        interpret=interpret, name=name,
        compiler_params=compiler_params(vmem),
    )(jnp.asarray(imap), jnp.asarray(jmap), *inputs)


# --------------------------------------------------------------------------
# packed-operand scheduler (SYMM): (nt, n2/bn, nt) grid over tile lookups
# --------------------------------------------------------------------------
def _sym_stream_kernel(flat_ref, mode_ref, a_ref, b_ref, o_ref, acc_ref, *,
                       nk: int, body: Callable, out_dtype):
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += body(a_ref[0], mode_ref[i * nk + k], b_ref[...])

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def sym_stream(body: Callable, a_tiles: jax.Array, b: jax.Array, *,
               name: str, bm: int, bn: int,
               interpret: Optional[bool] = None,
               out_dtype=jnp.float32) -> jax.Array:
    """Run a symmetric-times-dense product with A stored as packed tiles.

    ``a_tiles``: (T, bm, bm) packed lower-triangle tiles of sym(A)
    (diagonal tiles tril-valid — their upper halves are never read);
    ``b``: (n1, n2).  Each grid step fetches tile flat[i·nt+k] via the
    cached scalar-prefetch table and ``body(a_tile, mode, b_panel)``
    returns the (bm, bn) f32 contribution (mode 0/1/2 selects
    as-is / transpose / diagonal-symmetrize).  Output is (n1, n2) in
    ``out_dtype``, cast in-kernel; ``name`` names the kernel
    (``"symm"``).
    """
    interpret = resolve_interpret(interpret)
    n1, n2 = b.shape
    assert n1 % bm == 0 and n2 % bn == 0, (n1, n2, bm, bn)
    nt = n1 // bm
    assert a_tiles.shape == (nt * (nt + 1) // 2, bm, bm), \
        (a_tiles.shape, nt, bm)
    nk = nt
    flat, mode = symm_lookup(nt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nt, n2 // bn, nk),
        in_specs=[
            pl.BlockSpec((1, bm, bm),
                         lambda i, j, k, fl, md: (fl[i * nk + k], 0, 0)),
            pl.BlockSpec((bm, bn), lambda i, j, k, fl, md: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, fl, md: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(_sym_stream_kernel, nk=nk, body=body,
                               out_dtype=out_dtype)
    itemsize = max(a_tiles.dtype.itemsize, b.dtype.itemsize)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n1, n2), out_dtype),
        interpret=interpret, name=name,
        compiler_params=compiler_params(sym_stream_vmem(bm, bn, itemsize)),
    )(jnp.asarray(flat), jnp.asarray(mode), a_tiles, b)

"""Packed lower-triangle storage utilities (numpy + jax variants).

The symmetric communication savings come from moving only the ~n²/2 unique
entries.  We provide element packing (row-major over the lower triangle
including the diagonal) and *tile-granular* packing (lower triangle of the
tile grid, each tile dense) — the latter is what the TPU kernels and
parallel algorithms use to keep loads MXU-aligned (DESIGN §3).

Converter discipline (the PR-5 rewrite): no converter performs an
element-granular gather or scatter.  Row-major packed offsets are
quadratic in the row index, so no pure reshape exists between the packed
vector and any 2-D layout — but every matrix row (and every intra-tile
row of every tile) *is* one contiguous slice of the packed vector.  The
element-packed converters therefore move data as a single
`lax.gather`/`lax.scatter_add` whose index count is the number of rows
touched — O(n) for dense↔packed, O(T·bm) = O(n²/bm) for tiles↔packed —
with the per-element work reduced to one vectorized intra-tile mask.
The tile↔dense converters move whole tiles by static slices: the TPU
compiler lowers a gather or scatter of whole 1024×1024 tiles to a
serial loop per op, and a static slice to a plain fusion.  Only a grid
of small tiles too large to unroll (:func:`_unrolls`) keeps one gather
/ scatter over the tile axis, which stays one op (one short loop over
a stack) there.  Ballard et
al.'s point that layout conversion must not re-move the data is what
this buys: the old per-element tables made the packed *backward* path
~30× slower than tril at n=1024 (XLA serializes element-row scatters);
the slice-granular converters are 200–600× faster and their VJPs
(gather ↔ scatter-add transpose pairs) inherit the same granularity.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def tril_size(n: int, diag: bool = True) -> int:
    return n * (n + 1) // 2 if diag else n * (n - 1) // 2


def pad2d(x, m0: int, m1: int):
    """Zero-pad a 2-D array up to multiples of (m0, m1) (jnp)."""
    p0 = -x.shape[0] % m0
    p1 = -x.shape[1] % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


def tril_indices(n: int, diag: bool = True) -> Tuple[np.ndarray, np.ndarray]:
    return np.tril_indices(n, 0 if diag else -1)


# ---- slice-granular converter machinery ------------------------------------
@functools.lru_cache(maxsize=None)
def tril_row_starts(n: int, diag: bool = True) -> np.ndarray:
    """(n,) int32 packed offset of each matrix row: row ``r`` of the
    row-major packed triangle starts at r(r+1)/2 (r(r−1)/2 without the
    diagonal).  Cached and read-only; note the offsets do not depend on
    ``n`` beyond the length — a packed prefix stays valid under grid
    padding."""
    r = np.arange(n, dtype=np.int64)
    out = (r * (r + 1) // 2 if diag else r * (r - 1) // 2).astype(np.int32)
    out.setflags(write=False)
    return out


def _gather_rows(p: jax.Array, starts: np.ndarray, width: int) -> jax.Array:
    """(L,) -> (S, width) where row s is ``p[starts[s] : starts[s]+width]``
    — ONE gather with S contiguous-slice index rows (slice-granular: S is
    the row count, never the element count).  All starts must leave the
    slice in bounds."""
    idx = jnp.asarray(starts, jnp.int32).reshape(-1, 1)
    dnums = jax.lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,))
    return jax.lax.gather(p, idx, dnums, slice_sizes=(width,))


def _scatter_add_rows(rows: jax.Array, starts: np.ndarray, length: int
                      ) -> jax.Array:
    """Transpose of :func:`_gather_rows`: scatter-add (S, width) rows into
    a zeros(length) vector at the given starts.  Overlapping windows must
    only ever contribute zeros (callers mask first)."""
    idx = jnp.asarray(starts, jnp.int32).reshape(-1, 1)
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1,), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0,))
    return jax.lax.scatter_add(jnp.zeros((length,), rows.dtype), idx, rows,
                               dnums)


def _over_batch(fn, x, core_rank: int):
    """Apply a single-sample converter over flattened leading batch dims."""
    lead = x.shape[:x.ndim - core_rank]
    if not lead:
        return fn(x)
    flat = x.reshape((-1,) + x.shape[x.ndim - core_rank:])
    out = jax.vmap(fn)(flat)
    return out.reshape(lead + out.shape[1:])


def _iota2(shape, axis0: int, axis1: int):
    r = jax.lax.broadcasted_iota(jnp.int32, shape, axis0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, axis1)
    return r, c


def pack_tril(x, diag: bool = True):
    """(…, n, n) -> (…, n(n±1)/2) packed lower triangle (jnp).

    Only the lower triangle is read (the upper half may hold garbage,
    including NaN — it is where-masked away, never multiplied).  One
    scatter-add with n contiguous row slices; no per-element indexing."""
    n = x.shape[-1]
    L = tril_size(n, diag)
    if L == 0:
        return jnp.zeros(x.shape[:-2] + (0,), x.dtype)
    starts = tril_row_starts(n, diag)
    shift = 0 if diag else 1
    w = n if diag else max(n - 1, 1)

    def one(xm):
        rows, cols = _iota2((n, n), 0, 1)
        masked = jnp.where(cols + shift <= rows, xm,
                           jnp.zeros((), xm.dtype))
        # row r's slice [starts[r], starts[r]+w) overruns its own packed
        # segment into the next row's — but only with the masked zeros
        return _scatter_add_rows(masked[:, :w], starts, L)

    return _over_batch(one, x, 2)


def unpack_tril(p, n: int, diag: bool = True, symmetric: bool = True):
    """Packed (…, n(n±1)/2) -> full (…, n, n); mirrors into the upper
    triangle when ``symmetric``.  One gather with n contiguous row slices
    plus a vectorized mask; no per-element indexing."""
    # the gather clamps out-of-bounds starts, so a wrong-length input
    # would silently produce garbage where fancy indexing used to raise
    assert p.shape[-1] == tril_size(n, diag), (p.shape, n, diag)
    if tril_size(n, diag) == 0:
        out = jnp.zeros(p.shape[:-1] + (n, n), p.dtype)
        return out
    starts = tril_row_starts(n, diag)
    shift = 0 if diag else 1
    w = n if diag else max(n - 1, 1)

    def one(pv):
        e = _gather_rows(pv, starts, w)
        if w < n:
            e = jnp.pad(e, ((0, 0), (0, n - w)))
        rows, cols = _iota2((n, n), 0, 1)
        out = jnp.where(cols + shift <= rows, e, jnp.zeros((), e.dtype))
        if symmetric:
            mirror = jnp.swapaxes(out, -1, -2)
            if diag:
                out = jnp.where(rows == cols, out, out + mirror)
            else:
                out = out + mirror
        return out

    return _over_batch(one, p, 1)


# ---- tile-granular packing -------------------------------------------------
def tile_tril_count(nt: int) -> int:
    """Number of tiles in the lower triangle (incl. diagonal) of an nt×nt
    tile grid."""
    return nt * (nt + 1) // 2


@functools.lru_cache(maxsize=None)
def tile_tril_coords(nt: int) -> np.ndarray:
    """(T, 2) array of (i, j) tile coords, row-major lower triangle.

    Cached: the O(nt²) Python loop runs once per grid size, not once per
    trace of every kernel call."""
    out = [(i, j) for i in range(nt) for j in range(i + 1)]
    arr = np.array(out, dtype=np.int64).reshape(-1, 2)
    arr.setflags(write=False)
    return arr


def tile_flat_index(i: int, j: int) -> int:
    """Flat index of tile (i, j), j <= i, in row-major lower-tri order."""
    return i * (i + 1) // 2 + j


#: the largest tile count T = nt(nt+1)/2 (nt ≤ 16) that the tile↔dense
#: converters always unroll into static whole-tile slices
STATIC_TILE_MAX = 136

#: the largest tile side the converters may move by one gather / scatter
#: over the tile axis, on a grid past :data:`STATIC_TILE_MAX`.  Compiled
#: for a TPU v5e, a gather or scatter of whole 1024-row tiles lowers to a
#: serial loop per op (10 ``while`` around one 24-stacked SYRK), while
#: at 128-row tiles it stays one op, or one loop over a stack.  Unrolling
#: T = 2016 such tiles (n = 8064) made one SYRK compile in 93 s against
#: 0.6 s gathered.
GATHER_TILE_MAX = 128


def _unrolls(nt: int, tile: int) -> bool:
    """Whether the tile↔dense converters move an nt×nt grid of
    (tile, tile) tiles by static slices rather than one gather."""
    return tile > GATHER_TILE_MAX or tile_tril_count(nt) <= STATIC_TILE_MAX


def pack_tril_tiles(x, tile: int):
    """(…, n, n) -> (…, T, tile, tile): dense tiles of the lower triangle of
    the tile grid (diagonal tiles kept dense — the intra-tile upper halves of
    diagonal tiles are the only redundancy, a 1/nt fraction).

    The stack is built from static whole-tile slices, except on a large
    grid of small tiles (:func:`_unrolls`): one gather over the grid."""
    n = x.shape[-1]
    assert n % tile == 0
    nt = n // tile
    if _unrolls(nt, tile):
        return jnp.stack([x[..., i * tile:(i + 1) * tile,
                            j * tile:(j + 1) * tile]
                          for i, j in tile_tril_coords(nt)], axis=-3)
    coords = tile_tril_coords(nt)
    xt = x.reshape(x.shape[:-2] + (nt, tile, nt, tile))
    xt = jnp.moveaxis(xt, -2, -3)  # (…, nt, nt, tile, tile)
    return xt[..., coords[:, 0], coords[:, 1], :, :]


@functools.lru_cache(maxsize=None)
def packed_tile_indices(n: int, bm: int
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Static *element* tables between the element-packed lower triangle
    of an n×n matrix and its (T, bm, bm) tile-packed layout (tile grid of
    ceil(n/bm), as produced by the Pallas kernels on padded operands).

    Returns (tidx, ridx, cidx) int32 arrays of length tril_size(n):
    element l of the row-major packed triangle lives at
    ``tiles[tidx[l], ridx[l], cidx[l]]``.

    Kept as the *reference* definition of the layout bijection (tests
    assert the slice-granular converters below agree with it bit for
    bit); the hot converters no longer touch per-element tables.
    """
    i, j = np.tril_indices(n)
    ti, tj = i // bm, j // bm
    tidx = (ti * (ti + 1) // 2 + tj).astype(np.int32)
    ridx = (i % bm).astype(np.int32)
    cidx = (j % bm).astype(np.int32)
    for arr in (tidx, ridx, cidx):
        arr.setflags(write=False)
    return tidx, ridx, cidx


@functools.lru_cache(maxsize=None)
def tile_row_starts(nt: int, bm: int) -> Tuple[np.ndarray, np.ndarray]:
    """Slice-granular tile↔packed tables for an nt×nt tile grid of
    (bm, bm) tiles.

    Returns ``(starts, is_diag)``: ``starts`` is (T, bm) int32 — the
    packed offset of intra-tile row u of packed tile t (matrix row
    ti·bm+u, columns tj·bm…), i.e. every (tile, row) pair is one
    contiguous width-bm slice of the packed vector (padded to
    tril_size(nt·bm)); ``is_diag`` is (T,) bool for the grid-diagonal
    tiles whose upper halves need the intra-tile mask."""
    coords = tile_tril_coords(nt)
    u = np.arange(bm, dtype=np.int64)
    rr = coords[:, 0:1] * bm + u[None, :]                    # (T, bm)
    starts = (rr * (rr + 1) // 2 + coords[:, 1:2] * bm).astype(np.int32)
    is_diag = coords[:, 0] == coords[:, 1]
    starts.setflags(write=False)
    is_diag.setflags(write=False)
    return starts, is_diag


def _tile_keep_mask(T: int, bm: int, is_diag: np.ndarray):
    """(T, bm, bm) bool: True on every slot that belongs to the packed
    triangle (diagonal tiles keep their lower halves only)."""
    u, v = _iota2((T, bm, bm), 1, 2)
    return jnp.logical_or(~jnp.asarray(is_diag)[:, None, None], u >= v)


def packed_to_tiles(p, n: int, bm: int, nt: Optional[int] = None):
    """Element-packed (…, tril_size(n)) -> tile-packed (…, T, bm, bm)
    over an ``nt``-tile grid (default ceil(n/bm); padding slots zero).

    One gather of T·bm contiguous width-bm slices + one vectorized
    intra-tile mask — no per-element indexing, no dense intermediate.
    Diagonal-tile slice overruns read the next matrix row's leading
    elements and are masked; rows ≥ n read the zero padding."""
    assert p.shape[-1] == tril_size(n), (p.shape, n)
    if nt is None:
        nt = -(-n // bm)
    assert nt * bm >= n, (nt, bm, n)
    T = nt * (nt + 1) // 2
    starts, is_diag = tile_row_starts(nt, bm)
    lpad = tril_size(nt * bm)
    keep = _tile_keep_mask(T, bm, is_diag)

    def one(pv):
        pv = jnp.pad(pv, (0, lpad - pv.shape[0]))
        tiles = _gather_rows(pv, starts, bm).reshape(T, bm, bm)
        return jnp.where(keep, tiles, jnp.zeros((), tiles.dtype))

    return _over_batch(one, p, 1)


def _grid_side(T: int) -> int:
    """nt from T = nt(nt+1)/2."""
    nt = int((np.sqrt(8 * T + 1) - 1) // 2)
    assert nt * (nt + 1) // 2 == T, T
    return nt


def tiles_to_packed(tiles, n: int):
    """Tile-packed (…, T, bm, bm) -> element-packed (…, tril_size(n)).

    Transpose of :func:`packed_to_tiles`: mask, then ONE scatter-add of
    T·bm contiguous width-bm slices (off-diagonal rows land exactly in
    their packed segments; masked diagonal-tile overruns and padding
    rows contribute zeros / fall past tril_size(n))."""
    T = tiles.shape[-3]
    bm = tiles.shape[-1]
    nt = _grid_side(T)
    assert nt * bm >= n, (T, n, bm)
    starts, is_diag = tile_row_starts(nt, bm)
    lpad = tril_size(nt * bm)
    keep = _tile_keep_mask(T, bm, is_diag)

    def one(tl):
        upd = jnp.where(keep, tl, jnp.zeros((), tl.dtype))
        out = _scatter_add_rows(upd.reshape(T * bm, bm), starts, lpad)
        return out[:tril_size(n)]

    return _over_batch(one, tiles, 3)


def _sym_diag_tile(d):
    """A diagonal tile's lower half mirrored into its upper half."""
    return jnp.tril(d) + jnp.swapaxes(jnp.tril(d, -1), -1, -2)


def unpack_tril_tiles(p, n: int, tile: int, symmetric: bool = True):
    """(…, T, tile, tile) -> dense (…, n, n): the stored tiles in the
    lower triangle, and above it the mirror (``symmetric``: diagonal
    tiles symmetrized from their lower halves) or zeros (diagonal tiles
    as stored).

    Each tile row is a concatenation of static slices of the tile axis
    (an upper tile the transpose of its mirror), except on a large grid
    of small tiles (:func:`_unrolls`): one scatter into a zero grid."""
    nt = n // tile
    if _unrolls(nt, tile):
        def at(i, j):
            return p[..., tile_flat_index(i, j), :, :]

        if not symmetric:
            zero = jnp.zeros(p.shape[:-3] + (tile, tile), p.dtype)
        rows = []
        for i in range(nt):
            diag = _sym_diag_tile(at(i, i)) if symmetric else at(i, i)
            upper = [jnp.swapaxes(at(j, i), -1, -2) if symmetric else zero
                     for j in range(i + 1, nt)]
            rows.append(jnp.concatenate(
                [at(i, j) for j in range(i)] + [diag] + upper, axis=-1))
        return jnp.concatenate(rows, axis=-2)
    coords = tile_tril_coords(nt)
    full = jnp.zeros(p.shape[:-3] + (nt, nt, tile, tile), dtype=p.dtype)
    full = full.at[..., coords[:, 0], coords[:, 1], :, :].set(p)
    if symmetric:
        mirrored = jnp.swapaxes(jnp.swapaxes(full, -4, -3), -2, -1)
        # keep lower tiles from `full`, take strict-upper tiles from mirror
        ii = jnp.arange(nt)
        lower_mask = (ii[:, None] >= ii[None, :])[..., None, None]
        full = jnp.where(lower_mask, full, mirrored)
        full = full.at[..., ii, ii, :, :].set(
            _sym_diag_tile(full[..., ii, ii, :, :]))
    out = jnp.moveaxis(full, -3, -2)
    return out.reshape(p.shape[:-3] + (n, n))


# ---- PackedTriangle: the typed element-packed persistence format ----------
@dataclasses.dataclass(frozen=True)
class PackedTriangle:
    """Element-packed lower triangle ``vec`` (…, n(n+1)/2) plus its
    logical dimension ``n`` — the typed marker for packed symmetric
    vectors (Gram EMAs, Muon curvature stats, whitening caches).

    A bare (L,) array cannot be recognized as symmetric state by a
    pytree walk; wrapping it lets the persistence layer
    (:mod:`repro.distributed.checkpoint`), gradient compression, and the
    elastic re-shard path treat packed symmetric leaves natively — store
    them as packed words (~4× fewer bytes than the dense f32 matrix when
    narrowed to bf16) and rebuild them through the slice-granular
    converters instead of densifying.

    Registered as a jax pytree: ``vec`` is the only leaf, ``n`` is
    static, so PackedTriangle flows through jit/vmap/grad/eval_shape
    unchanged.  Leading batch dims vmap straight through.
    """
    vec: jax.Array                # (…, tril_size(n))
    n: int

    @property
    def dtype(self):
        return self.vec.dtype

    @property
    def batch_shape(self) -> Tuple[int, ...]:
        return self.vec.shape[:-1]

    def __post_init__(self):
        shape = getattr(self.vec, "shape", None)
        if shape is None or len(shape) < 1:
            return                 # pytree unflatten sentinels pass through
        if shape[-1] != tril_size(self.n):
            raise ValueError(f"PackedTriangle(n={self.n}) needs trailing "
                             f"length {tril_size(self.n)}, got {shape[-1]}")

    def astype(self, dtype) -> "PackedTriangle":
        return PackedTriangle(self.vec.astype(dtype), self.n)

    @classmethod
    def from_dense(cls, x) -> "PackedTriangle":
        """Dense tril-valid (…, n, n) -> PackedTriangle (reads tril)."""
        return cls(pack_tril(x), x.shape[-1])

    def to_dense(self, symmetric: bool = True) -> jax.Array:
        return unpack_tril(self.vec, self.n, diag=True,
                           symmetric=symmetric)

    def to_tritiles(self, bm: int = 128) -> "TriTiles":
        return TriTiles.from_packed(self.vec, self.n, bm)


jax.tree_util.register_pytree_node(
    PackedTriangle,
    lambda t: ((t.vec,), (t.n,)),
    lambda aux, children: PackedTriangle(children[0], *aux))


# ---- TriTiles: the first-class packed-triangular interchange format -------
@dataclasses.dataclass(frozen=True)
class TriTiles:
    """Tile-packed lower-triangular storage: the end-to-end interchange
    format of the symmetric BLAS stack (~n²/2 words instead of n²).

    ``tiles`` is (…, T, bm, bm) — the dense (bm, bm) tiles of the lower
    triangle of a ceil(n/bm)² tile grid, row-major, T = nt(nt+1)/2, with
    leading batch dims vmapped straight through.  ``n`` is the logical
    matrix dimension (the grid may be padded when n % bm != 0; padding
    slots are zero/ignored).  Diagonal tiles are lower-triangular by
    convention (their upper halves are structural zeros — the only
    intra-format redundancy, a 1/nt fraction).

    Registered as a jax pytree: ``tiles`` is the only leaf, (n, bm) are
    static metadata, so TriTiles flows through jit/vmap/grad unchanged.
    All converters route through the cached index tables above and never
    build an n×n dense intermediate except the explicitly-dense
    ``to_tril``/``to_full`` exits.
    """
    tiles: jax.Array
    n: int
    bm: int

    # -- structure ---------------------------------------------------------
    @property
    def nt(self) -> int:
        return -(-self.n // self.bm)

    @property
    def num_tiles(self) -> int:
        return self.nt * (self.nt + 1) // 2

    @property
    def batch_shape(self) -> Tuple[int, ...]:
        return self.tiles.shape[:-3]

    @property
    def dtype(self):
        return self.tiles.dtype

    def __post_init__(self):
        # tolerate non-array leaves (pytree unflatten passes sentinels
        # through during some jax transforms)
        shape = getattr(self.tiles, "shape", None)
        if shape is None or len(shape) < 3:
            return
        want = (self.num_tiles, self.bm, self.bm)
        if tuple(shape[-3:]) != want:
            raise ValueError(f"TriTiles(n={self.n}, bm={self.bm}) needs "
                             f"trailing tile shape {want}, got "
                             f"{tuple(shape[-3:])}")

    def astype(self, dtype) -> "TriTiles":
        return TriTiles(self.tiles.astype(dtype), self.n, self.bm)

    # -- constructors (cached index tables, no dense round-trips) ----------
    @classmethod
    def from_tril(cls, x, bm: int) -> "TriTiles":
        """Dense tril-valid (…, n, n) -> TriTiles.  Only the lower
        triangle is read: strictly-upper grid tiles are never gathered
        and diagonal tiles are masked to their lower halves."""
        n = x.shape[-1]
        lead = x.shape[:-2]
        xp = x
        pad = -n % bm
        if pad:
            cfg = [(0, 0)] * len(lead) + [(0, pad), (0, pad)]
            xp = jnp.pad(x, cfg)
        coords = tile_tril_coords(-(-n // bm))
        is_diag = coords[:, 0] == coords[:, 1]
        # where, not multiply: the unread upper halves may hold NaN/inf
        # garbage ("tril-valid" contract) and 0·NaN would propagate it
        tiles = pack_tril_tiles(xp, bm)
        keep = _tile_keep_mask(tiles.shape[-3], bm, is_diag)
        tiles = jnp.where(keep, tiles, jnp.zeros((), tiles.dtype))
        return cls(tiles, n, bm)

    @classmethod
    def from_full(cls, x, bm: int) -> "TriTiles":
        """Dense symmetric (…, n, n) -> TriTiles (reads tril only)."""
        return cls.from_tril(x, bm)

    @classmethod
    def from_packed(cls, p, n: int, bm: int) -> "TriTiles":
        """Element-packed (…, tril_size(n)) -> TriTiles (pure scatter)."""
        return cls(packed_to_tiles(p, n, bm), n, bm)

    # -- exits --------------------------------------------------------------
    def to_packed(self) -> jax.Array:
        """(…, tril_size(n)) element-packed triangle (pure gather)."""
        return tiles_to_packed(self.tiles, self.n)

    def to_tril(self) -> jax.Array:
        """Dense (…, n, n) with zeros above the diagonal."""
        npad = self.nt * self.bm
        dense = unpack_tril_tiles(self.tiles, npad, self.bm,
                                  symmetric=False)
        return dense[..., :self.n, :self.n]

    def to_full(self) -> jax.Array:
        """Dense symmetric (…, n, n) (mirrors the stored triangle)."""
        npad = self.nt * self.bm
        dense = unpack_tril_tiles(self.tiles, npad, self.bm,
                                  symmetric=True)
        return dense[..., :self.n, :self.n]


jax.tree_util.register_pytree_node(
    TriTiles,
    lambda t: ((t.tiles,), (t.n, t.bm)),
    lambda aux, children: TriTiles(children[0], *aux))


# ---- ShardedTriTiles: the packed mesh wire format -------------------------
@dataclasses.dataclass(frozen=True)
class ShardedTriTiles:
    """Per-device extended-triangle-block shards of a symmetric matrix —
    the wire format of the 2D/3D mesh schedules (paper Algs 10–15).

    The affine-plane partition assigns every block pair of the c²-block
    row grid to exactly one of P = c(c+1) devices: device k holds the
    T = c(c−1)/2 off-diagonal blocks ``off[k]`` (pairs i>j ∈ R_k) plus
    one lower-triangular diagonal block ``diag[k]`` (zeros when it owns
    none).  Total storage is P·(T+1)·nb² ≈ n²/2 — each device owns
    ~n²/(2P) words, the paper's per-processor memory bound.

    ``off`` is (…, P, T, nb, nb) and ``diag`` (…, P, nb, nb) with the
    device axis leading the core dims, exactly the shapes the shard_map
    schedules emit and consume sharded over the mesh axis; optional
    leading batch dims (stacked accumulators) ride through every
    converter; (n, c) are static metadata.
    Converters route through the cached :func:`~repro.core.twodim.
    tb_pack_tables` bijection and never build an n×n dense array except
    the explicitly-dense ``to_tril``/``to_full`` exits.
    """
    off: jax.Array                # (…, P, T, nb, nb)
    diag: jax.Array               # (…, P, nb, nb)
    n: int
    c: int

    # -- structure ---------------------------------------------------------
    @property
    def num_devices(self) -> int:
        return self.c * (self.c + 1)

    @property
    def T(self) -> int:
        return self.c * (self.c - 1) // 2

    @property
    def nb(self) -> int:
        return -(-self.n // (self.c * self.c))

    @property
    def dtype(self):
        return self.diag.dtype

    def __post_init__(self):
        shape = getattr(self.diag, "shape", None)
        if shape is None or len(shape) < 2:
            return                 # pytree unflatten sentinels pass through
        want_off = (self.num_devices, self.T, self.nb, self.nb)
        want_diag = (self.num_devices, self.nb, self.nb)
        off_shape = tuple(getattr(self.off, "shape", ()))
        ok = (len(off_shape) >= 4 and off_shape[-4:] == want_off
              and len(shape) >= 3 and tuple(shape[-3:]) == want_diag
              and off_shape[:-4] == tuple(shape[:-3]))
        if not ok:
            raise ValueError(
                f"ShardedTriTiles(n={self.n}, c={self.c}) needs off "
                f"(…,) + {want_off} and diag (…,) + {want_diag} with "
                f"matching batch dims, got {off_shape} and {tuple(shape)}")

    def astype(self, dtype) -> "ShardedTriTiles":
        return ShardedTriTiles(self.off.astype(dtype),
                               self.diag.astype(dtype), self.n, self.c)

    # -- packed exits / entrances (block-granular, never dense) ------------
    def to_packed(self) -> jax.Array:
        """(tril_size(n),) element-packed triangle: one take over the
        block axis (the static device-slot → grid-block bijection) plus
        the slice-granular :func:`tiles_to_packed` — no per-element
        indexing, no dense intermediate."""
        from .twodim import tb_block_tables
        src, _ = tb_block_tables(self.c)
        Pn, T, nb = self.num_devices, self.T, self.nb
        stack = jnp.concatenate(
            [self.off, self.diag[..., :, None, :, :]], axis=-3)
        stack = stack.reshape(stack.shape[:-4] + (Pn * (T + 1), nb, nb))
        blocks = jnp.take(stack, jnp.asarray(src), axis=-3)
        return tiles_to_packed(blocks, self.n)

    @classmethod
    def from_packed(cls, p, n: int, c: int) -> "ShardedTriTiles":
        """Element-packed (tril_size(n),) -> per-device shards: the
        slice-granular :func:`packed_to_tiles` over the full c²-block
        grid, then one take over the block axis (padding/absent-diagonal
        slots select an appended zero block)."""
        from .twodim import tb_block_tables
        assert p.shape[-1] == tril_size(n), (p.shape, n)
        _, dst = tb_block_tables(c)
        Pn = c * (c + 1)
        nb = -(-n // (c * c))
        T = c * (c - 1) // 2
        blocks = packed_to_tiles(p, n, nb, nt=c * c)
        stack = jnp.concatenate(
            [blocks, jnp.zeros(blocks.shape[:-3] + (1, nb, nb),
                               blocks.dtype)], axis=-3)
        sel = jnp.take(stack, jnp.asarray(dst).reshape(-1), axis=-3)
        sel = sel.reshape(sel.shape[:-3] + (Pn, T + 1, nb, nb))
        return cls(sel[..., :T, :, :], sel[..., T, :, :], n, c)

    # -- TriTiles interchange ----------------------------------------------
    def to_tritiles(self, bm: int = 128) -> TriTiles:
        """Mesh wire -> kernel wire: gather into the element-packed
        triangle, scatter into (T, bm, bm) tiles; never dense."""
        return TriTiles.from_packed(self.to_packed(), self.n, bm)

    @classmethod
    def from_tritiles(cls, t: TriTiles, c: int) -> "ShardedTriTiles":
        """Kernel wire -> mesh wire (gather + scatter, never dense)."""
        return cls.from_packed(t.to_packed(), t.n, c)

    # -- dense exits / entrances -------------------------------------------
    @classmethod
    def from_tril(cls, x, c: int) -> "ShardedTriTiles":
        """Dense tril-valid (n, n) -> per-device shards (reads the lower
        triangle only)."""
        n = x.shape[-1]
        return cls.from_packed(pack_tril(jnp.tril(x)), n, c)

    def to_tril(self) -> jax.Array:
        """Dense (n, n) with zeros above the diagonal."""
        return unpack_tril(self.to_packed(), self.n, diag=True,
                           symmetric=False)

    def to_full(self) -> jax.Array:
        """Dense symmetric (n, n)."""
        return unpack_tril(self.to_packed(), self.n, diag=True,
                           symmetric=True)


jax.tree_util.register_pytree_node(
    ShardedTriTiles,
    lambda t: ((t.off, t.diag), (t.n, t.c)),
    lambda aux, children: ShardedTriTiles(children[0], children[1], *aux))


def packed_to_device_shard(p, n: int, c: int, k: int
                           ) -> Tuple[jax.Array, jax.Array]:
    """Element-packed (tril_size(n),) -> device ``k``'s extended triangle
    block ``(off[k] (T, nb, nb), diag[k] (nb, nb))`` — and ONLY that
    device's shard.

    This is the straggler-eviction recovery path: when one device of a
    P = c(c+1) wire is replaced, the survivor shards are already
    resident, so the replacement needs just its own ~n²/(2P) words.  The
    gather is (T+1)·nb contiguous width-nb slices of the packed vector
    (the per-device rows of :func:`~repro.core.twodim.
    tb_device_row_starts`) + one vectorized mask — never the full
    P-shard :meth:`ShardedTriTiles.from_packed`, never a dense n×n.

    Bit-for-bit equal to ``ShardedTriTiles.from_packed(p, n, c).off[k]``
    / ``.diag[k]`` (asserted in the persist test suite).
    """
    from .twodim import tb_device_row_starts
    assert p.shape[-1] == tril_size(n), (p.shape, n)
    starts, is_diag, valid = tb_device_row_starts(c, n, k)
    Tslots, nb = starts.shape
    lpad = tril_size(c * c * nb)
    u, v = _iota2((Tslots, nb, nb), 1, 2)
    keep = jnp.logical_and(
        jnp.asarray(valid)[:, None, None],
        jnp.logical_or(~jnp.asarray(is_diag)[:, None, None], u >= v))

    def one(pv):
        pv = jnp.pad(pv, (0, lpad - pv.shape[0]))
        blocks = _gather_rows(pv, starts.reshape(-1), nb)
        blocks = blocks.reshape(Tslots, nb, nb)
        return jnp.where(keep, blocks, jnp.zeros((), blocks.dtype))

    blocks = _over_batch(one, p, 1)
    return blocks[..., :Tslots - 1, :, :], blocks[..., Tslots - 1, :, :]

"""2D communication-optimal parallel SYRK / SYR2K / SYMM (paper Algs 10–12).

Optimal regime (Thm 9 case 2): m·n₂ < n₁ and P ≤ n₁(n₁−1)/(m·n₂)².
P = c(c+1) processors, one per triangle block of the affine-plane partition
of the c² row blocks.  The symmetric matrix never moves; the non-symmetric
matrices move through ONE regular all-to-all (two for SYR2K; B in + C out
for SYMM) of total bandwidth m·(n₁n₂/c)·(1−1/P) — exactly eq. (6).

TPU adaptation (DESIGN §3): the paper's irregular point-to-point exchange
becomes a *regular* ``jax.lax.all_to_all``:  two triangle blocks (affine
lines) share at most one row-block index, so the pairwise payload is exactly
one share of one row block (or nothing — parallel lines — which we zero-pad).
All routing tables are static numpy computed from the partition at trace
time; they become HLO constants, and `axis_index` gathers select each
device's rows SPMD-uniformly.

Data layout per device k (leading axis = mesh axis of size P):
  * non-symmetric row shares  ``(c, nb, w)``: for the c row blocks
    i ∈ R_k (sorted), this device's 1/(c+1) column share (w = n₂/(c+1));
  * symmetric extended triangle block: off-diag ``(T, nb, nb)`` for the
    T = c(c−1)/2 pairs (i>j ∈ R_k, lexicographic) plus diag ``(nb, nb)``
    for the assigned diagonal block D_k (zeros when |D_k| = 0).

The schedules are batch-native: each per-device array carries a stack
axis K right behind the device axis (``(P, K, c, nb, w)`` globally), and
the stack rides the exchange payloads.  An unbatched call is K = 1.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .triangle import TrianglePartition, affine_partition


# --------------------------------------------------------------------------
# plan: static routing tables from the affine partition
# --------------------------------------------------------------------------
@dataclass
class TwoDPlan:
    c: int
    n1: int                      # real rows
    n2: int                      # real cols
    nb: int                      # rows per row block (n1_pad / c^2)
    w: int                       # cols per share (n2_pad / (c+1))
    n1_pad: int
    n2_pad: int
    part: TrianglePartition = field(repr=False)
    R: np.ndarray = field(repr=False)          # (P, c) row blocks per device
    Q: np.ndarray = field(repr=False)          # (c^2, c+1) owners per row blk
    send_slot: np.ndarray = field(repr=False)  # (P, P) slot in R_k or c
    send_valid: np.ndarray = field(repr=False)  # (P, P) bool
    gather_src: np.ndarray = field(repr=False)  # (P, c, c+1) supplier device
    self_col: np.ndarray = field(repr=False)   # (P, c) own column position
    peer_col: np.ndarray = field(repr=False)   # (P, P) col position of peer p
                                               # within Q_i for i = R_k ∩ R_p
    pairs: np.ndarray = field(repr=False)      # (T, 2) slot pairs a>b
    diag_slot: np.ndarray = field(repr=False)  # (P,) slot of diag blk or -1

    @property
    def num_devices(self) -> int:
        return self.c * (self.c + 1)

    @property
    def T(self) -> int:
        return self.c * (self.c - 1) // 2


@functools.lru_cache(maxsize=64)
def make_2d_plan(c: int, n1: int, n2: int) -> TwoDPlan:
    part = affine_partition(c)
    Pn = c * (c + 1)
    nblocks = c * c
    nb = -(-n1 // nblocks)
    w = -(-n2 // (c + 1))
    R = np.array([sorted(Rk) for Rk in part.blocks])          # (P, c)
    q = part.q_sets()
    Q = np.array([sorted(q[i]) for i in range(nblocks)])      # (c^2, c+1)
    inter = part.intersection_table()                          # (P, P)
    send_slot = np.full((Pn, Pn), c, dtype=np.int64)
    send_valid = np.zeros((Pn, Pn), dtype=bool)
    peer_col = np.zeros((Pn, Pn), dtype=np.int64)
    slot_of = {(k, i): s for k in range(Pn) for s, i in enumerate(R[k])}
    for k in range(Pn):
        for p in range(Pn):
            i = inter[k, p]
            if i >= 0:
                send_slot[k, p] = slot_of[(k, int(i))]
                send_valid[k, p] = True
                peer_col[k, p] = int(np.where(Q[int(i)] == p)[0][0])
    gather_src = np.zeros((Pn, c, c + 1), dtype=np.int64)
    self_col = np.zeros((Pn, c), dtype=np.int64)
    for k in range(Pn):
        for s in range(c):
            i = R[k][s]
            gather_src[k, s] = Q[i]
            self_col[k, s] = int(np.where(Q[i] == k)[0][0])
    pairs = np.array([(a, b) for a in range(c) for b in range(a)],
                     dtype=np.int64)
    diag_slot = np.full((Pn,), -1, dtype=np.int64)
    for k in range(Pn):
        if part.diag[k]:
            diag_slot[k] = slot_of[(k, part.diag[k][0])]
    return TwoDPlan(c=c, n1=n1, n2=n2, nb=nb, w=w, n1_pad=nb * nblocks,
                    n2_pad=w * (c + 1), part=part, R=R, Q=Q,
                    send_slot=send_slot, send_valid=send_valid,
                    gather_src=gather_src, self_col=self_col,
                    peer_col=peer_col, pairs=pairs, diag_slot=diag_slot)


# --------------------------------------------------------------------------
# packed-triangle <-> extended-triangle-block index tables (the mesh wire)
# --------------------------------------------------------------------------
def tb_flat_words(c: int, n1: int) -> int:
    """Per-device words of one flattened extended triangle block:
    (T + 1)·nb² — the ~n²/(2P) owned share of the paper's layout."""
    nb = -(-n1 // (c * c))
    T = c * (c - 1) // 2
    return (T + 1) * nb * nb


@functools.lru_cache(maxsize=64)
def tb_pack_tables(c: int, n1: int) -> Tuple[np.ndarray, np.ndarray]:
    """Static gather/scatter tables between the element-packed lower
    triangle of an n1×n1 matrix and the 2D plan's per-device extended
    triangle blocks.

    Element ``l`` of the row-major packed triangle lives at
    ``flat[kidx[l], sidx[l]]`` where ``flat`` is the (P, (T+1)·nb²)
    array of per-device flattened (off ‖ diag) extended triangle
    blocks.  The affine-plane partition stores every block pair
    exactly once (off-diagonal block (i>j) on the unique line through
    {i, j}; diagonal block on its unique assigned device), so the map
    is a bijection onto ~n1²/2 real slots — converting through it
    never touches an n1×n1 dense intermediate.

    Ownership only depends on (c, n1): every TwoDPlan for the same
    pair shares these tables regardless of n2.  Cached; returned
    arrays are read-only.
    """
    plan = make_2d_plan(c, n1, 1)          # n2 does not affect ownership
    nblocks = c * c
    nb, T, Pn = plan.nb, plan.T, plan.num_devices
    dev_of = np.full((nblocks, nblocks), -1, dtype=np.int64)
    slot_of = np.full((nblocks, nblocks), -1, dtype=np.int64)
    for k in range(Pn):
        for t, (a, b) in enumerate(plan.pairs):
            i, j = plan.R[k][a], plan.R[k][b]
            dev_of[i, j] = k
            slot_of[i, j] = t
        ds = plan.diag_slot[k]
        if ds >= 0:
            d = plan.R[k][ds]
            dev_of[d, d] = k
            slot_of[d, d] = T              # diag block rides as slot T
    i, j = np.tril_indices(n1)
    bi, bj = i // nb, j // nb
    assert (dev_of[bi, bj] >= 0).all(), "partition must cover the triangle"
    kidx = dev_of[bi, bj].astype(np.int32)
    sidx = ((slot_of[bi, bj] * nb + i % nb) * nb + j % nb).astype(np.int32)
    for arr in (kidx, sidx):
        arr.setflags(write=False)
    return kidx, sidx


@functools.lru_cache(maxsize=64)
def tb_block_tables(c: int) -> Tuple[np.ndarray, np.ndarray]:
    """*Block*-granular (device, slot) ↔ lower-triangle-grid bijection —
    the slice/tile-granular replacement for per-element
    :func:`tb_pack_tables` on the ShardedTriTiles converters.

    The c²-block row grid has Tb = c²(c²+1)/2 lower-triangle blocks in
    the row-major flat order of :func:`~repro.core.packing.
    tile_tril_coords`; every device k owns T+1 slots (T off-diagonal
    pairs + one diagonal slot).  Returns

      * ``src`` (Tb,) int32: flat slot index ``k·(T+1)+t`` owning each
        lower-triangle grid block (a bijection — every block owned
        exactly once);
      * ``dst`` (P, T+1) int32: the flat grid-block id held by each
        device slot, with the sentinel ``Tb`` for the diagonal slot of
        devices that own no diagonal block (callers append one zero pad
        block).

    Ownership depends only on c (so the cache is keyed on c alone);
    cached and read-only.
    """
    plan = make_2d_plan(c, 1, 1)
    T, Pn = plan.T, plan.num_devices
    nblocks = c * c
    Tb = nblocks * (nblocks + 1) // 2
    src = np.full(Tb, -1, dtype=np.int64)
    dst = np.full((Pn, T + 1), Tb, dtype=np.int64)
    for k in range(Pn):
        for t, (a, b) in enumerate(plan.pairs):
            i, j = int(plan.R[k][a]), int(plan.R[k][b])      # i > j
            f = i * (i + 1) // 2 + j
            src[f] = k * (T + 1) + t
            dst[k, t] = f
        ds = plan.diag_slot[k]
        if ds >= 0:
            d = int(plan.R[k][ds])
            f = d * (d + 1) // 2 + d
            src[f] = k * (T + 1) + T
            dst[k, T] = f
    assert (src >= 0).all(), "partition must cover the block triangle"
    src = src.astype(np.int32)
    dst = dst.astype(np.int32)
    src.setflags(write=False)
    dst.setflags(write=False)
    return src, dst


@functools.lru_cache(maxsize=256)
def tb_device_row_starts(c: int, n1: int, k: int
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Slice-granular packed-offset tables for ONE device's extended
    triangle block — the straggler-replacement rebuild path.

    Device ``k`` of the c(c+1) partition owns T+1 = c(c−1)/2 + 1 grid
    blocks (``tb_block_tables`` dst row k).  Returns

      * ``starts`` (T+1, nb) int32: packed offset of intra-block row u of
        owned block t — matrix row bi·nb+u, columns bj·nb…, i.e. each
        (block, row) pair is one contiguous width-nb slice of the packed
        triangle (padded to tril_size(c²·nb));
      * ``is_diag`` (T+1,) bool: grid-diagonal blocks whose intra-block
        upper halves must be masked;
      * ``valid`` (T+1,) bool: False only for the diagonal slot of
        devices that own no diagonal block (the ``dst`` sentinel).

    Rebuilding one device therefore costs (T+1)·nb slice gathers —
    ~n²/(2P) words — instead of the full P-shard ``from_packed``.
    """
    _, dst = tb_block_tables(c)
    from .packing import tile_tril_coords
    nblocks = c * c
    nb = -(-n1 // nblocks)
    coords = tile_tril_coords(nblocks)            # (Tb, 2) row-major tril
    Tb = coords.shape[0]
    f = dst[k].astype(np.int64)                   # (T+1,) grid block ids
    valid = f < Tb
    fv = np.where(valid, f, 0)
    bi, bj = coords[fv, 0], coords[fv, 1]         # (T+1,)
    u = np.arange(nb, dtype=np.int64)
    rr = bi[:, None] * nb + u[None, :]            # (T+1, nb) matrix rows
    starts = (rr * (rr + 1) // 2 + bj[:, None] * nb).astype(np.int32)
    is_diag = (bi == bj) & valid
    for arr in (starts, is_diag, valid):
        arr.setflags(write=False)
    return starts, is_diag, valid


# --------------------------------------------------------------------------
# the all-to-all row exchange (Alg 10 lines 3–14), batch-native
# --------------------------------------------------------------------------
# Collectives don't vmap under shard_map; instead a stack of K matrices
# rides the all-to-all payload as extra leading payload dims of the SAME
# exchange, so one collective (pair) covers the whole stack.  The
# collective-free local compute then vmaps over K.  An unbatched call
# is a stack of one.
def _exchange_rows(a_own: jax.Array, plan: TwoDPlan, axis: str
                   ) -> jax.Array:
    """(K, c, nb, w) own shares -> (K, c, nb, n2_pad) fully assembled
    rows, one all-to-all for the stack."""
    c, nb, w = plan.c, plan.nb, plan.w
    k = jax.lax.axis_index(axis)
    own = jnp.moveaxis(a_own, 0, 1)                           # (c, K, nb, w)
    K = own.shape[1]
    # send buffer: row p = our share of the row block shared with p
    own_pad = jnp.concatenate(
        [own, jnp.zeros((1, K, nb, w), own.dtype)], 0)
    send = own_pad[jnp.asarray(plan.send_slot)[k]]            # (P, K, nb, w)
    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=True)
    # assemble: rows[s] = concat over j of the share from Q_i[j]
    gsrc = jnp.asarray(plan.gather_src)[k]                    # (c, c+1)
    is_self = gsrc == k
    shares = recv[gsrc]                                   # (c, c+1, K, nb, w)
    shares = jnp.where(is_self[:, :, None, None, None], own[:, None],
                       shares)
    return shares.transpose(2, 0, 3, 1, 4).reshape(K, c, nb, (c + 1) * w)


def _reverse_exchange(c_partial: jax.Array, plan: TwoDPlan, axis: str
                      ) -> jax.Array:
    """SYMM output reduction (Alg 12 lines 21–33): partial full rows
    (K, c, nb, n2_pad) -> summed own column shares (K, c, nb, w)."""
    c, nb, w = plan.c, plan.nb, plan.w
    k = jax.lax.axis_index(axis)
    K = c_partial.shape[0]
    parts = c_partial.reshape(K, c, nb, c + 1, w)             # col shares
    # send: to peer p, our partial of the shared row, p's column share
    slot = jnp.asarray(plan.send_slot)[k]                      # (P,)
    pcol = jnp.asarray(plan.peer_col)[k]                       # (P,)
    valid = jnp.asarray(plan.send_valid)[k]                    # (P,)
    parts_pad = jnp.concatenate(
        [parts, jnp.zeros((K, 1, nb, c + 1, w), parts.dtype)], 1)
    send = parts_pad[:, slot, :, pcol]                         # (P, K, nb, w)
    send = send * valid[:, None, None, None]
    recv = jax.lax.all_to_all(send, axis, 0, 0, tiled=True)    # (P, K, nb, w)
    # sum received pieces into their slots (+ our own column share)
    seg = jnp.where(valid, slot, c)
    summed = jax.ops.segment_sum(recv, seg, num_segments=c + 1)[:c]
    own = jnp.take_along_axis(
        parts, jnp.asarray(plan.self_col)[k][None, :, None, None, None],
        axis=3)[:, :, :, 0, :]                                 # (K, c, nb, w)
    return own + jnp.moveaxis(summed, 0, 1)


# --------------------------------------------------------------------------
# local computations
# --------------------------------------------------------------------------
def _syrk_blocks(rows_a: jax.Array, rows_b: Optional[jax.Array],
                 plan: TwoDPlan, axis: str) -> Tuple[jax.Array, jax.Array]:
    """Off-diagonal GEMMs + diagonal SYRK for the triangle block (Alg 10
    lines 15–17 / Alg 11 lines 18–20) of one matrix of the stack."""
    k = jax.lax.axis_index(axis)
    pa, pb = plan.pairs[:, 0], plan.pairs[:, 1]
    if rows_b is None:  # SYRK
        off = jnp.einsum("tik,tjk->tij", rows_a[pa], rows_a[pb])
        ds = jnp.asarray(plan.diag_slot)[k]
        rd = rows_a[jnp.maximum(ds, 0)]
        diag = jnp.tril(rd @ rd.T) * (ds >= 0)
    else:  # SYR2K
        off = (jnp.einsum("tik,tjk->tij", rows_a[pa], rows_b[pb])
               + jnp.einsum("tik,tjk->tij", rows_b[pa], rows_a[pb]))
        ds = jnp.asarray(plan.diag_slot)[k]
        ra, rb = rows_a[jnp.maximum(ds, 0)], rows_b[jnp.maximum(ds, 0)]
        g = ra @ rb.T
        diag = jnp.tril(g + g.T) * (ds >= 0)
    return off, diag


def _symm_partial(a_off: jax.Array, a_diag: jax.Array, rows_b: jax.Array,
                  plan: TwoDPlan, axis: str) -> jax.Array:
    """Collective-free core of Alg 12 for one matrix of the stack:
    extended triangle block × assembled B rows (c, nb, n2p) -> partial
    C rows (c, nb, n2p)."""
    c = plan.c
    k = jax.lax.axis_index(axis)
    pa, pb = plan.pairs[:, 0], plan.pairs[:, 1]
    # C_i += A_ij B_j  and  C_j += A_ij^T B_i  for each pair (i>j)
    contrib_i = jnp.einsum("tnm,tmk->tnk", a_off, rows_b[pb])  # (T, nb, n2p)
    contrib_j = jnp.einsum("tmn,tmk->tnk", a_off, rows_b[pa])
    c_partial = (jax.ops.segment_sum(contrib_i, pa, num_segments=c)
                 + jax.ops.segment_sum(contrib_j, pb, num_segments=c))
    # diagonal block: C_d += sym(A_dd) B_d
    ds = jnp.asarray(plan.diag_slot)[k]
    a_dd = a_diag + jnp.tril(a_diag, -1).T
    dcontrib = (a_dd @ rows_b[jnp.maximum(ds, 0)]) * (ds >= 0)
    return c_partial.at[jnp.maximum(ds, 0)].add(
        jnp.where(ds >= 0, dcontrib, jnp.zeros_like(dcontrib)))


def syrk_2d_local(a_own: jax.Array, plan: TwoDPlan, axis: str):
    """Alg 10.  (K, c, nb, w) -> (off (K, T, nb, nb), diag (K, nb, nb)):
    one exchange for the stack + vmapped (collective-free) block
    compute."""
    rows = _exchange_rows(a_own, plan, axis)
    return jax.vmap(lambda r: _syrk_blocks(r, None, plan, axis))(rows)


def syr2k_2d_local(a_own: jax.Array, b_own: jax.Array, plan: TwoDPlan,
                   axis: str):
    """Alg 11, stacked as :func:`syrk_2d_local`."""
    rows_a = _exchange_rows(a_own, plan, axis)
    rows_b = _exchange_rows(b_own, plan, axis)
    return jax.vmap(
        lambda ra, rb: _syrk_blocks(ra, rb, plan, axis))(rows_a, rows_b)


def symm_2d_local(a_off: jax.Array, a_diag: jax.Array, b_own: jax.Array,
                  plan: TwoDPlan, axis: str) -> jax.Array:
    """Alg 12.  a_off (K, T, nb, nb): off-diag blocks A_{ij}, i>j ∈ R_k;
    a_diag (K, nb, nb): lower-tri diagonal block (zeros if none);
    b_own (K, c, nb, w): B row shares.  Returns C row shares
    (K, c, nb, w); both exchanges cover the whole stack."""
    rows_b = _exchange_rows(b_own, plan, axis)            # (K, c, nb, n2p)
    c_partial = jax.vmap(
        lambda o, d, r: _symm_partial(o, d, r, plan, axis))(
        a_off, a_diag, rows_b)
    return _reverse_exchange(c_partial, plan, axis)


# --------------------------------------------------------------------------
# full-array wrappers (mesh axis of size P = c(c+1))
# --------------------------------------------------------------------------
def _device_map(local, n_in: int, mesh, axis: str):
    """shard_map a per-device body over the mesh axis: every input and
    output carries the device axis first."""
    def body(*xs):                     # xs: (1, K, …) per device
        out = local(*(x[0] for x in xs))
        return jax.tree.map(lambda y: y[None], out)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(axis),) * n_in,
                                 out_specs=P(axis)))


def syrk_2d(a_dist: jax.Array, plan: TwoDPlan, mesh, axis: str = "x"):
    """a_dist: (P, K, c, nb, w) globally, sharded P(axis).  Returns
    (off (P, K, T, nb, nb), diag (P, K, nb, nb)) sharded over axis."""
    local = functools.partial(syrk_2d_local, plan=plan, axis=axis)
    return _device_map(local, 1, mesh, axis)(a_dist)


def syr2k_2d(a_dist: jax.Array, b_dist: jax.Array, plan: TwoDPlan, mesh,
             axis: str = "x"):
    local = functools.partial(syr2k_2d_local, plan=plan, axis=axis)
    return _device_map(local, 2, mesh, axis)(a_dist, b_dist)


def symm_2d(a_off: jax.Array, a_diag: jax.Array, b_dist: jax.Array,
            plan: TwoDPlan, mesh, axis: str = "x"):
    """a_off (P, K, T, nb, nb), a_diag (P, K, nb, nb),
    b_dist (P, K, c, nb, w) -> C shares (P, K, c, nb, w)."""
    local = functools.partial(symm_2d_local, plan=plan, axis=axis)
    return _device_map(local, 3, mesh, axis)(a_off, a_diag, b_dist)


# --------------------------------------------------------------------------
# host-side distribution / assembly helpers (tests, data prep)
# --------------------------------------------------------------------------
def distribute_rows(Xf: np.ndarray, plan: TwoDPlan) -> np.ndarray:
    """(n1, n2) -> (P, c, nb, w): per-device row-block column shares."""
    c, nb, w = plan.c, plan.nb, plan.w
    Xp = np.zeros((plan.n1_pad, plan.n2_pad), Xf.dtype)
    Xp[:Xf.shape[0], :Xf.shape[1]] = Xf
    blocks = Xp.reshape(c * c, nb, plan.n2_pad)
    out = np.zeros((plan.num_devices, c, nb, w), Xf.dtype)
    for k in range(plan.num_devices):
        for s, i in enumerate(plan.R[k]):
            col = plan.self_col[k, s]
            out[k, s] = blocks[i][:, col * w:(col + 1) * w]
    return out


def collect_rows(dist: np.ndarray, plan: TwoDPlan) -> np.ndarray:
    """Inverse of :func:`distribute_rows` (unpadded)."""
    c, nb, w = plan.c, plan.nb, plan.w
    Xp = np.zeros((plan.n1_pad, plan.n2_pad), dist.dtype)
    blocks = Xp.reshape(c * c, nb, plan.n2_pad)
    for k in range(plan.num_devices):
        for s, i in enumerate(plan.R[k]):
            col = plan.self_col[k, s]
            blocks[i][:, col * w:(col + 1) * w] = dist[k, s]
    return blocks.reshape(plan.n1_pad, plan.n2_pad)[:plan.n1, :plan.n2]


def distribute_sym(Af: np.ndarray, plan: TwoDPlan
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Full symmetric (n1, n1) -> extended triangle blocks
    (P, T, nb, nb) off-diag + (P, nb, nb) diag(lower)."""
    c, nb = plan.c, plan.nb
    Ap = np.zeros((plan.n1_pad, plan.n1_pad), Af.dtype)
    Ap[:Af.shape[0], :Af.shape[0]] = Af
    At = Ap.reshape(c * c, nb, c * c, nb).transpose(0, 2, 1, 3)
    off = np.zeros((plan.num_devices, plan.T, nb, nb), Af.dtype)
    diag = np.zeros((plan.num_devices, nb, nb), Af.dtype)
    for k in range(plan.num_devices):
        for t, (a, b) in enumerate(plan.pairs):
            i, j = plan.R[k][a], plan.R[k][b]
            off[k, t] = At[i, j]
        ds = plan.diag_slot[k]
        if ds >= 0:
            d = plan.R[k][ds]
            diag[k] = np.tril(At[d, d])
    return off, diag


def assemble_sym(off: np.ndarray, diag: np.ndarray, plan: TwoDPlan
                 ) -> np.ndarray:
    """(P, T, nb, nb) + (P, nb, nb) -> dense lower-triangular (n1, n1)."""
    c, nb = plan.c, plan.nb
    full = np.zeros((c * c, c * c, nb, nb), off.dtype)
    for k in range(plan.num_devices):
        for t, (a, b) in enumerate(plan.pairs):
            i, j = plan.R[k][a], plan.R[k][b]
            if i >= j:
                full[i, j] = off[k, t]
            else:
                full[j, i] = off[k, t].T
        ds = plan.diag_slot[k]
        if ds >= 0:
            d = plan.R[k][ds]
            full[d, d] = diag[k]
    dense = full.transpose(0, 2, 1, 3).reshape(plan.n1_pad, plan.n1_pad)
    return np.tril(dense)[:plan.n1, :plan.n1]

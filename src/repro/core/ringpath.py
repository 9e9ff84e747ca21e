"""Computation-optimal cyclic-shift (ring) SYRK / SYR2K / SYMM.

The Koanantakool–Yelick style c=1 schedule: device r owns row block
A_r (nb = ceil(n1/P) rows, rounded up to even when P is even) and the
extended-triangle slots of C it is responsible for.  A buffer copy of
the local operand circulates around the ring with ``lax.ppermute`` for
S = ⌊P/2⌋ shifts; after s shifts device r holds A_{(r-s) mod P} and
computes exactly ONE unique block C[r, (r-s) mod P] — never the
transpose partner.  When P is even the final shift is antipodal (the
pair (r, r-S) meets twice), so the two partners split the block: the
device with rank < P/2 computes the first nb/2 rows, the other the
last nb/2, each as a genuinely half-size dot.

Per-device dot flops are therefore (P+1)·nb²·n2 ≈ (P+1)/P · n1²n2/P —
the unique half of the symmetric work — versus ~2·n1²n2/P for the
2d/3d routes which compute both halves before discarding one.
Collective volume is S shifts of the nb×n2 slice: m·⌊P/2⌋·nb·n2 words,
the 1d-route scale (no n×n dense ever crosses the wire).

The slot stack (…, S+1, nb, nb) per device maps to the lower block
triangle by a static (device, slot) ↔ block bijection
(:func:`ring_block_tables`): blocks with row distance d ≤ S live on
device i directly; d > S live transposed on device j at slot P−d; the
even-P antipodal block is the SUM of both partners' half-slots.  Two
pairs of converters use it:

* packed — ``ring_stack_to_packed`` / ``packed_to_ring`` move between
  the slot stack and the element-packed triangle, the packed wire of
  ``fill="packed"``/``"sharded"`` and of packed or tiled SYMM operands;
* dense — ``ring_stack_to_full`` / ``dense_to_ring`` move between the
  slot stack and a dense (tril or symmetrized) n1×n1 matrix in whole
  nb×nb blocks: static block slices, transposes and concatenates, with
  no element-packed intermediate and no gather or scatter.  The exit
  reads a replicated stack (:func:`gather_ring_stack`, one all-gather
  of the slots), so no per-block slice of the sharded stack is left to
  the partitioner.

SYMM rides the same ring with B circulating instead of A: each shift
contributes S[r,q]·B_q to the local C_r AND S[q,r]·B_r = L^T·B_r to a
second buffer that travels with B and is ppermute'd home after the
loop (one extra shift: S+1 total for SYMM).
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from .dispatch import ring_nb
from .packing import packed_to_tiles, tile_tril_coords, tiles_to_packed


def _mm_t(x, y):
    """x @ y^T over the last two axes, batch-generic."""
    return jnp.einsum("...ik,...jk->...ij", x, y)


def _mm(x, y):
    return jnp.einsum("...ij,...jk->...ik", x, y)


def _mm_T(x, y):
    """x^T @ y over the last two axes, batch-generic."""
    return jnp.einsum("...ji,...jk->...ik", x, y)


def _fwd_perm(P):
    return [(i, (i + 1) % P) for i in range(P)]


# --------------------------------------------------------------------------
# ring bodies (shard_map over one named axis)
# --------------------------------------------------------------------------


def syrk_ring(a_stage, mesh, axis: str = "x"):
    """Ring SYRK over a staged operand.

    ``a_stage``: (P, …, nb, n2) — device-major zero-padded row blocks.
    Returns the device-major slot stack (P, …, S+1, nb, nb); exactly
    ⌊P/2⌋ collective-permutes on the wire.
    """
    P = mesh.shape[axis]
    assert P >= 2, "ring route needs P >= 2"
    S = P // 2
    even = P % 2 == 0
    perm = _fwd_perm(P)

    def body(x):
        a_loc = x[0]
        buf = a_loc
        slots = [jnp.tril(_mm_t(a_loc, a_loc))]
        for s in range(1, S + 1):
            buf = jax.lax.ppermute(buf, axis, perm=perm)
            if even and s == S:
                # antipodal shift: split the block with the partner —
                # rank < P/2 computes rows [:h], the partner rows [h:],
                # each as a half-size dot (this is where the flop
                # saving over a masked full block comes from)
                h = a_loc.shape[-2] // 2
                lo = jax.lax.axis_index(axis) < P // 2
                lhs = jnp.where(lo, buf[..., :h, :], a_loc[..., h:, :])
                rhs = jnp.where(lo, a_loc, buf)
                half = _mm_t(lhs, rhs)
                z = jnp.zeros_like(half)
                slots.append(jnp.concatenate(
                    [jnp.where(lo, half, z), jnp.where(lo, z, half)],
                    axis=-2))
            else:
                slots.append(_mm_t(a_loc, buf))
        return jnp.stack(slots, axis=-3)[None]

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=PartitionSpec(axis),
        out_specs=PartitionSpec(axis)))(a_stage)


def syr2k_ring(ab_stage, mesh, axis: str = "x"):
    """Ring SYR2K: ``ab_stage`` (P, 2, …, nb, n2) stacks A and B row
    blocks so ONE buffer (hence still exactly ⌊P/2⌋ ppermutes)
    circulates both.  Returns (P, …, S+1, nb, nb) slots of
    A·Bᵀ + B·Aᵀ."""
    P = mesh.shape[axis]
    assert P >= 2, "ring route needs P >= 2"
    S = P // 2
    even = P % 2 == 0
    perm = _fwd_perm(P)

    def body(x):
        ab = x[0]
        a_loc, b_loc = ab[0], ab[1]
        buf = ab
        g = _mm_t(a_loc, b_loc)
        slots = [jnp.tril(g + jnp.swapaxes(g, -1, -2))]
        for s in range(1, S + 1):
            buf = jax.lax.ppermute(buf, axis, perm=perm)
            if even and s == S:
                h = a_loc.shape[-2] // 2
                lo = jax.lax.axis_index(axis) < P // 2
                lhs_a = jnp.where(lo, buf[0][..., :h, :],
                                  a_loc[..., h:, :])
                rhs_b = jnp.where(lo, b_loc, buf[1])
                lhs_b = jnp.where(lo, buf[1][..., :h, :],
                                  b_loc[..., h:, :])
                rhs_a = jnp.where(lo, a_loc, buf[0])
                half = _mm_t(lhs_a, rhs_b) + _mm_t(lhs_b, rhs_a)
                z = jnp.zeros_like(half)
                slots.append(jnp.concatenate(
                    [jnp.where(lo, half, z), jnp.where(lo, z, half)],
                    axis=-2))
            else:
                slots.append(_mm_t(a_loc, buf[1]) + _mm_t(b_loc, buf[0]))
        return jnp.stack(slots, axis=-3)[None]

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=PartitionSpec(axis),
        out_specs=PartitionSpec(axis)))(ab_stage)


def symm_ring(slots_stage, b_stage, mesh, axis: str = "x"):
    """Ring SYMM: C = sym(S)·B with S held as the ring slot stack.

    ``slots_stage``: (P, …, S+1, nb, nb) — the :func:`packed_to_ring`
    layout (diagonal slot tril-masked, transposed partners
    materialized, even-P antipodal block FULL on both partners).
    ``b_stage``: (P, …, nb, n2) row blocks of B.  Returns the
    device-major C row blocks (P, …, nb, n2).

    Each shift s contributes the owned update S[r,q]·B_q locally AND
    the mirror update S[q,r]·B_r into a return buffer riding with B;
    at the even-P antipodal shift the mirror is skipped (the partner's
    own full-block update already covers it).  S+1 ppermutes total.
    """
    P = mesh.shape[axis]
    assert P >= 2, "ring route needs P >= 2"
    S = P // 2
    even = P % 2 == 0
    perm = _fwd_perm(P)
    home = [(i, (i - S) % P) for i in range(P)]

    def body(sx, bx):
        sl, b_loc = sx[0], bx[0]
        diag = sl[..., 0, :, :]
        sym = diag + jnp.swapaxes(jnp.tril(diag, -1), -1, -2)
        c_own = _mm(sym, b_loc)
        buf = jnp.stack([b_loc, jnp.zeros_like(b_loc)], axis=0)
        for s in range(1, S + 1):
            buf = jax.lax.ppermute(buf, axis, perm=perm)
            L = sl[..., s, :, :]
            c_own = c_own + _mm(L, buf[0])
            if not (even and s == S):
                buf = buf.at[1].add(_mm_T(L, b_loc))
        ret = jax.lax.ppermute(buf[1], axis, perm=home)
        return (c_own + ret)[None]

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(PartitionSpec(axis),
                                   PartitionSpec(axis)),
        out_specs=PartitionSpec(axis)))(slots_stage, b_stage)


# --------------------------------------------------------------------------
# (device, slot) <-> packed-triangle layout converters
# --------------------------------------------------------------------------


@lru_cache(maxsize=None)
def ring_block_tables(P: int):
    """Static gather tables: lower block t=(i,j) of the P×P block grid
    (row-major, j ≤ i) ← flat ring slot ``dev·(S+1)+s``.

    d = i−j ≤ S: device i slot d holds C[i,j] directly.  d > S: device
    j slot P−d holds C[j,i] = C[i,j]ᵀ (transpose on the way out).
    Even P, d = S: the block is the SUM of both partners' half-slots
    (device i rows [h:], device j rows [:h]), no transpose.
    """
    S = P // 2
    even = P % 2 == 0
    coords = [(i, j) for i in range(P) for j in range(i + 1)]
    src1 = np.zeros(len(coords), np.int32)
    src2 = np.zeros(len(coords), np.int32)
    use2 = np.zeros(len(coords), bool)
    transp = np.zeros(len(coords), bool)
    for t, (i, j) in enumerate(coords):
        d = i - j
        if even and d == S:
            src1[t] = i * (S + 1) + S
            src2[t] = j * (S + 1) + S
            use2[t] = True
        elif d <= S:
            src1[t] = i * (S + 1) + d
        else:
            src1[t] = j * (S + 1) + (P - d)
            transp[t] = True
    return src1, src2, use2, transp


@lru_cache(maxsize=None)
def ring_unpack_tables(P: int):
    """Static gather tables: (device r, slot s) ← lower block index.

    Slot s on device r must hold S[r, q] for q = (r−s) mod P: the lower
    block (r,q) directly when r ≥ q, else block (q,r) transposed.  For
    even P both antipodal partners get the FULL block (one direct, one
    transposed) — the SYMM body skips the mirror update there.
    """
    S = P // 2
    src = np.zeros((P, S + 1), np.int32)
    transp = np.zeros((P, S + 1), bool)
    for r in range(P):
        for s in range(S + 1):
            q = (r - s) % P
            if r >= q:
                src[r, s] = r * (r + 1) // 2 + q
            else:
                src[r, s] = q * (q + 1) // 2 + r
                transp[r, s] = True
    return src, transp


def ring_stack_to_packed(stack, n1: int):
    """(P, …, S+1, nb, nb) device-major slot stack → packed (…, L)."""
    P = stack.shape[0]
    S = P // 2
    nb = stack.shape[-1]
    src1, src2, use2, transp = ring_block_tables(P)
    flat = jnp.moveaxis(stack, 0, -4)
    flat = flat.reshape(flat.shape[:-4] + (P * (S + 1), nb, nb))
    g = jnp.take(flat, jnp.asarray(src1), axis=-3)
    g2 = jnp.take(flat, jnp.asarray(src2), axis=-3)
    g = g + jnp.where(jnp.asarray(use2)[:, None, None], g2,
                      jnp.zeros_like(g2))
    blocks = jnp.where(jnp.asarray(transp)[:, None, None],
                       jnp.swapaxes(g, -1, -2), g)
    return tiles_to_packed(blocks, n1)


def packed_to_ring(p, n1: int, P: int):
    """Packed (…, L) → (P, …, S+1, nb, nb) device-major slot stack
    (diagonal slots arrive tril-masked; the body symmetrizes)."""
    nb = ring_nb(n1, P)
    S = P // 2
    blocks = packed_to_tiles(p, n1, nb, nt=P)
    src, transp = ring_unpack_tables(P)
    g = jnp.take(blocks, jnp.asarray(src.reshape(-1)), axis=-3)
    g = g.reshape(g.shape[:-3] + (P, S + 1, nb, nb))
    g = jnp.where(jnp.asarray(transp)[:, :, None, None],
                  jnp.swapaxes(g, -1, -2), g)
    return jnp.moveaxis(g, -4, 0)


def gather_ring_stack(stack, mesh, axis: str = "x"):
    """``P(axis)``-sharded slot stack (P, …, S+1, nb, nb) → the same
    stack replicated on every device, by ONE tiled all-gather, so the
    dense exit assembles its blocks locally."""
    return jax.jit(jax.shard_map(
        lambda x: jax.lax.all_gather(x, axis, tiled=True), mesh=mesh,
        in_specs=PartitionSpec(axis), out_specs=PartitionSpec(),
        check_vma=False))(stack)


def _swap(x):
    return jnp.swapaxes(x, -1, -2)


def ring_stack_to_full(stack, n1: int, symmetric: bool = True):
    """(P, …, S+1, nb, nb) device-major slot stack → dense (…, n1, n1):
    the symmetrized matrix, or its lower triangle (``symmetric=False``).

    Each block of the P×P block grid is a static slice of the stack,
    placed by :func:`ring_block_tables` (the even-P antipodal
    block is the sum of the partners' half-slots; diagonal slots are
    read through ``tril``); upper blocks are the lower ones transposed,
    or zero for the tril fill.  Equal to
    ``unpack_tril(ring_stack_to_packed(stack, n1), n1, symmetric=…)``
    with no element-packed round trip."""
    P = stack.shape[0]
    S = P // 2
    src1, src2, use2, transp = ring_block_tables(P)

    def slot(flat):
        dev, s = divmod(int(flat), S + 1)
        return stack[dev, ..., s, :, :]

    def lower(i, j):
        t = i * (i + 1) // 2 + j
        g = slot(src1[t]) + slot(src2[t]) if use2[t] else slot(src1[t])
        return _swap(g) if transp[t] else g

    zero = jnp.zeros_like(stack[0, ..., 0, :, :])
    rows = []
    for i in range(P):
        row = []
        for j in range(P):
            if i == j:
                t = jnp.tril(stack[i, ..., 0, :, :])
                row.append(t + _swap(jnp.tril(t, -1)) if symmetric else t)
            elif j < i:
                row.append(lower(i, j))
            else:
                row.append(_swap(lower(j, i)) if symmetric else zero)
        rows.append(jnp.concatenate(row, axis=-1))
    full = jnp.concatenate(rows, axis=-2)
    return full[..., :n1, :n1]


def dense_to_ring(a, P: int):
    """Dense (…, n1, n1), lower triangle valid → (P, …, S+1, nb, nb)
    device-major slot stack, the :func:`packed_to_ring` layout.

    Slot s of device r holds block (r, q = (r−s) mod P) of tril(A), as
    :func:`ring_unpack_tables` places it: a static block slice when
    r ≥ q, the transposed block (q, r) when r < q (at even P both
    antipodal partners get the full block), the diagonal slot
    tril-masked.  The upper triangle of A is never read,
    and A is never packed."""
    n1 = a.shape[-1]
    nb = ring_nb(n1, P)
    pad = P * nb - n1
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 2) + [(0, pad), (0, pad)])
    src, transp = ring_unpack_tables(P)
    coords = tile_tril_coords(P)

    def slot(r, s):
        i, j = coords[src[r, s]]
        g = a[..., i * nb:(i + 1) * nb, j * nb:(j + 1) * nb]
        if i == j:
            return jnp.tril(g)
        return _swap(g) if transp[r, s] else g

    return jnp.stack([jnp.stack([slot(r, s) for s in range(src.shape[1])],
                                axis=-3) for r in range(P)], axis=0)

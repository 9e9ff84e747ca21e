"""shard_map execution paths for :mod:`repro.blas` (replicated in/out),
and the one table that maps a mesh route to them.

The core parallel algorithms (core/{twodim,threedim,ringpath}.py)
operate on pre-distributed device layouts — the right interface when
the data already lives sharded.  The blas front-end instead takes
ordinary (replicated or GSPMD-sharded) arrays, so this module adds
traced jnp distribute / assemble shims around them, one schedule per
(route family, op):

  1d         — column-shard the non-symmetric operands, move only the
               packed triangle (Algs 7–9's wire);
  ring       — cyclic shifts of row blocks, each device computing only
               the unique blocks it owns (flop-halving SYRK/SYR2K);
  2d         — triangle-block layout on exactly P = c(c+1) devices
               (Algs 10–12);
  3d         — p1 × p2 grid (2D in-slice + replication axis, Algs
               13–15), reshaped from a single-axis mesh;
  3d-limited — the 3d grid streaming b-column chunks (Algs 16–18, §IX).

Batch-native: every schedule but 3d-limited takes leading batch dims
(…, n1, n2), and the stack rides the collectives' payloads — one
collective (pair) covers it, since collectives don't vmap under
shard_map.  An unbatched call is a stack of one
(:func:`_batch_native`).  3d-limited has one unbatched form: the
planner never routes a stack to it.

Packed wire discipline: the symmetric operand/result crosses every
boundary here in a packed layout — the element-packed triangle on the
1D and ring wires, :class:`~repro.core.packing.ShardedTriTiles`
extended triangle-block shards on the 2D/3D wires.  SYRK/SYR2K return
the packed triangle (1d, ring) or ``ShardedTriTiles`` (2d, 3d) and SYMM
consumes either; nothing on these paths builds an n₁×n₁ dense
intermediate.  The dense exits and entrance live in
:mod:`repro.blas.api`, except the ring's: ``*_ring_dense`` move between
dense and the ring slot stacks in whole nb×nb blocks, never through the
packed triangle.  All functions take/return f32; :mod:`repro.blas.api`
handles fill/dtype.

:data:`WIRES` is the only place a route's path picks its schedules:
the blas executors, the autodiff layer and the ABFT runners look a
route up there and never branch on its path.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..core import ringpath
from ..core.dispatch import ring_nb
from ..core.onedim import _padded_tril_len
from ..core.packing import ShardedTriTiles, pack_tril, tril_size, unpack_tril
from ..core.threedim import (flat_tb_size, symm_3d, symm_3d_limited,
                             syr2k_3d, syr2k_3d_limited, syrk_3d,
                             syrk_3d_limited)
from ..core.twodim import TwoDPlan, make_2d_plan, symm_2d, syr2k_2d, syrk_2d

TB_AXIS, REP_AXIS = "blas_p1", "blas_p2"


# --------------------------------------------------------------------------
# batch dims, operand layouts
# --------------------------------------------------------------------------
def _batch_native(n_ops: int):
    """Lift a stack schedule — its first ``n_ops`` arguments carry one
    leading stack axis K — to any leading batch dims, none included:
    they fold into K (an unbatched call is a stack of one) and unfold
    on the output.  The last of those operands is a non-symmetric
    (…, n1, n2) matrix, whose leading dims are the batch."""
    def wrap(stack_schedule):
        @functools.wraps(stack_schedule)
        def run(*args, **kw):
            lead = args[n_ops - 1].shape[:-2]
            ops = jax.tree.map(
                lambda x: x.reshape((-1,) + x.shape[len(lead):]),
                args[:n_ops])
            out = stack_schedule(*ops, *args[n_ops:], **kw)
            return jax.tree.map(lambda y: y.reshape(lead + y.shape[1:]),
                                out)
        return run
    return wrap


def as_packed(a) -> jax.Array:
    """A packed-layout symmetric matrix as the element-packed triangle
    (a mesh-resident ShardedTriTiles regathers only its packed words)."""
    return a.to_packed() if isinstance(a, ShardedTriTiles) else a


def _on_grid(a, n1: int, c: int) -> ShardedTriTiles:
    """A packed-layout symmetric operand as ShardedTriTiles of the
    c-grid: the packed triangle scatters straight into the extended
    triangle-block shards (a pure index-table scatter, no dense
    staging); a mesh-resident layout is used as is, and repacked only
    when it was built for another c."""
    if isinstance(a, ShardedTriTiles) and a.c == c:
        return a
    return ShardedTriTiles.from_packed(as_packed(a), n1, c)


def _pin_row_shards(x: jax.Array, mesh: Mesh, *axes: str) -> jax.Array:
    """Constrain the leading device axes of a staged (P, …) — or
    (p1, p2, …) — buffer to the mesh axes, so a ``P(axis)``-row-sharded
    operand enters the shard_map without a replicating gather first."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*axes)))


# --------------------------------------------------------------------------
# traced distribute / collect for the non-symmetric operands
# --------------------------------------------------------------------------
def distribute_rows_jnp(x: jax.Array, plan: TwoDPlan) -> jax.Array:
    """(K, n1, n2) -> (P, K, c, nb, w): per-device row-block column
    shares, the stack behind the device axis so it rides one exchange
    payload."""
    c, nb, w = plan.c, plan.nb, plan.w
    K = x.shape[0]
    xp = jnp.zeros((K, plan.n1_pad, plan.n2_pad), x.dtype)
    xp = xp.at[:, :x.shape[1], :x.shape[2]].set(x)
    blocks = xp.reshape(K, c * c, nb, plan.n2_pad)
    rows = blocks[:, np.asarray(plan.R)]               # (K, P, c, nb, n2_pad)
    base = plan.self_col[..., None] * w + np.arange(w)  # (P, c, w) static
    idx = jnp.asarray(base)[None, :, :, None, :]
    return jnp.moveaxis(jnp.take_along_axis(rows, idx, axis=-1), 0, 1)


def collect_rows_jnp(dist: jax.Array, plan: TwoDPlan) -> jax.Array:
    """Inverse of :func:`distribute_rows_jnp` (unpadded)."""
    c, nb, w = plan.c, plan.nb, plan.w
    Pn, K = dist.shape[:2]
    rows_idx = np.asarray(plan.R).reshape(-1)           # (P*c,)
    col_idx = (plan.self_col[..., None] * w
               + np.arange(w)).reshape(Pn * c, w)
    data = jnp.moveaxis(dist, 1, 0).reshape(K, Pn * c, nb, w)
    out = jnp.zeros((K, c * c, nb, plan.n2_pad), dist.dtype)
    out = out.at[:, jnp.asarray(rows_idx)[:, None, None],
                 jnp.arange(nb)[None, :, None],
                 jnp.asarray(col_idx)[:, None, :]].set(data)
    return out.reshape(K, plan.n1_pad, plan.n2_pad)[:, :plan.n1, :plan.n2]


def distribute_rows_3d_jnp(x: jax.Array, plan: TwoDPlan, p2: int
                           ) -> jax.Array:
    """(K, n1, n2) -> (p1, p2, K, c, nb, w2): column slices over the
    replication axis, 2D layout within each (n2 % p2 == 0 required)."""
    K, n1, n2 = x.shape
    xs = x.reshape(K, n1, p2, n2 // p2).transpose(2, 0, 1, 3)
    d = distribute_rows_jnp(xs.reshape(p2 * K, n1, n2 // p2), plan)
    return d.reshape((d.shape[0], p2, K) + d.shape[2:])


def collect_rows_3d_jnp(c_dist: jax.Array, plan: TwoDPlan, p2: int
                        ) -> jax.Array:
    """(p1, p2, K, c, nb, w2) SYMM output -> dense (K, n1, n2)."""
    p1, _, K = c_dist.shape[:3]
    per = collect_rows_jnp(c_dist.reshape((p1, p2 * K) + c_dist.shape[3:]),
                           plan)                          # (p2·K, n1, n2s)
    n1 = per.shape[1]
    return per.reshape(p2, K, n1, -1).transpose(1, 2, 0, 3).reshape(K, n1, -1)


def _sharded_from_flat(flat: jax.Array, plan: TwoDPlan, n1: int, c: int
                       ) -> ShardedTriTiles:
    """(p1, p2, …, shard) reduce-scattered 3D output -> ShardedTriTiles
    with the … stack dims leading (a reshape of the ~n²/2 packed words;
    no dense rebuild)."""
    p1 = flat.shape[0]
    lead = flat.shape[2:-1]
    flat = jnp.moveaxis(flat, (0, 1), (-3, -2)).reshape(lead + (p1, -1))
    flat = flat[..., :flat_tb_size(plan)]
    t = plan.T * plan.nb * plan.nb
    off = flat[..., :t].reshape(lead + (p1, plan.T, plan.nb, plan.nb))
    diag = flat[..., t:].reshape(lead + (p1, plan.nb, plan.nb))
    return ShardedTriTiles(off, diag, n1, c)


def _flat_from_sharded(st: ShardedTriTiles, p2: int) -> jax.Array:
    """ShardedTriTiles (stack dims … leading) -> (p1, p2, …, shard):
    flattened extended triangle blocks, shard-split over the
    replication axis (3D SYMM input)."""
    lead = st.diag.shape[:-3]
    p1 = st.num_devices
    flat = jnp.concatenate([st.off.reshape(lead + (p1, -1)),
                            st.diag.reshape(lead + (p1, -1))], -1)
    flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1)
                   + [(0, -flat.shape[-1] % p2)])
    return jnp.moveaxis(flat.reshape(lead + (p1, p2, -1)), (-3, -2), (0, 1))


# --------------------------------------------------------------------------
# 1D (Algs 7–9's wire): packed triangles, the stack along the payload
# --------------------------------------------------------------------------
# ONE reduce-scatter / all-gather of (k, tril) covers the whole stack,
# moving k·n₁²/2 words instead of the 2·k·n₁² of a dense all-reduce +
# broadcast.
def _rank_update_1d(local_gram, operands, mesh: Mesh, axis: str
                    ) -> jax.Array:
    """Shared wire of the 1D rank-updates: pack the local (k, n1, n1)
    Grams (slice-granular batched :func:`pack_tril`), reduce-scatter +
    all-gather the (k, tril) stack once, trim the padding.
    ``local_gram`` maps the per-device column shards to the local Gram
    stack."""
    n1 = operands[0].shape[1]
    nsh = mesh.shape[axis]
    L = tril_size(n1)

    def body(*ops):
        g = local_gram(*ops)
        packed = jnp.pad(pack_tril(g),
                         ((0, 0), (0, _padded_tril_len(n1, nsh) - L)))
        shard = jax.lax.psum_scatter(packed, axis, scatter_dimension=1,
                                     tiled=True)
        return jax.lax.all_gather(shard, axis, axis=1, tiled=True)[:, :L]

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(None, None, axis),) * len(operands),
                         out_specs=P(), check_vma=False)(*operands)


@_batch_native(1)
def syrk_1d_packed(a: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """f32 (…, n1, n2), n2 % P == 0 -> replicated (…, tril_size(n1))."""
    return _rank_update_1d(
        lambda al: jnp.einsum("kmi,kni->kmn", al, al), (a,), mesh, axis)


@_batch_native(2)
def syr2k_1d_packed(a: jax.Array, b: jax.Array, mesh: Mesh, axis: str
                    ) -> jax.Array:
    """f32 (…, n1, n2) × 2 -> replicated (…, tril_size(n1)) of ABᵀ+BAᵀ."""
    def local_gram(al, bl):
        g = jnp.einsum("kmi,kni->kmn", al, bl)
        return g + g.swapaxes(-1, -2)

    return _rank_update_1d(local_gram, (a, b), mesh, axis)


@_batch_native(2)
def symm_1d_packed_a(a_packed: jax.Array, b: jax.Array, n1: int,
                     mesh: Mesh, axis: str) -> jax.Array:
    """f32 (…, tril_size(n1)) × (…, n1, n2), n2 % P == 0 -> (…, n1, n2).

    The packed stack is all-gathered once (Alg 9's wire, batched along
    the payload) and unpacked to the per-device working set — the dense
    rebuild happens only inside the shard_map body, the 1D algorithm's
    own local unpack (slice-granular batched :func:`unpack_tril`)."""
    nsh = mesh.shape[axis]
    L = tril_size(n1)
    packed = jnp.pad(a_packed,
                     ((0, 0), (0, _padded_tril_len(n1, nsh) - L)))

    def body(p_loc, b_loc):
        full = jax.lax.all_gather(p_loc, axis, axis=1, tiled=True)[:, :L]
        sym = unpack_tril(full, n1, diag=True, symmetric=True)
        return jnp.einsum("kmn,knj->kmj", sym, b_loc)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(None, axis), P(None, None, axis)),
                         out_specs=P(None, None, axis),
                         check_vma=False)(packed, b)


# --------------------------------------------------------------------------
# ring: computation-optimal cyclic shift (flop-halving SYRK/SYR2K)
# --------------------------------------------------------------------------
def _ring_stage(x: jax.Array, nsh: int) -> jax.Array:
    """(…, n1, n2) -> (nsh, …, nb, n2): zero-pad the rows to nsh·nb
    blocks and move the device-block axis to the front; leading batch
    dims ride the shifted payload (the stacked-1d pattern)."""
    nb = ring_nb(x.shape[-2], nsh)
    pad = nsh * nb - x.shape[-2]
    if pad:
        z = jnp.zeros(x.shape[:-2] + (pad, x.shape[-1]), x.dtype)
        x = jnp.concatenate([x, z], axis=-2)
    x = x.reshape(x.shape[:-2] + (nsh, nb, x.shape[-1]))
    return jnp.moveaxis(x, -3, 0)


def _ring_unstage(y: jax.Array, n1: int) -> jax.Array:
    """(nsh, …, nb, n2) -> (…, n1, n2): undo :func:`_ring_stage`."""
    y = jnp.moveaxis(y, 0, -3)
    y = y.reshape(y.shape[:-3] + (y.shape[-3] * y.shape[-2], y.shape[-1]))
    return y[..., :n1, :]


def syrk_ring_packed(a: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """f32 (…, n1, n2) -> replicated packed tril of A·Aᵀ (…, L).

    Cyclic-shift schedule: ⌊P/2⌋ ppermutes of the nb×n2 row block, each
    device computing only the unique blocks it owns — ~(P+1)/(2P) of
    the 2d route's per-device flops at 1d-level collective volume."""
    n1 = a.shape[-2]
    nsh = mesh.shape[axis]
    stack = ringpath.syrk_ring(_ring_stage(a, nsh), mesh, axis)
    return ringpath.ring_stack_to_packed(stack, n1)


def syr2k_ring_packed(a: jax.Array, b: jax.Array, mesh: Mesh, axis: str
                      ) -> jax.Array:
    """f32 (…, n1, n2) × 2 -> replicated packed tril of A·Bᵀ + B·Aᵀ.
    A and B row blocks stack into ONE circulating buffer, so the wire
    still moves exactly ⌊P/2⌋ collective-permutes."""
    n1 = a.shape[-2]
    nsh = mesh.shape[axis]
    ab = jnp.stack([_ring_stage(a, nsh), _ring_stage(b, nsh)], axis=1)
    stack = ringpath.syr2k_ring(ab, mesh, axis)
    return ringpath.ring_stack_to_packed(stack, n1)


def _ring_dense_exit(stack: jax.Array, n1: int, mesh: Mesh, axis: str,
                     symmetric: bool) -> jax.Array:
    """Slot stack -> replicated dense (…, n1, n1), symmetrized or its
    lower triangle: gathered once, placed in whole nb×nb blocks (no
    element-packed round trip)."""
    return ringpath.ring_stack_to_full(
        ringpath.gather_ring_stack(stack, mesh, axis), n1, symmetric)


def syrk_ring_dense(a: jax.Array, mesh: Mesh, axis: str,
                    symmetric: bool = True) -> jax.Array:
    """Dense exit of :func:`syrk_ring_packed`."""
    stack = ringpath.syrk_ring(_ring_stage(a, mesh.shape[axis]), mesh, axis)
    return _ring_dense_exit(stack, a.shape[-2], mesh, axis, symmetric)


def syr2k_ring_dense(a: jax.Array, b: jax.Array, mesh: Mesh, axis: str,
                     symmetric: bool = True) -> jax.Array:
    """Dense exit of :func:`syr2k_ring_packed`."""
    nsh = mesh.shape[axis]
    ab = jnp.stack([_ring_stage(a, nsh), _ring_stage(b, nsh)], axis=1)
    stack = ringpath.syr2k_ring(ab, mesh, axis)
    return _ring_dense_exit(stack, a.shape[-2], mesh, axis, symmetric)


def _symm_ring_slots(slots: jax.Array, b: jax.Array, n1: int, mesh: Mesh,
                     axis: str, pin_b: bool) -> jax.Array:
    """SYMM on ready slot stacks; ``pin_b=True`` keeps the staged B row
    blocks ``P(axis)``-sharded — the sharded-B entry point — instead of
    letting GSPMD replicate them."""
    b_stage = _ring_stage(b, mesh.shape[axis])
    if pin_b:
        b_stage = _pin_row_shards(b_stage, mesh, axis)
    out = ringpath.symm_ring(slots, b_stage, mesh, axis)
    return _ring_unstage(out, n1)


def symm_ring_packed_a(a_packed: jax.Array, b: jax.Array, n1: int,
                       mesh: Mesh, axis: str, pin_b: bool = False
                       ) -> jax.Array:
    """f32 packed tril (…, tril_size(n1)) × (…, n1, n2) -> (…, n1, n2).

    The packed triangle scatters straight into the per-device ring slot
    stacks (a static-table gather, no dense rebuild); B circulates the
    ring."""
    slots = ringpath.packed_to_ring(a_packed, n1, mesh.shape[axis])
    return _symm_ring_slots(slots, b, n1, mesh, axis, pin_b)


def symm_ring_dense(a_sym: jax.Array, b: jax.Array, mesh: Mesh, axis: str,
                    pin_b: bool = False) -> jax.Array:
    """tril-valid dense A: the slot stacks are whole nb×nb blocks of
    tril(A) (:func:`ringpath.dense_to_ring`), never the packed
    triangle."""
    n1 = a_sym.shape[-1]
    slots = ringpath.dense_to_ring(a_sym, mesh.shape[axis])
    return _symm_ring_slots(slots, b, n1, mesh, axis, pin_b)


# --------------------------------------------------------------------------
# 2D (Algs 10–12): P == c(c+1) triangle-block grid, the stack on the
# all-to-all payload
# --------------------------------------------------------------------------
def _stack_leading(off: jax.Array, diag: jax.Array, n1: int, c: int
                   ) -> ShardedTriTiles:
    """Device-leading (P, K, …) core output -> stack-leading
    ShardedTriTiles (K, P, …)."""
    return ShardedTriTiles(jnp.moveaxis(off, 0, 1), jnp.moveaxis(diag, 0, 1),
                           n1, c)


@_batch_native(1)
def syrk_2d_sharded(a: jax.Array, c: int, mesh: Mesh, axis: str
                    ) -> ShardedTriTiles:
    """f32 (…, n1, n2) -> per-device extended triangle blocks of
    tril(A·Aᵀ), stack dims leading — the output stays in the
    ~n²/(2P)-per-device wire format; callers gather only the packed
    words (``.to_packed()``)."""
    _, n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2)
    off, diag = syrk_2d(distribute_rows_jnp(a, plan), plan, mesh, axis)
    return _stack_leading(off, diag, n1, c)


@_batch_native(2)
def syr2k_2d_sharded(a: jax.Array, b: jax.Array, c: int, mesh: Mesh,
                     axis: str) -> ShardedTriTiles:
    _, n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2)
    off, diag = syr2k_2d(distribute_rows_jnp(a, plan),
                         distribute_rows_jnp(b, plan), plan, mesh, axis)
    return _stack_leading(off, diag, n1, c)


@_batch_native(2)
def symm_2d_packed_a(a, b: jax.Array, c: int, mesh: Mesh, axis: str,
                     pin_b: bool = False) -> jax.Array:
    """Packed-layout A — the packed triangle (…, tril_size(n1)) or a
    mesh-resident ShardedTriTiles, no distribute step for A at all —
    × (…, n1, n2) -> (…, n1, n2).  ``pin_b=True`` keeps the staged B row
    shares ``P(axis)``-sharded (the sharded-B entry point) instead of
    letting GSPMD replicate them."""
    _, n1, n2 = b.shape
    st = _on_grid(a, n1, c)
    plan = make_2d_plan(c, n1, n2)
    b_dist = distribute_rows_jnp(b, plan)
    if pin_b:
        b_dist = _pin_row_shards(b_dist, mesh, axis)
    c_dist = symm_2d(jnp.moveaxis(st.off, 0, 1), jnp.moveaxis(st.diag, 0, 1),
                     b_dist, plan, mesh, axis)
    return collect_rows_jnp(c_dist, plan)


# --------------------------------------------------------------------------
# 3D (Algs 13–15): p1 × p2 grid from a single-axis mesh; the stack rides
# the in-slice all-to-all and the cross-slice reduce-scatter / all-gather
# --------------------------------------------------------------------------
def _mesh_3d(mesh: Mesh, p1: int, p2: int) -> Mesh:
    devs = np.asarray(mesh.devices).reshape(-1)
    return Mesh(devs[:p1 * p2].reshape(p1, p2), (TB_AXIS, REP_AXIS))


@_batch_native(1)
def syrk_3d_sharded(a: jax.Array, c: int, p2: int, mesh: Mesh
                    ) -> ShardedTriTiles:
    _, n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2 // p2)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    flat = syrk_3d(distribute_rows_3d_jnp(a, plan, p2), plan, mesh3,
                   TB_AXIS, REP_AXIS)
    return _sharded_from_flat(flat, plan, n1, c)


@_batch_native(2)
def syr2k_3d_sharded(a: jax.Array, b: jax.Array, c: int, p2: int,
                     mesh: Mesh) -> ShardedTriTiles:
    _, n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2 // p2)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    flat = syr2k_3d(distribute_rows_3d_jnp(a, plan, p2),
                    distribute_rows_3d_jnp(b, plan, p2), plan, mesh3,
                    TB_AXIS, REP_AXIS)
    return _sharded_from_flat(flat, plan, n1, c)


@_batch_native(2)
def symm_3d_packed_a(a, b: jax.Array, c: int, p2: int, mesh: Mesh,
                     pin_b: bool = False) -> jax.Array:
    """Packed-layout A (as :func:`symm_2d_packed_a`) × (…, n1, n2),
    its extended triangle blocks shard-split over the replication axis.
    ``pin_b=True`` keeps the staged B shares ``P(p1, p2)``-sharded."""
    _, n1, n2 = b.shape
    st = _on_grid(a, n1, c)
    plan = make_2d_plan(c, n1, n2 // p2)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    b_dist = distribute_rows_3d_jnp(b, plan, p2)
    if pin_b:
        b_dist = _pin_row_shards(b_dist, mesh3, TB_AXIS, REP_AXIS)
    c_dist = symm_3d(_flat_from_sharded(st, p2), b_dist, plan, mesh3,
                     TB_AXIS, REP_AXIS)
    return collect_rows_3d_jnp(c_dist, plan, p2)


# --------------------------------------------------------------------------
# 3D limited-memory (Algs 16–18, §IX): streamed b-column chunks, unbatched
# --------------------------------------------------------------------------
def _limited_steps(n2: int, p2: int, b: int):
    """Clamp the chunk to the per-slice column count and return
    (b, nsteps) with nsteps·b >= n2/p2 (the tail chunk is zero-padded —
    padded columns add nothing to a rank update and padded SYMM output
    columns are trimmed at collect)."""
    n2s = max(n2 // p2, 1)
    b = max(min(b, n2s), 1)
    return b, -(-n2s // b)


def _chunk_cols_3d_jnp(x: jax.Array, plan_b: TwoDPlan, p2: int,
                       nsteps: int) -> jax.Array:
    """(n1, n2) -> (p1, p2, nsteps, c, nb, bw): column slices over the
    replication axis, each zero-padded to nsteps b-column chunks, 2D
    row-share layout per chunk (n2 % p2 == 0 required) — the 3d layout
    with p2·nsteps slices."""
    n1, n2 = x.shape
    pad = nsteps * plan_b.n2 - n2 // p2
    xs = jnp.pad(x.reshape(n1, p2, n2 // p2), ((0, 0), (0, 0), (0, pad)))
    d = distribute_rows_3d_jnp(xs.reshape(1, n1, -1), plan_b, p2 * nsteps)
    return d.reshape((d.shape[0], p2, nsteps) + d.shape[3:])


def _collect_cols_3d_jnp(c_dist: jax.Array, plan_b: TwoDPlan, p2: int,
                         n2: int) -> jax.Array:
    """Inverse of :func:`_chunk_cols_3d_jnp` for the SYMM output
    (drops the zero-padded tail columns)."""
    p1, _, nsteps = c_dist.shape[:3]
    full = collect_rows_3d_jnp(
        c_dist.reshape((p1, p2 * nsteps, 1) + c_dist.shape[3:]), plan_b,
        p2 * nsteps)[0]                            # (n1, p2·nsteps·b)
    n1 = full.shape[0]
    return full.reshape(n1, p2, -1)[:, :, :n2 // p2].reshape(n1, n2)


def syrk_3d_limited_sharded(a: jax.Array, c: int, p2: int, chunk: int,
                            mesh: Mesh) -> ShardedTriTiles:
    """Alg 16 on the packed wire: stream ``chunk``-column panels through
    the scan, reduce-scatter the accumulated extended triangle blocks
    once.  Per-device peak-live stays O(chunk working set + owned
    triangle block), not O(n₂/p₂)."""
    n1, n2 = a.shape
    b, nsteps = _limited_steps(n2, p2, chunk)
    plan_b = make_2d_plan(c, n1, b)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    flat = syrk_3d_limited(_chunk_cols_3d_jnp(a, plan_b, p2, nsteps),
                           plan_b, mesh3, TB_AXIS, REP_AXIS)
    return _sharded_from_flat(flat, plan_b, n1, c)


def syr2k_3d_limited_sharded(a: jax.Array, b_mat: jax.Array, c: int,
                             p2: int, chunk: int, mesh: Mesh
                             ) -> ShardedTriTiles:
    n1, n2 = a.shape
    b, nsteps = _limited_steps(n2, p2, chunk)
    plan_b = make_2d_plan(c, n1, b)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    flat = syr2k_3d_limited(_chunk_cols_3d_jnp(a, plan_b, p2, nsteps),
                            _chunk_cols_3d_jnp(b_mat, plan_b, p2, nsteps),
                            plan_b, mesh3, TB_AXIS, REP_AXIS)
    return _sharded_from_flat(flat, plan_b, n1, c)


def symm_3d_limited_packed_a(a, b: jax.Array, c: int, p2: int, chunk: int,
                             mesh: Mesh, pin_b: bool = False) -> jax.Array:
    """Alg 18: gather A's triangle blocks once, stream B/C chunks."""
    n1, n2 = b.shape
    st = _on_grid(a, n1, c)
    bw, nsteps = _limited_steps(n2, p2, chunk)
    plan_b = make_2d_plan(c, n1, bw)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    b_dist = _chunk_cols_3d_jnp(b, plan_b, p2, nsteps)
    if pin_b:
        b_dist = _pin_row_shards(b_dist, mesh3, TB_AXIS, REP_AXIS)
    c_dist = symm_3d_limited(_flat_from_sharded(st, p2), b_dist,
                             plan_b, mesh3, TB_AXIS, REP_AXIS)
    return _collect_cols_3d_jnp(c_dist, plan_b, p2, n2)


# --------------------------------------------------------------------------
# the route table
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class Wire:
    """The schedules of one mesh route family, on f32 operands:
    ``syrk(a, mesh, route)``, ``syr2k(a, b, mesh, route)`` and
    ``symm(a, b, mesh, route, pin_b)``.  The route supplies the axis and
    the grid (``route.choice``).

    ``syrk``/``syr2k`` return the replicated packed triangle
    (…, tril_size(n1)) or, where ``sharded``, the mesh-resident
    ShardedTriTiles; ``symm`` takes its symmetric operand as the packed
    triangle or as ShardedTriTiles.  ``pin_b`` keeps a row-sharded B
    sharded into the shard_map (the 1D wire column-shards B itself and
    ignores it).  The ``*_dense`` members, where set, are the family's
    own dense exits (``syrk_dense(a, mesh, route, symmetric)``) and
    dense-A entrance (``symm_dense(a, b, mesh, route, pin_b)``); the
    other families exit and enter through the packed triangle."""
    syrk: Callable
    syr2k: Callable
    symm: Callable
    sharded: bool = False
    syrk_dense: Optional[Callable] = None
    syr2k_dense: Optional[Callable] = None
    symm_dense: Optional[Callable] = None


WIRES = {
    "1d": Wire(
        syrk=lambda a, mesh, r: syrk_1d_packed(a, mesh, r.axis),
        syr2k=lambda a, b, mesh, r: syr2k_1d_packed(a, b, mesh, r.axis),
        symm=lambda a, b, mesh, r, pin_b=False: symm_1d_packed_a(
            as_packed(a), b, b.shape[-2], mesh, r.axis)),
    "ring": Wire(
        syrk=lambda a, mesh, r: syrk_ring_packed(a, mesh, r.axis),
        syr2k=lambda a, b, mesh, r: syr2k_ring_packed(a, b, mesh, r.axis),
        symm=lambda a, b, mesh, r, pin_b=False: symm_ring_packed_a(
            as_packed(a), b, b.shape[-2], mesh, r.axis, pin_b),
        syrk_dense=lambda a, mesh, r, symmetric: syrk_ring_dense(
            a, mesh, r.axis, symmetric),
        syr2k_dense=lambda a, b, mesh, r, symmetric: syr2k_ring_dense(
            a, b, mesh, r.axis, symmetric),
        symm_dense=lambda a, b, mesh, r, pin_b=False: symm_ring_dense(
            a, b, mesh, r.axis, pin_b)),
    "2d": Wire(
        syrk=lambda a, mesh, r: syrk_2d_sharded(a, r.choice.c, mesh,
                                                r.axis),
        syr2k=lambda a, b, mesh, r: syr2k_2d_sharded(a, b, r.choice.c, mesh,
                                                     r.axis),
        symm=lambda a, b, mesh, r, pin_b=False: symm_2d_packed_a(
            a, b, r.choice.c, mesh, r.axis, pin_b),
        sharded=True),
    "3d": Wire(
        syrk=lambda a, mesh, r: syrk_3d_sharded(a, r.choice.c, r.choice.p2,
                                                mesh),
        syr2k=lambda a, b, mesh, r: syr2k_3d_sharded(
            a, b, r.choice.c, r.choice.p2, mesh),
        symm=lambda a, b, mesh, r, pin_b=False: symm_3d_packed_a(
            a, b, r.choice.c, r.choice.p2, mesh, pin_b),
        sharded=True),
    "3d-limited": Wire(
        syrk=lambda a, mesh, r: syrk_3d_limited_sharded(
            a, r.choice.c, r.choice.p2, r.choice.b, mesh),
        syr2k=lambda a, b, mesh, r: syr2k_3d_limited_sharded(
            a, b, r.choice.c, r.choice.p2, r.choice.b, mesh),
        symm=lambda a, b, mesh, r, pin_b=False: symm_3d_limited_packed_a(
            a, b, r.choice.c, r.choice.p2, r.choice.b, mesh, pin_b),
        sharded=True),
}


def wire(route) -> Optional[Wire]:
    """The mesh schedules of ``route``, or None off the mesh (the
    ``pallas`` and ``dense`` routes)."""
    return WIRES.get(route.path)

"""shard_map execution paths for :mod:`repro.blas` (replicated in/out).

The core parallel algorithms (core/{onedim,twodim,threedim}.py) operate
on pre-distributed device layouts — the right interface when the data
already lives sharded.  The blas front-end instead takes ordinary
(replicated or GSPMD-sharded) arrays, so this module adds traced jnp
distribute / assemble shims around them:

  1D — column-shard the non-symmetric operands, move only the packed
       triangle (Algs 7–9); batched stacks ride the same wire (one
       reduce-scatter / all-gather covers the whole stack);
  2D — triangle-block layout on exactly P = c(c+1) devices (Algs 10–12);
  3D — p1 × p2 grid (2D in-slice + replication axis, Algs 13–15),
       reshaped from a single-axis mesh.

Packed wire discipline: the symmetric operand/result crosses every
boundary here in a packed layout — the element-packed triangle on the
1D wire, :class:`~repro.core.packing.ShardedTriTiles` extended
triangle-block shards on the 2D/3D wire.  SYRK/SYR2K return
``ShardedTriTiles`` (2d/3d) or the packed triangle (1d) and SYMM
consumes a pre-packed triangle via a pure scatter into the per-device
shards; nothing on these paths builds an n₁×n₁ dense intermediate —
that exit exists only in the explicitly-dense ``*_dense`` wrappers.
The ring's ``*_ring_dense`` wrappers move between dense and the ring
slot stacks in whole nb×nb blocks, never through the packed triangle.
All functions take/return f32; :mod:`repro.blas.api` handles
fill/dtype.

The distribute/collect helpers mirror the numpy host-side versions in
core/twodim.py but use static index tables with jnp gathers/scatters so
they stay traceable under jit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..core import ringpath
from ..core.dispatch import ring_nb
from ..core.onedim import (_padded_tril_len, symm_1d_local, syr2k_1d_local,
                           syrk_1d_local)
from ..core.packing import (ShardedTriTiles, pack_tril, tril_size,
                            unpack_tril)
from ..core.twodim import (TwoDPlan, make_2d_plan, symm_2d,
                           symm_2d_stacked, syr2k_2d, syr2k_2d_stacked,
                           syrk_2d, syrk_2d_stacked, tb_flat_words)
from ..core.threedim import (symm_3d, symm_3d_limited, symm_3d_stacked,
                             syr2k_3d, syr2k_3d_limited, syr2k_3d_stacked,
                             syrk_3d, syrk_3d_limited, syrk_3d_stacked)

TB_AXIS, REP_AXIS = "blas_p1", "blas_p2"


# --------------------------------------------------------------------------
# traced distribute / collect for the non-symmetric operands
# --------------------------------------------------------------------------
def distribute_rows_jnp(x: jax.Array, plan: TwoDPlan) -> jax.Array:
    """(n1, n2) -> (P, c, nb, w) per-device row-block column shares."""
    c, nb, w = plan.c, plan.nb, plan.w
    xp = jnp.zeros((plan.n1_pad, plan.n2_pad), x.dtype)
    xp = xp.at[:x.shape[0], :x.shape[1]].set(x)
    blocks = xp.reshape(c * c, nb, plan.n2_pad)
    rows = blocks[np.asarray(plan.R)]                   # (P, c, nb, n2_pad)
    base = plan.self_col[..., None] * w + np.arange(w)  # (P, c, w) static
    idx = jnp.asarray(base)[:, :, None, :]
    return jnp.take_along_axis(rows, idx, axis=-1)


def collect_rows_jnp(dist: jax.Array, plan: TwoDPlan) -> jax.Array:
    """Inverse of :func:`distribute_rows_jnp` (unpadded)."""
    c, nb, w = plan.c, plan.nb, plan.w
    Pn = plan.num_devices
    rows_idx = np.asarray(plan.R).reshape(-1)           # (P*c,)
    col_idx = (plan.self_col[..., None] * w
               + np.arange(w)).reshape(Pn * c, w)
    data = dist.reshape(Pn * c, nb, w)
    out = jnp.zeros((c * c, nb, plan.n2_pad), dist.dtype)
    out = out.at[jnp.asarray(rows_idx)[:, None, None],
                 jnp.arange(nb)[None, :, None],
                 jnp.asarray(col_idx)[:, None, :]].set(data)
    return out.reshape(plan.n1_pad, plan.n2_pad)[:plan.n1, :plan.n2]


def distribute_rows_stacked_jnp(x: jax.Array, plan: TwoDPlan) -> jax.Array:
    """(k, n1, n2) -> (P, k, c, nb, w): the batch stacked behind the
    device axis so the whole stack rides one exchange payload."""
    return jnp.moveaxis(
        jax.vmap(lambda s: distribute_rows_jnp(s, plan))(x), 1, 0)


def collect_rows_stacked_jnp(dist: jax.Array, plan: TwoDPlan) -> jax.Array:
    """Inverse of :func:`distribute_rows_stacked_jnp` (unpadded)."""
    return jax.vmap(lambda d: collect_rows_jnp(d, plan))(
        jnp.moveaxis(dist, 0, 1))


def distribute_rows_3d_jnp(x: jax.Array, plan: TwoDPlan, p2: int
                           ) -> jax.Array:
    """(n1, n2) -> (p1, p2, c, nb, w2): column slices over the
    replication axis, 2D layout within each (n2 % p2 == 0 required)."""
    n1, n2 = x.shape
    xs = x.reshape(n1, p2, n2 // p2).transpose(1, 0, 2)   # (p2, n1, n2s)
    dist = jax.vmap(lambda s: distribute_rows_jnp(s, plan))(xs)
    return dist.transpose(1, 0, 2, 3, 4)                  # (p1, p2, ...)


def collect_rows_3d_jnp(c_dist: jax.Array, plan: TwoDPlan, p2: int
                        ) -> jax.Array:
    """(p1, p2, c, nb, w2) SYMM output -> dense (n1, n2)."""
    per = jax.vmap(lambda d: collect_rows_jnp(d, plan))(
        c_dist.transpose(1, 0, 2, 3, 4))                  # (p2, n1, n2s)
    n1 = per.shape[1]
    return per.transpose(1, 0, 2).reshape(n1, -1)


def flat_tb_size(plan: TwoDPlan) -> int:
    return tb_flat_words(plan.c, plan.n1)


def _sharded_from_flat(flat_shards: jax.Array, plan: TwoDPlan, n1: int,
                       c: int) -> ShardedTriTiles:
    """(p1, p2, shard) reduce-scattered 3D output -> ShardedTriTiles
    (a reshape of the ~n²/2 packed words; no dense rebuild)."""
    p1, p2, s = flat_shards.shape
    flat = flat_shards.reshape(p1, p2 * s)[:, :flat_tb_size(plan)]
    t = plan.T * plan.nb * plan.nb
    off = flat[:, :t].reshape(p1, plan.T, plan.nb, plan.nb)
    diag = flat[:, t:].reshape(p1, plan.nb, plan.nb)
    return ShardedTriTiles(off, diag, n1, c)


def _flat_from_sharded(st: ShardedTriTiles, p2: int) -> jax.Array:
    """ShardedTriTiles -> (p1, p2, shard) flattened extended triangle
    blocks, shard-split over the replication axis (3D SYMM input)."""
    p1 = st.num_devices
    flat = jnp.concatenate([st.off.reshape(p1, -1),
                            st.diag.reshape(p1, -1)], 1)
    pad = -flat.shape[1] % p2
    flat = jnp.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(p1, p2, -1)


def distribute_rows_3d_stacked_jnp(x: jax.Array, plan: TwoDPlan, p2: int
                                   ) -> jax.Array:
    """(k, n1, n2) -> (p1, p2, k, c, nb, w2)."""
    d = jax.vmap(lambda s: distribute_rows_3d_jnp(s, plan, p2))(x)
    return d.transpose(1, 2, 0, 3, 4, 5)


def _sharded_from_flat_stacked(flat_shards: jax.Array, plan: TwoDPlan,
                               n1: int, c: int) -> ShardedTriTiles:
    """(p1, p2, k, shard) stacked 3D output -> batched ShardedTriTiles
    (leading stack dim)."""
    p1, p2, k, s = flat_shards.shape
    flat = flat_shards.transpose(2, 0, 1, 3).reshape(k, p1, p2 * s)
    flat = flat[:, :, :flat_tb_size(plan)]
    t = plan.T * plan.nb * plan.nb
    off = flat[:, :, :t].reshape(k, p1, plan.T, plan.nb, plan.nb)
    diag = flat[:, :, t:].reshape(k, p1, plan.nb, plan.nb)
    return ShardedTriTiles(off, diag, n1, c)


def _flat_from_sharded_stacked(st: ShardedTriTiles, p2: int) -> jax.Array:
    """Batched ShardedTriTiles (leading stack dim) -> (p1, p2, k, shard)."""
    k = st.off.shape[0]
    p1 = st.num_devices
    flat = jnp.concatenate([st.off.reshape(k, p1, -1),
                            st.diag.reshape(k, p1, -1)], 2)
    flat = jnp.pad(flat, ((0, 0), (0, 0), (0, -flat.shape[2] % p2)))
    return flat.reshape(k, p1, p2, -1).transpose(1, 2, 0, 3)


# --------------------------------------------------------------------------
# 1D paths (Algs 7–9): packed triangle on the wire
# --------------------------------------------------------------------------
def syrk_1d_packed(a: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """f32 (n1, n2), n2 % P == 0 -> replicated packed tril of A·Aᵀ."""
    n1 = a.shape[0]
    nsh = mesh.shape[axis]

    def body(a_loc):
        shard = syrk_1d_local(a_loc, axis, nsh)
        full = jax.lax.all_gather(shard, axis, axis=0, tiled=True)
        return full[:tril_size(n1)]

    return jax.shard_map(body, mesh=mesh, in_specs=P(None, axis),
                         out_specs=P(), check_vma=False)(a)


def syr2k_1d_packed(a: jax.Array, b: jax.Array, mesh: Mesh, axis: str
                    ) -> jax.Array:
    n1 = a.shape[0]
    nsh = mesh.shape[axis]

    def body(a_loc, b_loc):
        shard = syr2k_1d_local(a_loc, b_loc, axis, nsh)
        full = jax.lax.all_gather(shard, axis, axis=0, tiled=True)
        return full[:tril_size(n1)]

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(None, axis), P(None, axis)),
                         out_specs=P(), check_vma=False)(a, b)


def symm_1d_packed_a(a_packed: jax.Array, b: jax.Array, n1: int, mesh: Mesh,
                     axis: str) -> jax.Array:
    """f32 packed tril (tril_size(n1),) × (n1, n2), n2 % P == 0 -> (n1, n2).

    SYMM whose symmetric operand arrives *already packed* — the wire
    format of the 1D algorithms, and the shape the autodiff layer hands
    back when a packed-fill SYRK/SYR2K cotangent flows into its
    backward SYMM (no dense round-trip before the shard_map)."""
    nsh = mesh.shape[axis]
    packed = jnp.pad(a_packed,
                     (0, _padded_tril_len(n1, nsh) - a_packed.shape[0]))
    f = functools.partial(symm_1d_local, axis=axis, n1=n1)
    return jax.shard_map(f, mesh=mesh, in_specs=(P(axis), P(None, axis)),
                         out_specs=P(None, axis), check_vma=False)(packed, b)


def symm_1d_dense(a_sym: jax.Array, b: jax.Array, mesh: Mesh, axis: str
                  ) -> jax.Array:
    """f32 tril-valid (n1, n1) × (n1, n2), n2 % P == 0 -> (n1, n2)."""
    n1 = a_sym.shape[0]
    return symm_1d_packed_a(pack_tril(jnp.tril(a_sym)), b, n1, mesh, axis)


# ---- batched stacks on the 1D wire ----------------------------------------
# Collectives don't vmap under shard_map, so batched mesh calls used to
# fall back to GSPMD dense.  Stacking the packed triangles along a
# leading axis (the `_ns_iteration_1d_stacked` pattern in optim.muon)
# keeps them on the comm-optimal wire: ONE reduce-scatter / all-gather
# of (k, tril) covers the whole stack, moving k·n₁²/2 words instead of
# the 2·k·n₁² of a dense all-reduce + broadcast.
def _rank_update_1d_stacked(local_gram, operands, mesh: Mesh, axis: str
                            ) -> jax.Array:
    """Shared wire of the stacked 1D rank-updates: pack the local
    (k, n1, n1) Grams (slice-granular batched :func:`pack_tril`),
    reduce-scatter + all-gather the (k, tril) stack once, trim the
    padding.  ``local_gram`` maps the per-device column shards to the
    local Gram stack."""
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


def syrk_1d_packed_stacked(a: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """f32 (k, n1, n2), n2 % P == 0 -> replicated (k, tril_size(n1))."""
    return _rank_update_1d_stacked(
        lambda al: jnp.einsum("kmi,kni->kmn", al, al), (a,), mesh, axis)


def syr2k_1d_packed_stacked(a: jax.Array, b: jax.Array, mesh: Mesh,
                            axis: str) -> jax.Array:
    """f32 (k, n1, n2) × 2 -> replicated (k, tril_size(n1)) of ABᵀ+BAᵀ."""
    def local_gram(al, bl):
        g = jnp.einsum("kmi,kni->kmn", al, bl)
        return g + g.swapaxes(-1, -2)

    return _rank_update_1d_stacked(local_gram, (a, b), mesh, axis)


def symm_1d_packed_a_stacked(a_packed: jax.Array, b: jax.Array, n1: int,
                             mesh: Mesh, axis: str) -> jax.Array:
    """f32 (k, tril_size(n1)) × (k, n1, n2), n2 % P == 0 -> (k, n1, n2).

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
# 2D paths (Algs 10–12): P == c(c+1) triangle-block grid, packed wire
# --------------------------------------------------------------------------
def syrk_2d_sharded(a: jax.Array, c: int, mesh: Mesh, axis: str
                    ) -> ShardedTriTiles:
    """f32 (n1, n2) -> per-device extended triangle blocks of tril(A·Aᵀ)
    — the output stays in the ~n²/(2P)-per-device wire format; callers
    gather only the packed words (``.to_packed()``) or exit dense
    explicitly."""
    n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2)
    off, diag = syrk_2d(distribute_rows_jnp(a, plan), plan, mesh, axis)
    return ShardedTriTiles(off, diag, n1, c)


def syr2k_2d_sharded(a: jax.Array, b: jax.Array, c: int, mesh: Mesh,
                     axis: str) -> ShardedTriTiles:
    n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2)
    off, diag = syr2k_2d(distribute_rows_jnp(a, plan),
                         distribute_rows_jnp(b, plan), plan, mesh, axis)
    return ShardedTriTiles(off, diag, n1, c)


def symm_2d_sharded_a(st: ShardedTriTiles, b: jax.Array, mesh: Mesh,
                      axis: str, pin_b: bool = False) -> jax.Array:
    """SYMM whose symmetric operand is already on the mesh as
    ShardedTriTiles — no distribute step for A at all.  ``pin_b=True``
    keeps the staged B row shares ``P(axis)``-sharded (the sharded-B
    entry point) instead of letting GSPMD replicate them."""
    n1, n2 = st.n, b.shape[1]
    plan = make_2d_plan(st.c, n1, n2)
    b_dist = distribute_rows_jnp(b, plan)
    if pin_b:
        b_dist = _pin_row_shards(b_dist, mesh, axis)
    c_dist = symm_2d(st.off, st.diag, b_dist, plan, mesh, axis)
    return collect_rows_jnp(c_dist, plan)


def symm_2d_packed_a(a_packed: jax.Array, b: jax.Array, c: int, mesh: Mesh,
                     axis: str, pin_b: bool = False) -> jax.Array:
    """f32 packed tril (tril_size(n1),) × (n1, n2) -> (n1, n2).

    The symmetric operand arrives element-packed and is scattered
    straight into the extended triangle-block shards (a pure
    index-table scatter — the distribute_sym step without the dense
    (n1_pad, n1_pad) staging buffer)."""
    n1 = b.shape[0]
    st = ShardedTriTiles.from_packed(a_packed, n1, c)
    return symm_2d_sharded_a(st, b, mesh, axis, pin_b=pin_b)


# ---- batched stacks on the 2D wire ----------------------------------------
def syrk_2d_sharded_stacked(a: jax.Array, c: int, mesh: Mesh, axis: str
                            ) -> ShardedTriTiles:
    """f32 (k, n1, n2) -> batched ShardedTriTiles (leading stack dim):
    the whole stack rides ONE all-to-all payload."""
    _, n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2)
    off, diag = syrk_2d_stacked(distribute_rows_stacked_jnp(a, plan), plan,
                                mesh, axis)
    return ShardedTriTiles(jnp.moveaxis(off, 0, 1),
                           jnp.moveaxis(diag, 0, 1), n1, c)


def syr2k_2d_sharded_stacked(a: jax.Array, b: jax.Array, c: int,
                             mesh: Mesh, axis: str) -> ShardedTriTiles:
    _, n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2)
    off, diag = syr2k_2d_stacked(distribute_rows_stacked_jnp(a, plan),
                                 distribute_rows_stacked_jnp(b, plan),
                                 plan, mesh, axis)
    return ShardedTriTiles(jnp.moveaxis(off, 0, 1),
                           jnp.moveaxis(diag, 0, 1), n1, c)


def symm_2d_packed_a_stacked(a_packed: jax.Array, b: jax.Array, c: int,
                             mesh: Mesh, axis: str) -> jax.Array:
    """f32 (k, tril_size(n1)) × (k, n1, n2) -> (k, n1, n2): the packed
    stack scatters into batched shards, B rides the stacked exchange."""
    _, n1, n2 = b.shape
    st = ShardedTriTiles.from_packed(a_packed, n1, c)
    plan = make_2d_plan(c, n1, n2)
    c_dist = symm_2d_stacked(jnp.moveaxis(st.off, 0, 1),
                             jnp.moveaxis(st.diag, 0, 1),
                             distribute_rows_stacked_jnp(b, plan),
                             plan, mesh, axis)
    return collect_rows_stacked_jnp(c_dist, plan)


def syrk_2d_dense(a: jax.Array, c: int, mesh: Mesh, axis: str) -> jax.Array:
    """Explicit dense exit: packed wire + one unpack of the result."""
    return syrk_2d_sharded(a, c, mesh, axis).to_tril()


def syr2k_2d_dense(a: jax.Array, b: jax.Array, c: int, mesh: Mesh,
                   axis: str) -> jax.Array:
    return syr2k_2d_sharded(a, b, c, mesh, axis).to_tril()


def symm_2d_dense(a_sym: jax.Array, b: jax.Array, c: int, mesh: Mesh,
                  axis: str, pin_b: bool = False) -> jax.Array:
    """tril-valid dense A: pack the triangle (reads tril only), then the
    packed entrance above."""
    return symm_2d_packed_a(pack_tril(jnp.tril(a_sym)), b, c, mesh, axis,
                            pin_b=pin_b)


# --------------------------------------------------------------------------
# ring path: computation-optimal cyclic shift (flop-halving SYRK/SYR2K)
# --------------------------------------------------------------------------
def _pin_row_shards(x: jax.Array, mesh: Mesh, *axes: str) -> jax.Array:
    """Constrain the leading device axes of a staged (P, …) — or
    (p1, p2, …) — buffer to the mesh axes, so a ``P(axis)``-row-sharded
    operand enters the shard_map without a replicating gather first."""
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*axes)))


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
# 3D paths (Algs 13–15): p1 × p2 grid from a single-axis mesh, packed wire
# --------------------------------------------------------------------------
def _mesh_3d(mesh: Mesh, p1: int, p2: int) -> Mesh:
    devs = np.asarray(mesh.devices).reshape(-1)
    return Mesh(devs[:p1 * p2].reshape(p1, p2), (TB_AXIS, REP_AXIS))


def syrk_3d_sharded(a: jax.Array, c: int, p2: int, mesh: Mesh
                    ) -> ShardedTriTiles:
    n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2 // p2)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    flat = syrk_3d(distribute_rows_3d_jnp(a, plan, p2), plan, mesh3,
                   TB_AXIS, REP_AXIS)
    return _sharded_from_flat(flat, plan, n1, c)


def syr2k_3d_sharded(a: jax.Array, b: jax.Array, c: int, p2: int,
                     mesh: Mesh) -> ShardedTriTiles:
    n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2 // p2)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    flat = syr2k_3d(distribute_rows_3d_jnp(a, plan, p2),
                    distribute_rows_3d_jnp(b, plan, p2), plan, mesh3,
                    TB_AXIS, REP_AXIS)
    return _sharded_from_flat(flat, plan, n1, c)


def symm_3d_sharded_a(st: ShardedTriTiles, b: jax.Array, p2: int,
                      mesh: Mesh, pin_b: bool = False) -> jax.Array:
    """3D SYMM with the symmetric operand already in ShardedTriTiles.
    ``pin_b=True`` keeps the staged B shares ``P(p1, p2)``-sharded."""
    n1, n2 = st.n, b.shape[1]
    c = st.c
    plan = make_2d_plan(c, n1, n2 // p2)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    b_dist = distribute_rows_3d_jnp(b, plan, p2)
    if pin_b:
        b_dist = _pin_row_shards(b_dist, mesh3, TB_AXIS, REP_AXIS)
    c_dist = symm_3d(_flat_from_sharded(st, p2), b_dist, plan, mesh3,
                     TB_AXIS, REP_AXIS)
    return collect_rows_3d_jnp(c_dist, plan, p2)


def symm_3d_packed_a(a_packed: jax.Array, b: jax.Array, c: int, p2: int,
                     mesh: Mesh, pin_b: bool = False) -> jax.Array:
    """f32 packed tril × (n1, n2) -> (n1, n2): packed scatter into the
    extended triangle blocks, shard-split over the replication axis."""
    st = ShardedTriTiles.from_packed(a_packed, b.shape[0], c)
    return symm_3d_sharded_a(st, b, p2, mesh, pin_b=pin_b)


# ---- batched stacks on the 3D wire ----------------------------------------
def syrk_3d_sharded_stacked(a: jax.Array, c: int, p2: int, mesh: Mesh
                            ) -> ShardedTriTiles:
    """f32 (k, n1, n2) -> batched ShardedTriTiles: the stack rides the
    in-slice all-to-all and the cross-slice reduce-scatter payloads."""
    _, n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2 // p2)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    flat = syrk_3d_stacked(distribute_rows_3d_stacked_jnp(a, plan, p2),
                           plan, mesh3, TB_AXIS, REP_AXIS)
    return _sharded_from_flat_stacked(flat, plan, n1, c)


def syr2k_3d_sharded_stacked(a: jax.Array, b: jax.Array, c: int, p2: int,
                             mesh: Mesh) -> ShardedTriTiles:
    _, n1, n2 = a.shape
    plan = make_2d_plan(c, n1, n2 // p2)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    flat = syr2k_3d_stacked(distribute_rows_3d_stacked_jnp(a, plan, p2),
                            distribute_rows_3d_stacked_jnp(b, plan, p2),
                            plan, mesh3, TB_AXIS, REP_AXIS)
    return _sharded_from_flat_stacked(flat, plan, n1, c)


def symm_3d_packed_a_stacked(a_packed: jax.Array, b: jax.Array, c: int,
                             p2: int, mesh: Mesh) -> jax.Array:
    """f32 (k, tril_size(n1)) × (k, n1, n2) -> (k, n1, n2)."""
    _, n1, n2 = b.shape
    st = ShardedTriTiles.from_packed(a_packed, n1, c)
    plan = make_2d_plan(c, n1, n2 // p2)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    c_dist = symm_3d_stacked(_flat_from_sharded_stacked(st, p2),
                             distribute_rows_3d_stacked_jnp(b, plan, p2),
                             plan, mesh3, TB_AXIS, REP_AXIS)
    return jax.vmap(lambda d: collect_rows_3d_jnp(d, plan, p2))(
        c_dist.transpose(2, 0, 1, 3, 4, 5))


def syrk_3d_dense(a: jax.Array, c: int, p2: int, mesh: Mesh) -> jax.Array:
    return syrk_3d_sharded(a, c, p2, mesh).to_tril()


def syr2k_3d_dense(a: jax.Array, b: jax.Array, c: int, p2: int, mesh: Mesh
                   ) -> jax.Array:
    return syr2k_3d_sharded(a, b, c, p2, mesh).to_tril()


def symm_3d_dense(a_sym: jax.Array, b: jax.Array, c: int, p2: int,
                  mesh: Mesh, pin_b: bool = False) -> jax.Array:
    return symm_3d_packed_a(pack_tril(jnp.tril(a_sym)), b, c, p2, mesh,
                            pin_b=pin_b)


# --------------------------------------------------------------------------
# 3D limited-memory paths (Algs 16–18, §IX): streamed b-column chunks
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
    replication axis, b-column chunks within each, 2D row-share layout
    per chunk (n2 % p2 == 0 required)."""
    n1, n2 = x.shape
    b = plan_b.n2
    n2s = n2 // p2
    xs = x.reshape(n1, p2, n2s).transpose(1, 0, 2)        # (p2, n1, n2s)
    xs = jnp.pad(xs, ((0, 0), (0, 0), (0, nsteps * b - n2s)))
    xc = xs.reshape(p2, n1, nsteps, b).transpose(0, 2, 1, 3)
    dist = jax.vmap(jax.vmap(
        lambda s: distribute_rows_jnp(s, plan_b)))(xc)
    return dist.transpose(2, 0, 1, 3, 4, 5)               # (p1, p2, ...)


def _collect_cols_3d_jnp(c_dist: jax.Array, plan_b: TwoDPlan, p2: int,
                         n2: int) -> jax.Array:
    """Inverse of :func:`_chunk_cols_3d_jnp` for the SYMM output
    (drops the zero-padded tail columns)."""
    per = jax.vmap(jax.vmap(
        lambda d: collect_rows_jnp(d, plan_b)))(
        c_dist.transpose(1, 2, 0, 3, 4, 5))               # (p2, ns, n1, b)
    n1 = per.shape[-2]
    n2s = n2 // p2
    per = per.transpose(0, 2, 1, 3).reshape(p2, n1, -1)[:, :, :n2s]
    return per.transpose(1, 0, 2).reshape(n1, n2)


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


def symm_3d_limited_sharded_a(st: ShardedTriTiles, b: jax.Array, p2: int,
                              chunk: int, mesh: Mesh, pin_b: bool = False
                              ) -> jax.Array:
    """Alg 18: gather A's triangle blocks once, stream B/C chunks."""
    n1, n2 = st.n, b.shape[1]
    c = st.c
    bw, nsteps = _limited_steps(n2, p2, chunk)
    plan_b = make_2d_plan(c, n1, bw)
    mesh3 = _mesh_3d(mesh, c * (c + 1), p2)
    b_dist = _chunk_cols_3d_jnp(b, plan_b, p2, nsteps)
    if pin_b:
        b_dist = _pin_row_shards(b_dist, mesh3, TB_AXIS, REP_AXIS)
    c_dist = symm_3d_limited(_flat_from_sharded(st, p2), b_dist,
                             plan_b, mesh3, TB_AXIS, REP_AXIS)
    return _collect_cols_3d_jnp(c_dist, plan_b, p2, n2)


def symm_3d_limited_packed_a(a_packed: jax.Array, b: jax.Array, c: int,
                             p2: int, chunk: int, mesh: Mesh,
                             pin_b: bool = False) -> jax.Array:
    st = ShardedTriTiles.from_packed(a_packed, b.shape[0], c)
    return symm_3d_limited_sharded_a(st, b, p2, chunk, mesh, pin_b=pin_b)


def syrk_3d_limited_dense(a: jax.Array, c: int, p2: int, chunk: int,
                          mesh: Mesh) -> jax.Array:
    return syrk_3d_limited_sharded(a, c, p2, chunk, mesh).to_tril()


def syr2k_3d_limited_dense(a: jax.Array, b: jax.Array, c: int, p2: int,
                           chunk: int, mesh: Mesh) -> jax.Array:
    return syr2k_3d_limited_sharded(a, b, c, p2, chunk, mesh).to_tril()


def symm_3d_limited_dense(a_sym: jax.Array, b: jax.Array, c: int, p2: int,
                          chunk: int, mesh: Mesh, pin_b: bool = False
                          ) -> jax.Array:
    return symm_3d_limited_packed_a(pack_tril(jnp.tril(a_sym)), b, c, p2,
                                    chunk, mesh, pin_b=pin_b)

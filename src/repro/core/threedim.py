"""3D and limited-memory parallel SYRK / SYR2K / SYMM (paper Algs 13–18).

Optimal regime (Thm 9 case 3, large P): processor grid p₁ × p₂ with
p₁ = c(c+1); the 2D algorithm runs inside each p₂-slice on n₂/p₂ columns,
then the symmetric matrix is reduce-scattered (SYRK/SYR2K) or all-gathered
(SYMM) across the replication axis — total bandwidth eq. (7):
m·n₁n₂/(√p₁·p₂) + n₁²/(2p₁).

Limited-memory variants (Algs 16–18, §IX) stream the non-symmetric columns
in chunks of b via ``lax.scan``, trading latency for a working set of
m·b·n₁/c + n₁²/(2p₁) — matching the memory-dependent bound (Cor 6–8) when
p₂ = x = 2MP/n₁² (up to the owned-data term).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .twodim import (TwoDPlan, symm_2d_local, syr2k_2d_local, syrk_2d_local,
                     tb_flat_words)


# --------------------------------------------------------------------------
# local bodies (inside shard_map over axes (tb, rep))
# --------------------------------------------------------------------------
# The stack of K matrices rides the in-slice all-to-all and the
# cross-slice reduce-scatter / all-gather as extra payload dims, as on
# the 2D wire (core/twodim.py); an unbatched call is K = 1.
def _flatten_tb(off: jax.Array, diag: jax.Array) -> jax.Array:
    """(…, T, nb, nb) + (…, nb, nb) -> (…, (T+1)·nb²)."""
    lead = diag.shape[:-2]
    return jnp.concatenate([off.reshape(lead + (-1,)),
                            diag.reshape(lead + (-1,))], -1)


def _unflatten_tb(flat: jax.Array, plan: TwoDPlan
                  ) -> Tuple[jax.Array, jax.Array]:
    """Inverse of :func:`_flatten_tb` (drops the shard padding)."""
    lead = flat.shape[:-1]
    t = plan.T * plan.nb * plan.nb
    off = flat[..., :t].reshape(lead + (plan.T, plan.nb, plan.nb))
    diag = flat[..., t:t + plan.nb * plan.nb].reshape(
        lead + (plan.nb, plan.nb))
    return off, diag


def _pad_to(x: jax.Array, mult: int) -> jax.Array:
    """Zero-pad the last axis to a multiple of ``mult``."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, -x.shape[-1] % mult)])


def _varying(x: jax.Array, axes: Tuple[str, ...]) -> jax.Array:
    """Mark a constant as varying over manual axes (scan-carry vma rule)."""
    return jax.lax.pcast(x, axes, to="varying")


def syrk_3d_local(a_own: jax.Array, plan: TwoDPlan, tb_axis: str,
                  rep_axis: str, p2: int) -> jax.Array:
    """Alg 13: 2D SYRK in-slice + reduce-scatter of the extended triangle
    block over the replication axis.  a_own: (K, c, nb, w₂) with
    w₂ = n₂/(p₂(c+1)).  Returns this device's flat shards of C_Tk
    (K, shard)."""
    off, diag = syrk_2d_local(a_own, plan, tb_axis)
    return jax.lax.psum_scatter(_pad_to(_flatten_tb(off, diag), p2),
                                rep_axis, scatter_dimension=1, tiled=True)


def syr2k_3d_local(a_own: jax.Array, b_own: jax.Array, plan: TwoDPlan,
                   tb_axis: str, rep_axis: str, p2: int) -> jax.Array:
    """Alg 14, stacked as :func:`syrk_3d_local`."""
    off, diag = syr2k_2d_local(a_own, b_own, plan, tb_axis)
    return jax.lax.psum_scatter(_pad_to(_flatten_tb(off, diag), p2),
                                rep_axis, scatter_dimension=1, tiled=True)


def symm_3d_local(a_flat_shard: jax.Array, b_own: jax.Array, plan: TwoDPlan,
                  tb_axis: str, rep_axis: str) -> jax.Array:
    """Alg 15: all-gather A_Tk over the replication axis, then 2D SYMM
    in-slice.  a_flat_shard (K, shard): this device's 1/p₂ of the
    flattened extended triangle blocks of A; b_own (K, c, nb, w₂).
    Returns C shares (K, c, nb, w₂)."""
    flat = jax.lax.all_gather(a_flat_shard, rep_axis, axis=1, tiled=True)
    a_off, a_diag = _unflatten_tb(flat, plan)
    return symm_2d_local(a_off, a_diag, b_own, plan, tb_axis)


# ---- limited-memory variants (Algs 16–18), unbatched ----------------------
# Each streamed chunk runs the in-slice 2D schedule as a stack of one.
def _zero_tb(plan: TwoDPlan, dtype, axes: Tuple[str, ...]
             ) -> Tuple[jax.Array, jax.Array]:
    """The owned extended triangle block (off, diag), zeroed — the scan
    carry of the streamed Algs 16/17.  Its T·nb² + nb² words are the
    resident x·n₁²/(2P) term of the §IX tradeoff, independent of n₂."""
    zeros = lambda s: _varying(jnp.zeros(s, dtype), axes)
    return (zeros((plan.T, plan.nb, plan.nb)), zeros((plan.nb, plan.nb)))


def syrk_3d_limited_local(a_own_chunks: jax.Array, plan: TwoDPlan,
                          tb_axis: str, rep_axis: str, p2: int) -> jax.Array:
    """Alg 16: a_own_chunks (nsteps, c, nb, bw) — b-column chunks streamed
    through a lax.scan, each step's 2D rank update accumulated into the
    owned extended triangle block; one reduce-scatter at the end."""
    def step(acc, chunk):
        off, diag = syrk_2d_local(chunk[None], plan, tb_axis)
        return (acc[0] + off[0], acc[1] + diag[0]), None

    acc0 = _zero_tb(plan, a_own_chunks.dtype, (tb_axis, rep_axis))
    (off, diag), _ = jax.lax.scan(step, acc0, a_own_chunks)
    return jax.lax.psum_scatter(_pad_to(_flatten_tb(off, diag), p2),
                                rep_axis, scatter_dimension=0, tiled=True)


def syr2k_3d_limited_local(a_own_chunks: jax.Array, b_own_chunks: jax.Array,
                           plan: TwoDPlan, tb_axis: str, rep_axis: str,
                           p2: int) -> jax.Array:
    """Alg 17: like Alg 16 with the symmetrized two-sided update."""
    def step(acc, ab):
        off, diag = syr2k_2d_local(ab[0][None], ab[1][None], plan, tb_axis)
        return (acc[0] + off[0], acc[1] + diag[0]), None

    acc0 = _zero_tb(plan, a_own_chunks.dtype, (tb_axis, rep_axis))
    (off, diag), _ = jax.lax.scan(step, acc0,
                                  (a_own_chunks, b_own_chunks))
    return jax.lax.psum_scatter(_pad_to(_flatten_tb(off, diag), p2),
                                rep_axis, scatter_dimension=0, tiled=True)


def symm_3d_limited_local(a_flat_shard: jax.Array, b_own_chunks: jax.Array,
                          plan: TwoDPlan, tb_axis: str, rep_axis: str
                          ) -> jax.Array:
    """Alg 18: gather A once, stream B/C chunks."""
    flat = jax.lax.all_gather(a_flat_shard, rep_axis, axis=0, tiled=True)
    a_off, a_diag = _unflatten_tb(flat, plan)

    def step(_, chunk):
        return None, symm_2d_local(a_off[None], a_diag[None], chunk[None],
                                   plan, tb_axis)[0]

    _, c_chunks = jax.lax.scan(step, None, b_own_chunks)
    return c_chunks  # (nsteps, c, nb, bw)


# --------------------------------------------------------------------------
# full-array wrappers over a 2-axis mesh
# --------------------------------------------------------------------------
def _grid_map(local, n_in: int, mesh, tb_axis: str, rep_axis: str):
    """shard_map a per-device body over the (tb, rep) grid: every input
    and the output carry the two device axes first."""
    def body(*xs):                     # xs: (1, 1, …) per device
        return local(*(x[0, 0] for x in xs))[None, None]

    spec = P(tb_axis, rep_axis)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(spec,) * n_in,
                                 out_specs=spec))


def syrk_3d(a_dist: jax.Array, plan: TwoDPlan, mesh, tb_axis: str = "tb",
            rep_axis: str = "rep") -> jax.Array:
    """a_dist global (p1, p2, K, c, nb, w2) sharded P(tb, rep) ->
    (p1, p2, K, shard)."""
    f = functools.partial(syrk_3d_local, plan=plan, tb_axis=tb_axis,
                          rep_axis=rep_axis, p2=mesh.shape[rep_axis])
    return _grid_map(f, 1, mesh, tb_axis, rep_axis)(a_dist)


def syr2k_3d(a_dist, b_dist, plan: TwoDPlan, mesh, tb_axis="tb",
             rep_axis="rep"):
    f = functools.partial(syr2k_3d_local, plan=plan, tb_axis=tb_axis,
                          rep_axis=rep_axis, p2=mesh.shape[rep_axis])
    return _grid_map(f, 2, mesh, tb_axis, rep_axis)(a_dist, b_dist)


def symm_3d(a_flat, b_dist, plan: TwoDPlan, mesh, tb_axis="tb",
            rep_axis="rep"):
    """a_flat global (p1, p2, K, shard) sharded P(tb, rep);
    b_dist global (p1, p2, K, c, nb, w2)."""
    f = functools.partial(symm_3d_local, plan=plan, tb_axis=tb_axis,
                          rep_axis=rep_axis)
    return _grid_map(f, 2, mesh, tb_axis, rep_axis)(a_flat, b_dist)


def syrk_3d_limited(a_chunks: jax.Array, plan: TwoDPlan, mesh,
                    tb_axis: str = "tb", rep_axis: str = "rep") -> jax.Array:
    """a_chunks global (p1, p2, nsteps, c, nb, bw) sharded P(tb, rep);
    plan is the per-chunk 2D plan (n₂ = b).  Returns (p1, p2, shard)."""
    f = functools.partial(syrk_3d_limited_local, plan=plan, tb_axis=tb_axis,
                          rep_axis=rep_axis, p2=mesh.shape[rep_axis])
    return _grid_map(f, 1, mesh, tb_axis, rep_axis)(a_chunks)


def syr2k_3d_limited(a_chunks, b_chunks, plan: TwoDPlan, mesh,
                     tb_axis="tb", rep_axis="rep"):
    f = functools.partial(syr2k_3d_limited_local, plan=plan, tb_axis=tb_axis,
                          rep_axis=rep_axis, p2=mesh.shape[rep_axis])
    return _grid_map(f, 2, mesh, tb_axis, rep_axis)(a_chunks, b_chunks)


def symm_3d_limited(a_flat, b_chunks, plan: TwoDPlan, mesh,
                    tb_axis="tb", rep_axis="rep"):
    """a_flat global (p1, p2, shard) sharded P(tb, rep);
    b_chunks global (p1, p2, nsteps, c, nb, bw).  Returns the C chunks
    in the same (p1, p2, nsteps, c, nb, bw) layout."""
    f = functools.partial(symm_3d_limited_local, plan=plan, tb_axis=tb_axis,
                          rep_axis=rep_axis)
    return _grid_map(f, 2, mesh, tb_axis, rep_axis)(a_flat, b_chunks)


def flat_tb_size(plan: TwoDPlan) -> int:
    """Words of one flattened extended triangle block (off ‖ diag) —
    the shared layout of the 3D flat shards and the packed mesh wire."""
    return tb_flat_words(plan.c, plan.n1)

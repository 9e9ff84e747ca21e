"""Int8 gradient compression with error feedback — packed-native for
symmetric state.

Synchronous DP all-reduces move 4 bytes/param/step (f32 master grads).
Block-wise int8 with per-block scales moves ~1.03 bytes/param — a 3.9×
wire saving — and error feedback (Seide et al.; Karimireddy et al.)
carries the quantization residual into the next step so SGD/Adam
trajectories stay unbiased to first order.

Symmetric accumulator gradients (Gram-EMA, Muon stats, the
``decorrelation_penalty`` cotangents) are redundant on the wire: the
same communication-avoiding argument as the packed collectives (arXiv
2409.11304) says move only the n(n+1)/2 lower triangle.  Two packed
paths implement that:

  * :class:`ErrorFeedbackInt8` with ``sym_mask`` — masked dense
    symmetric leaves quantize (and keep their EF residual) in
    element-packed layout, halving both wire words and residual memory;
    the diagonal rides in the packed vector once, so no double-count
    correction is needed.  Typed packed leaves
    (:class:`~repro.core.packing.PackedTriangle` etc.) flatten to their
    packed component arrays and are therefore packed-on-the-wire with
    no mask at all.
  * :func:`compressed_allreduce_sym` — the explicit collective for a
    symmetric n×n (or already-packed) array: pack → int8 mean-reduce →
    symmetric unpack.

Two integration points:

  * :class:`ErrorFeedbackInt8` — a pure-jax gradient transform inserted
    before the optimizer update (what launch/train.py uses).  Under
    GSPMD the transform runs *after* the implicit psum, modelling
    end-to-end numerics of a compressed pipeline.
  * :func:`compressed_allreduce` — the explicit shard_map collective:
    quantize the LOCAL shard → int8 all-to-all (reduce-scatter
    pattern) → dequant-sum → requant → int8 all-gather.  Wire bytes
    per device: 2·(P-1)/P·n·(1+4/block) vs 2·(P-1)/P·n·4 uncompressed
    (:func:`wire_bytes_per_device` is this exact model).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.packing import PackedTriangle, pack_tril, tril_size, unpack_tril


def _pad_to(x: jax.Array, block: int) -> Tuple[jax.Array, int]:
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat, pad


def quantize_int8(x: jax.Array, block: int = 256
                  ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric block-wise int8: returns (q[int8, padded], scale[f32])."""
    flat, _ = _pad_to(x, block)
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array, shape, dtype=jnp.float32
                    ) -> jax.Array:
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    n = 1
    for d in shape:
        n *= d
    return flat[:n].reshape(shape).astype(dtype)


class EFState(NamedTuple):
    error: Any                     # residual pytree, f32; packed for
    #                                sym-masked leaves (tril_size(n),)


class ErrorFeedbackInt8:
    """grads -> (decompressed grads, new EF state).

    ``sym_mask`` (optional) is a pytree of bools matching the grads
    structure: True marks a dense symmetric (…, n, n) leaf whose wire
    form is the element-packed lower triangle — n(n+1)/2 words
    quantized instead of n², and the EF residual is stored packed too
    (half the accumulator memory).  Dequantized grads come back dense
    symmetric, so the optimizer update is unchanged.  Leaves that are
    already packed types (``PackedTriangle``; ``TriTiles`` /
    ``ShardedTriTiles`` state) flatten to packed component arrays and
    need no mask — they are packed on the wire by construction.
    """

    def __init__(self, block: int = 256, sym_mask: Any = None):
        self.block = block
        self.sym_mask = sym_mask

    def _masks(self, treedef, nleaves: int):
        if self.sym_mask is None:
            return [False] * nleaves
        flat_m = jax.tree_util.tree_leaves(self.sym_mask)
        if len(flat_m) != nleaves:
            raise ValueError(
                f"sym_mask has {len(flat_m)} leaves, grads have {nleaves}")
        return [bool(m) for m in flat_m]

    def init(self, params: Any) -> EFState:
        flat, treedef = jax.tree_util.tree_flatten(params)
        masks = self._masks(treedef, len(flat))

        def zero(p, sym):
            if sym:
                n = p.shape[-1]
                if p.shape[-2:] != (n, n):
                    raise ValueError(
                        f"sym-masked leaf must be (…, n, n), got {p.shape}")
                return jnp.zeros(p.shape[:-2] + (tril_size(n),),
                                 jnp.float32)
            return jnp.zeros(p.shape, jnp.float32)

        return EFState(error=jax.tree_util.tree_unflatten(
            treedef, [zero(p, m) for p, m in zip(flat, masks)]))

    def compress(self, grads: Any, state: EFState
                 ) -> Tuple[Any, EFState]:
        def one(g, e, sym):
            if sym:
                n = g.shape[-1]
                corrected = pack_tril(g.astype(jnp.float32)) + e
            else:
                corrected = g.astype(jnp.float32) + e
            q, s = quantize_int8(corrected, self.block)
            deq = dequantize_int8(q, s, corrected.shape)
            if sym:
                out = unpack_tril(deq, n, symmetric=True).astype(g.dtype)
            else:
                out = deq.astype(g.dtype)
            return out, corrected - deq

        flat_g, treedef = jax.tree_util.tree_flatten(grads)
        flat_e = jax.tree_util.tree_leaves(state.error)
        masks = self._masks(treedef, len(flat_g))
        outs = [one(g, e, m) for g, e, m in zip(flat_g, flat_e, masks)]
        new_g = jax.tree_util.tree_unflatten(treedef,
                                             [o[0] for o in outs])
        new_e = jax.tree_util.tree_unflatten(treedef,
                                             [o[1] for o in outs])
        return new_g, EFState(error=new_e)


def compressed_allreduce(x: jax.Array, mesh, axis: str = "data",
                         block: int = 256) -> jax.Array:
    """Mean of ``x`` over ``axis`` moving int8 on the wire.

    Each device quantizes ITS OWN shard (the input is laid out with one
    replica per device along ``axis``), then: reduce-scatter in int8 →
    local dequant-sum (f32) → requant → all-gather in int8.  Matches
    jnp.mean over the axis to ~1e-2 rel, and moves exactly what
    :func:`wire_bytes_per_device` accounts: per device,
    (P-1)/P·n·(1+4/block) bytes out in the all-to-all plus the same
    again in the all-gather.
    """
    naxis = mesh.shape[axis]

    def inner(xs):
        # xs: (1, nb, block) — this device's replica.  Quantization is
        # genuinely per-shard: only the local copy is seen here.
        q, s = quantize_int8(xs[0], block)
        # reduce-scatter: each device receives the others' quantized
        # copies of ITS 1/P stripe and sums after dequant.
        nb = q.shape[0]
        stripe = nb // naxis
        qs = q.reshape(naxis, stripe, block)
        ss = s.reshape(naxis, stripe, 1)
        qs = jax.lax.all_to_all(qs, axis, split_axis=0, concat_axis=0,
                                tiled=False)
        ss = jax.lax.all_to_all(ss, axis, split_axis=0, concat_axis=0,
                                tiled=False)
        part = jnp.sum(qs.astype(jnp.float32) * ss, axis=0) / naxis
        # requant the reduced stripe and all-gather it
        q2, s2 = quantize_int8(part, block)
        q2 = jax.lax.all_gather(q2.reshape(stripe, block), axis, axis=0,
                                tiled=False).reshape(nb, block)
        s2 = jax.lax.all_gather(s2, axis, axis=0,
                                tiled=False).reshape(nb, 1)
        return (q2.astype(jnp.float32) * s2)[None]

    flat, pad = _pad_to(x, block)
    nb = flat.shape[0] // block
    # pad so the block count divides the axis
    extra = (-nb) % naxis
    if extra:
        flat = jnp.concatenate(
            [flat, jnp.zeros(extra * block, flat.dtype)])
    blocks = flat.reshape(-1, block)
    # one replica per device along the mesh axis; the block axis is what
    # the in_specs shard, so quantization inside is per-shard (the old
    # in_specs=P() route replicated the input and every device
    # re-quantized the whole array).
    stack = jnp.broadcast_to(blocks[None], (naxis,) + blocks.shape)
    out = jax.shard_map(inner, mesh=mesh, in_specs=P(axis),
                        out_specs=P(axis), check_vma=False)(stack)
    n = 1
    for d in x.shape:
        n *= d
    return out[0].reshape(-1)[:n].reshape(x.shape).astype(x.dtype)


def compressed_allreduce_sym(x, mesh, axis: str = "data",
                             block: int = 256):
    """Packed-symmetric :func:`compressed_allreduce`.

    A dense symmetric (n, n) array moves as its n(n+1)/2-element packed
    lower triangle — half the blocks on the DP wire — and comes back
    dense symmetric (mirrored from the reduced triangle, so symmetry is
    exact by construction).  A :class:`PackedTriangle` input stays
    packed end to end.  The diagonal is carried once inside the packed
    vector; because pack/unpack are bijective on the triangle, no
    double-count rescale is needed (same algebra as the ``_diag_scale``
    fused SYRK cotangent path, which folds the mirror into the packed
    update instead of densifying).
    """
    if isinstance(x, PackedTriangle):
        v = compressed_allreduce(x.vec, mesh, axis, block)
        return PackedTriangle(v.astype(x.vec.dtype), x.n)
    n = x.shape[-1]
    if x.shape[-2:] != (n, n):
        raise ValueError(f"expected symmetric (…, n, n), got {x.shape}")
    v = compressed_allreduce(pack_tril(x), mesh, axis, block)
    return unpack_tril(v, n, symmetric=True).astype(x.dtype)


def wire_bytes_per_device(n_params: int, p: int, *, compressed: bool,
                          block: int = 256, sym_n: Optional[int] = None
                          ) -> float:
    """Ring-model wire bytes for one DP gradient reduction.

    Matches :func:`compressed_allreduce` exactly: the all-to-all leg
    moves (P-1)/P of the local int8 blocks + f32 scales, the all-gather
    leg moves the same again — 2·(P-1)/P·n·(1+4/block) bytes.  With
    ``sym_n`` set, ``n_params`` counts a dense symmetric n×n leaf and
    the packed wire (``compressed_allreduce_sym`` / sym-masked EF)
    moves only its tril_size(n) triangle.
    """
    if sym_n is not None:
        full = sym_n * sym_n
        n_params = (n_params // full) * tril_size(sym_n)
    pf = 2.0 * (p - 1) / p
    per_param = (1.0 + 4.0 / block) if compressed else 4.0
    return pf * n_params * per_param

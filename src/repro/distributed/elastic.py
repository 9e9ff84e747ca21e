"""Elastic scaling: re-plan the mesh for whatever devices survive and
re-shard the training state onto it — including packed symmetric state.

Recovery story at scale: a pod loses hosts -> the job restarts with a
smaller world -> ``plan_mesh(len(jax.devices()))`` picks the best
(data, model) factorization -> ``restore_checkpoint`` +
``reshard_tree`` place the saved logical arrays on the new mesh.  No
state is keyed to device ids, so shrink and grow are symmetric.

Packed symmetric state (:class:`~repro.core.packing.ShardedTriTiles`
extended triangle blocks, :class:`~repro.core.packing.TriTiles`,
:class:`~repro.core.packing.PackedTriangle`) re-shards through the
block-granular element↔(device,slot) bijection
(:func:`~repro.core.twodim.tb_block_tables`): a P = c(c+1) wire moves
to P′ = c′(c′+1) by gathering each old shard into the element-packed
triangle and scattering it into the new shards — ~n²/2 words moved
once, never a dense n×n intermediate (``reshard_tritiles`` is
jaxpr-asserted dense-free in the persist suite).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..compat import make_mesh
from ..core.dispatch import fit_c_grid
from ..core.packing import PackedTriangle, ShardedTriTiles, TriTiles

_PACKED_TYPES = (TriTiles, ShardedTriTiles, PackedTriangle)


def _is_packed_leaf(x) -> bool:
    return isinstance(x, _PACKED_TYPES)


def plan_shape(n_devices: int, *, max_model: int = 16,
               model_divides: Optional[int] = None) -> Tuple[int, int]:
    """Pick (data, model) for ``n_devices`` — pure, device-free.

    Prefers the largest model axis ≤ max_model that divides n_devices
    (and divides ``model_divides`` — e.g. n_heads or d_ff — when given),
    maximizing TP while keeping DP ≥ 1.  Deterministic, so every
    surviving host computes the same mesh independently.
    """
    best = 1
    for m in range(1, min(max_model, n_devices) + 1):
        if n_devices % m:
            continue
        if model_divides is not None and model_divides % m:
            continue
        best = m
    return n_devices // best, best


def plan_mesh(n_devices: Optional[int] = None, *, max_model: int = 16,
              model_divides: Optional[int] = None):
    """Instantiate the planned mesh over the live devices."""
    if n_devices is None:
        n_devices = jax.device_count()
    data, model = plan_shape(n_devices, max_model=max_model,
                             model_divides=model_divides)
    return make_mesh((data, model), ("data", "model"))


def wire_c(n_devices: Optional[int] = None) -> int:
    """The triangle-block wire parameter for a world of ``n_devices``:
    largest c with P = c(c+1) ≤ n_devices (0 when no wire fits).  Pure
    and deterministic, so — like :func:`plan_shape` — every surviving
    host computes the same c′ after an elastic restart."""
    if n_devices is None:
        n_devices = jax.device_count()
    return fit_c_grid(n_devices)


def reshard_tritiles(st: ShardedTriTiles, c_new: int) -> ShardedTriTiles:
    """Re-shard a P = c(c+1) extended-triangle-block wire onto
    P′ = c′(c′+1) devices.

    Both directions of the remap are the block-granular converters over
    the :func:`~repro.core.twodim.tb_block_tables` bijection: old
    (device, slot) → element-packed triangle → new (device, slot).  The
    packed vector (~n²/2 words) is the only intermediate — no dense
    n×n is ever materialized (asserted on this function's jaxpr by
    ``dist_checks --suite persist``) — and the remap is bit-exact in
    any dtype (pure data movement, no arithmetic).
    """
    if c_new == st.c:
        return st
    if c_new < 1:
        raise ValueError(f"no triangle wire fits c_new={c_new}")
    return ShardedTriTiles.from_packed(st.to_packed(), st.n, c_new)


def reshard_packed_state(tree: Any, n_devices: Optional[int] = None, *,
                         c: Optional[int] = None) -> Any:
    """Walk ``tree`` and re-shard every :class:`ShardedTriTiles` leaf
    onto the wire of the new world (``c`` explicit, or
    ``wire_c(n_devices)``).  TriTiles / PackedTriangle / plain leaves
    are device-count-independent and pass through unchanged."""
    c_new = wire_c(n_devices) if c is None else c

    def one(x):
        if isinstance(x, ShardedTriTiles):
            return reshard_tritiles(x, c_new)
        return x

    return jax.tree.map(one, tree, is_leaf=_is_packed_leaf)


def reshard_tree(tree: Any, specs: Any, mesh) -> Any:
    """Place every leaf of ``tree`` per the matching PartitionSpec on
    ``mesh``.  Accepts host numpy arrays or jax Arrays from another mesh
    (elastic restore path).  Packed symmetric leaves pair with either a
    single spec (broadcast over their component arrays) or a
    same-format subtree of specs (what :func:`spec_tree_like` emits)."""
    def place(x, spec):
        if _is_packed_leaf(x) and _is_packed_leaf(spec):
            return jax.tree.map(
                lambda xx, ss: jax.device_put(xx, NamedSharding(mesh, ss)),
                x, spec)
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(place, tree, specs,
                        is_leaf=lambda x: _is_packed_leaf(x) or
                        not isinstance(x, (dict, list, tuple)))


def spec_tree_like(tree: Any, spec: P = P(), *,
                   shard_axis: Optional[str] = None) -> Any:
    """A spec tree of the same structure, all replicated (default).

    Packed-aware: a :class:`ShardedTriTiles` leaf maps to a same-format
    subtree whose ``off``/``diag`` carry ``P(shard_axis)`` on the
    leading device axis (replicated when ``shard_axis`` is None) —
    exactly what the shard_map mesh schedules consume; TriTiles /
    PackedTriangle leaves stay replicated (they are single-device
    formats)."""
    def one(x):
        if isinstance(x, ShardedTriTiles):
            s = P(shard_axis) if shard_axis is not None else spec
            return ShardedTriTiles(s, s, x.n, x.c)
        if isinstance(x, TriTiles):
            return TriTiles(spec, x.n, x.bm)
        if isinstance(x, PackedTriangle):
            return PackedTriangle(spec, x.n)
        return spec

    return jax.tree.map(one, tree, is_leaf=_is_packed_leaf)


def validate_divisibility(mesh, *, global_batch: int,
                          model_dims: Sequence[int]) -> Tuple[bool, str]:
    """Pre-flight check: batch divides the DP axes, model dims divide
    the TP axis.  Returns (ok, reason)."""
    dp = 1
    for a in ("pod", "data"):
        dp *= mesh.shape.get(a, 1)
    tp = mesh.shape.get("model", 1)
    if global_batch % dp:
        return False, f"global_batch {global_batch} % dp {dp} != 0"
    for d in model_dims:
        if d % tp:
            return False, f"model dim {d} % tp {tp} != 0"
    return True, "ok"

"""Muon optimizer with Newton–Schulz orthogonalization built on the
paper's communication-optimal SYRK + SYMM (the core integration,
DESIGN §4).

Each NS iteration of X (m × n, m ≤ n) computes

    S  = X·Xᵀ                (SYRK,  m=1 non-symmetric operand)
    X ← a·X + (b·S + c·S²)·X (SYMM chain: S², then symmetric·X)

On a (data, model) mesh with X column-sharded over 'model', the Gram is
computed with the paper's **1D SYRK** (Alg 7): local outer product +
reduce-scatter of the *packed lower triangle*, then the symmetric factor
is rebuilt with the **1D SYMM** gather of the packed triangle (Alg 9) —
together (1−1/P)·m² words per iteration versus 2·(1−1/P)·m² for the naive
full-matrix psum/all-gather: exactly the paper's factor-2 savings, visible
in the dry-run collective bytes (EXPERIMENTS §Perf).

The regime matches Thm 9 case 1 (n₁ = m ≤ m·n₂ = n, small P), where the 1D
algorithm is communication-optimal — `repro.core.dispatch.choose_algorithm`
confirms the selection for every parameter shape at setup time.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from .. import blas
from ..core.onedim import syrk_1d_local
from ..core.packing import (PackedTriangle, pack_tril, tril_size,
                            unpack_tril)

# quintic Newton–Schulz coefficients (Jordan et al., Muon)
NS_COEFFS = (3.4445, -4.7750, 2.0315)
#: the named scope of Muon's own 1d wire (:func:`orthogonalize_1d`), so
#: its collectives and local products are told apart from the model's
#: and from the ``blas.<op>.<path>`` routes in a profile
WIRE_SCOPE = "optim.muon_1d"


class MuonState(NamedTuple):
    step: jax.Array
    momentum: Any
    #: optional per-matrix Gram EMA of the momentum (packed lower
    #: triangles, m(m+1)/2 words each) — curvature telemetry that
    #: checkpoints packed; None unless ``Muon.gram_decay`` is set.
    gram: Any = None


# ---------------------------------------------------------------------------
# Newton–Schulz cores
# ---------------------------------------------------------------------------
def ns_iteration_reference(x: jax.Array, mesh: Optional[Mesh] = None,
                           axis: Optional[str] = None,
                           gram_chunk: Optional[int] = None) -> jax.Array:
    """One NS step on the unified symmetric-BLAS surface: the Gram is a
    SYRK and both symmetric products are SYMMs, so `repro.blas` routes
    each to the best path (fused jnp off-accelerator, the triangular
    flat-grid Pallas kernels on TPU, the paper's mesh schedules when
    ``mesh`` is given).  Since blas.grad the whole chain is also
    reverse-differentiable on every route — the SYRK/SYMM cotangents are
    routed SYMMs/SYR2Ks — so NS can sit inside a differentiated loss
    (meta-learning through the optimizer) without densification
    workarounds.

    ``gram_chunk``: stream the Gram over column chunks of that size
    through the SYRK beta-accumulate epilogue (``c=s, beta=1``) — for
    wide X the (m, n) slab never needs to be live all at once."""
    a, b, c = NS_COEFFS
    n = x.shape[-1]
    with jax.named_scope("ns.gram"):
        if gram_chunk is None or gram_chunk >= n:
            s = blas.syrk(x, fill="full", mesh=mesh, axis=axis)  # S = X·Xᵀ
        else:
            s = None
            for lo in range(0, n, gram_chunk):
                s = blas.syrk(x[..., lo:lo + gram_chunk], fill="full", c=s,
                              mesh=mesh, axis=axis)
    with jax.named_scope("ns.square"):
        y = b * s + c * blas.symm(s, s, mesh=mesh, axis=axis)  # S²
    with jax.named_scope("ns.apply"):
        return a * x + blas.symm(y, x, mesh=mesh, axis=axis)   # sym(Y)·X


def orthogonalize_reference(g: jax.Array, steps: int = 5,
                            mesh: Optional[Mesh] = None,
                            axis: Optional[str] = None,
                            gram_chunk: Optional[int] = None) -> jax.Array:
    """NS orthogonalization of a (..., m, n) matrix (leading stack dims
    allowed), operating on the short side; returns an approximately
    semi-orthogonal matrix per stacked slice."""
    transpose = g.shape[-2] > g.shape[-1]
    x = g.swapaxes(-1, -2) if transpose else g
    x = x.astype(jnp.float32)
    x = x / (jnp.linalg.norm(x, axis=(-2, -1), keepdims=True) + 1e-7)
    x = jax.lax.fori_loop(
        0, steps,
        lambda _, v: ns_iteration_reference(v, mesh, axis, gram_chunk), x)
    return (x.swapaxes(-1, -2) if transpose else x).astype(g.dtype)


def _ns_iteration_1d_local(x_loc: jax.Array, axis: str, n_shards: int
                           ) -> jax.Array:
    """One NS step inside shard_map: x_loc (m, n/P) column shard.

    SYRK via packed reduce-scatter (Alg 7) + packed all-gather (the Alg 9
    data path) — half the collective bytes of the naive approach."""
    a, b, c = NS_COEFFS
    m = x_loc.shape[0]
    with jax.named_scope("ns.gram"):
        packed_shard = syrk_1d_local(x_loc, axis, n_shards)  # RS: m²/2
        packed = jax.lax.all_gather(packed_shard, axis, axis=0,
                                    tiled=True)[:tril_size(m)]  # AG: m²/2
        s = unpack_tril(packed, m, diag=True, symmetric=True)  # unpack
    with jax.named_scope("ns.square"):
        y = b * s + c * (s @ s)                             # S² local (sym)
    with jax.named_scope("ns.apply"):
        return a * x_loc + y @ x_loc                        # sharded update


def _ns_iteration_1d_stacked(x_loc: jax.Array, axis: str, n_shards: int
                             ) -> jax.Array:
    """Batched NS step: x_loc (k, m, n/P).  Natively batched (no vmap —
    collective batching under shard_map is unsupported in this jax):
    one packed reduce-scatter + all-gather covers the whole stack."""
    a, b, c = NS_COEFFS
    k, m, _ = x_loc.shape
    L = tril_size(m)
    with jax.named_scope("ns.gram"):
        g = jnp.einsum("kmi,kni->kmn", x_loc, x_loc)        # local SYRK
        packed = pack_tril(g)                               # (k, L) packed
        pad = (-L) % n_shards
        if pad:
            packed = jnp.pad(packed, ((0, 0), (0, pad)))
        shard = jax.lax.psum_scatter(packed, axis, scatter_dimension=1,
                                     tiled=True)
        full = jax.lax.all_gather(shard, axis, axis=1, tiled=True)[:, :L]
        sym = unpack_tril(full, m, diag=True, symmetric=True)
    with jax.named_scope("ns.square"):
        y = b * sym + c * jnp.einsum("kmi,kin->kmn", sym, sym)
    with jax.named_scope("ns.apply"):
        return a * x_loc + jnp.einsum("kmi,kin->kmn", y, x_loc)


def orthogonalize_1d(g: jax.Array, mesh: Mesh, axis: str = "model",
                     steps: int = 5) -> jax.Array:
    """Distributed NS orthogonalization with the comm-optimal 1D algorithms.

    ``g``: (m, n) or stacked (..., m, n) with the orientation m <= n;
    n must divide by |axis|.  Stacked leading dims (scan periods /
    experts) are vmapped INSIDE the shard_map body, so a single pass of
    collectives covers the whole stack."""
    nsh = mesh.shape[axis]
    stacked = g.ndim > 2

    def one(x_loc):
        x_loc = x_loc.astype(jnp.float32)
        nrm = jnp.sqrt(jax.lax.psum(jnp.sum(jnp.square(x_loc)), axis)) + 1e-7
        x_loc = x_loc / nrm
        x_loc = jax.lax.fori_loop(
            0, steps,
            lambda _, v: _ns_iteration_1d_local(v, axis, nsh), x_loc)
        return x_loc.astype(g.dtype)

    def one_stacked(x_loc):
        x_loc = x_loc.astype(jnp.float32)
        sq = jax.lax.psum(jnp.sum(jnp.square(x_loc), axis=(-1, -2)), axis)
        x_loc = x_loc / (jnp.sqrt(sq)[:, None, None] + 1e-7)
        x_loc = jax.lax.fori_loop(
            0, steps,
            lambda _, v: _ns_iteration_1d_stacked(v, axis, nsh), x_loc)
        return x_loc.astype(g.dtype)

    def body(x_loc):
        if stacked:
            flat = x_loc.reshape((-1,) + x_loc.shape[-2:])
            return one_stacked(flat).reshape(x_loc.shape)
        return one(x_loc)

    spec = P(*([None] * (g.ndim - 1) + [axis]))
    fn = jax.shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)
    with jax.named_scope(WIRE_SCOPE):
        return fn(g)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def ns_scope(path) -> str:
    """``optim.muon.ns.<leaf>``: the named scope of one matrix leaf's NS
    chain, the leaf's key path as a dotted name
    (``optim.muon.ns.periods.b0.mlp.wi``)."""
    return "optim.muon.ns." + jax.tree_util.keystr(path, simple=True,
                                                   separator=".")


def _is_matrix(p: jax.Array) -> bool:
    """Muon applies to true 2D weight matrices (≤1D norms and biases
    take the fallback); stacked 3D params orthogonalize per trailing 2D
    slice."""
    return p.ndim >= 2 and min(p.shape[-2:]) >= 8


@dataclass(frozen=True)
class Muon:
    """Momentum + NS orthogonalization for matrix params, AdamW-style
    fallback for the rest.

    mode: 'syrk-1d' = paper's comm-optimal kernels inside shard_map;
          'reference' = plain jnp NS (baseline for the §Perf comparison).
    """
    lr: float = 2e-2
    momentum: float = 0.95
    ns_steps: int = 5
    weight_decay: float = 0.0
    mode: str = "reference"
    mesh: Optional[Mesh] = None
    axis: str = "model"
    fallback_lr: float = 3e-4
    #: stream NS Grams over column chunks of this size via the SYRK
    #: beta-accumulate epilogue (None = one-shot)
    gram_chunk: Optional[int] = None
    #: EMA decay for a packed momentum-Gram per 2D matrix param
    #: (curvature telemetry; ``MuonState.gram``).  The Gram is the
    #: short-side ``blas.syrk(fill="packed")`` — m(m+1)/2 words of
    #: state, never densified; None disables tracking.
    gram_decay: Optional[float] = None

    def _gram_zero(self, p: jax.Array):
        if _is_matrix(p) and p.ndim == 2:
            m = min(p.shape)
            return PackedTriangle(jnp.zeros((tril_size(m),), jnp.float32),
                                  m)
        return jnp.zeros((0,), jnp.float32)   # structure placeholder

    def init(self, params: Any) -> MuonState:
        gram = None
        if self.gram_decay is not None:
            gram = jax.tree.map(self._gram_zero, params)
        return MuonState(
            step=jnp.zeros((), jnp.int32),
            momentum=jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params),
            gram=gram)

    def _use_1d(self, n1: int, n2: int) -> bool:
        """The paper's regime selection (Thm 9 / §VIII-D): the packed
        1D algorithm is communication-optimal only in case 1
        (n1 ≤ n2 and P ≤ n2/√(n1(n1−1))).  Outside it — e.g. square
        LLM weight matrices on a 16-way axis — replicating the NS
        symmetric chain costs more than it saves (measured on
        granite-20b: 55× flops, 1.6× wire — EXPERIMENTS §Perf cell 3),
        so we fall back to the GSPMD-sharded reference."""
        from ..core.dispatch import choose_algorithm
        P_ = self.mesh.shape[self.axis]
        return choose_algorithm(n1, n2, P_, m=1).case == 1

    def _orthogonalize(self, m2: jax.Array) -> jax.Array:
        """m2: (..., m, n) f32 momentum matrix (stack dims allowed)."""
        if self.mode == "syrk-1d" and self.mesh is not None:
            transpose = m2.shape[-2] > m2.shape[-1]
            x = m2.swapaxes(-1, -2) if transpose else m2
            if x.shape[-1] % self.mesh.shape[self.axis] == 0 \
                    and self._use_1d(x.shape[-2], x.shape[-1]):
                out = orthogonalize_1d(x, self.mesh, self.axis,
                                       self.ns_steps)
                return out.swapaxes(-1, -2) if transpose else out
        mesh, axis = None, None
        if self.mesh is not None and self.axis in self.mesh.shape:
            # reference mode on a mesh: let the blas router pick the
            # comm-optimal schedule per (shape, P) instead of a manual
            # shard_map — forward and (custom-VJP) backward both routed;
            # stacked params ride the batch-native mesh wires (a Pallas
            # kernel cannot be partitioned by GSPMD, so a meshless call
            # inside a multi-device step would not compile on TPU)
            mesh, axis = self.mesh, self.axis
        return orthogonalize_reference(m2, self.ns_steps, mesh, axis,
                                       gram_chunk=self.gram_chunk)

    @jax.named_scope("optim.muon")
    def update(self, grads: Any, state: MuonState, params: Any,
               lr_scale: jax.Array = 1.0) -> Tuple[Any, MuonState]:
        step = state.step + 1
        mom = jax.tree.map(
            lambda mm, g: self.momentum * mm + g.astype(jnp.float32),
            state.momentum, grads)

        def upd(path, p, mm):
            if _is_matrix(p):
                with jax.named_scope(ns_scope(path)):
                    o = self._orthogonalize(mm)
                scale = jnp.sqrt(jnp.maximum(1.0, p.shape[-2] / p.shape[-1]))
                delta = o * scale + self.weight_decay * p.astype(jnp.float32)
                return (p.astype(jnp.float32)
                        - self.lr * lr_scale * delta).astype(p.dtype)
            # non-matrix fallback: signSGD-with-momentum (lightweight)
            return (p.astype(jnp.float32)
                    - self.fallback_lr * lr_scale * jnp.sign(mm)
                    ).astype(p.dtype)

        new_params = jax.tree_util.tree_map_with_path(upd, params, mom)

        gram = state.gram
        if self.gram_decay is not None and gram is not None:
            d = self.gram_decay

            def upd_gram(gm, mm):
                if not isinstance(gm, PackedTriangle):
                    return gm
                x = mm if mm.shape[0] <= mm.shape[1] else mm.T
                g = blas.syrk(x.astype(jnp.float32),
                              fill="packed") / x.shape[-1]
                ema = d * gm.vec.astype(jnp.float32) + (1.0 - d) * g
                return PackedTriangle(ema.astype(gm.dtype), gm.n)

            gram = jax.tree.map(
                upd_gram, gram, mom,
                is_leaf=lambda x: isinstance(x, PackedTriangle))
        return new_params, MuonState(step=step, momentum=mom, gram=gram)


def state_dict(state: MuonState) -> dict:
    """MuonState as a stable-keyed dict pytree for
    :func:`~repro.distributed.save_checkpoint` — the ``gram`` entry is
    a tree of typed :class:`PackedTriangle` leaves, which the
    persistence layer stores packed (bf16 words on disk)."""
    return {"step": state.step, "momentum": state.momentum,
            "gram": state.gram}


def load_state_dict(d: dict) -> MuonState:
    """Inverse of :func:`state_dict` (``gram`` optional for states
    saved before gram tracking existed)."""
    return MuonState(step=d["step"], momentum=d["momentum"],
                     gram=d.get("gram"))

"""Mixture-of-Experts MLP: top-k routing with shared experts
(DeepSeek-V2/V3, Jamba style).

Three interchangeable expert-compute paths (``impl=``):
  * ``capacity`` (default for big T) — sort-grouped tokens × per-expert
    capacity windows, custom-VJP grouped matmul: FLOPs ∝ active
    experts, no (E,cap,d) residual stacking (§Perf iters 5–9);
  * ``gather``  (default for decode-sized T) — per-token expert-weight
    gather;
  * ``ragged``  — dropless ``lax.ragged_dot`` reference (beware: XLA
    lowers it DENSE → E/k flop waste; kept as the numerics oracle).

On a mesh with a model axis the layer runs TENSOR-parallel under
shard_map: experts f-sharded, tokens never leave their data shard, one
(T,d) psum per layer — no EP all-to-all, no global dispatch sorts.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compat import get_ambient_mesh
from .common import ArchConfig, MoECfg, Params, dense_init, split_keys


def act_fn(name: str):
    return jax.nn.gelu if name.startswith("gelu") else jax.nn.silu


# ---------------------------------------------------------------------------
# dense MLP (SwiGLU / GeGLU / plain)
# ---------------------------------------------------------------------------
def mlp_params(cfg: ArchConfig, key, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    ks = split_keys(key, 3)
    if cfg.act == "gelu_mlp":           # plain 2-matrix MLP (granite/musicgen)
        return {"wi": dense_init(ks[0], (d, f)),
                "wo": dense_init(ks[1], (f, d))}
    return {"wi": dense_init(ks[0], (d, f)),      # gate
            "wg": dense_init(ks[1], (d, f)),      # up
            "wo": dense_init(ks[2], (f, d))}


def mlp_apply(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    a = act_fn(cfg.act)
    if "wg" not in p:
        return a(x @ p["wi"]) @ p["wo"]
    return (a(x @ p["wi"]) * (x @ p["wg"])) @ p["wo"]


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------
def moe_params(cfg: ArchConfig, key) -> Params:
    mo: MoECfg = cfg.moe
    d, f, e = cfg.d_model, mo.d_ff_expert, mo.n_experts
    ks = split_keys(key, 5)
    p: Params = {
        "router": dense_init(ks[0], (d, e)).astype(jnp.float32),
        "wi": dense_init(ks[1], (e, d, f)),
        "wg": dense_init(ks[2], (e, d, f)),
        "wo": dense_init(ks[3], (e, f, d)),
    }
    if mo.n_shared:
        p["shared"] = mlp_params(cfg, ks[4], d_ff=mo.d_ff_expert * mo.n_shared)
    return p


def _ragged_expert_mm(xs: jax.Array, w: jax.Array, group_sizes: jax.Array
                      ) -> jax.Array:
    """xs: (N, d) sorted by expert; w: (E, d, f); group_sizes: (E,)."""
    return jax.lax.ragged_dot(xs, w, group_sizes)


CAPACITY_FACTOR = 1.5     # slack over the mean tokens/expert
MIN_CAPACITY = 8


def _capacity(t_k: int, n_experts: int,
              factor: float = None) -> int:
    if factor is None:
        factor = CAPACITY_FACTOR          # module global: test-patchable
    cap = int(t_k * factor / n_experts) + 1
    return max((cap + 7) // 8 * 8, MIN_CAPACITY)


def _window_index(offsets, n, e, cap):
    """Sorted row r lives in expert e_r at slot r − off_e; slots ≥ cap
    are dropped (capacity overflow) -> OOB index -> take fills 0."""
    r = jnp.arange(n)
    e_r = jnp.searchsorted(offsets, r, side="right") - 1
    slot = r - offsets[e_r]
    return jnp.where(slot < cap, e_r * cap + slot, e * cap)


def _expert_mm(act, blk, wi_e, wg_e, wo_e):
    return (act(blk @ wi_e) * (blk @ wg_e)) @ wo_e


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _grouped_core(xs_pad, wi, wg, wo, offsets, group_sizes, cap,
                  act_name):
    """(E, cap, d) f32 expert outputs; windows at each expert's offset.

    Custom VJP (§Perf iter 9): jax's default scan transpose stacks the
    per-expert input blocks as (E,cap,d) residuals (with dtype-mismatch
    convert storms on top); the hand-written backward instead
    recomputes each block INSIDE its own reverse-scan step and
    reconstructs dxs with the same disjoint-window gather as the
    forward — no (E,cap,d) residual ever materializes."""
    act = act_fn(act_name)
    d = xs_pad.shape[1]
    rows = jnp.arange(cap)

    def body(_, inp):
        wi_e, wg_e, wo_e, off, g = inp
        blk = jax.lax.dynamic_slice(xs_pad, (off, 0), (cap, d))
        valid = (rows < g)[:, None]
        y = _expert_mm(act, blk, wi_e, wg_e, wo_e)
        return None, (y * valid).astype(jnp.float32)

    _, ys = jax.lax.scan(body, None, (wi, wg, wo, offsets, group_sizes))
    return ys


def _grouped_core_fwd(xs_pad, wi, wg, wo, offsets, group_sizes, cap,
                      act_name):
    ys = _grouped_core(xs_pad, wi, wg, wo, offsets, group_sizes, cap,
                       act_name)
    return ys, (xs_pad, wi, wg, wo, offsets, group_sizes)


def _grouped_core_bwd(cap, act_name, res, dys):
    xs_pad, wi, wg, wo, offsets, group_sizes = res
    act = act_fn(act_name)
    e = wi.shape[0]
    n_pad, d = xs_pad.shape
    rows = jnp.arange(cap)

    def body(_, inp):
        wi_e, wg_e, wo_e, off, g, dy_e = inp
        blk = jax.lax.dynamic_slice(xs_pad, (off, 0), (cap, d))
        valid = (rows < g)[:, None]
        _, pull = jax.vjp(
            lambda b_, a_, g_, o_: _expert_mm(act, b_, a_, g_, o_),
            blk, wi_e, wg_e, wo_e)
        db, dwi_e, dwg_e, dwo_e = pull((dy_e * valid).astype(blk.dtype))
        return None, ((db * valid).astype(jnp.float32),
                      dwi_e.astype(jnp.float32),
                      dwg_e.astype(jnp.float32),
                      dwo_e.astype(jnp.float32))

    _, (dblk, dwi, dwg, dwo) = jax.lax.scan(
        body, None, (wi, wg, wo, offsets, group_sizes,
                     dys.astype(jnp.float32)))
    # valid windows are disjoint: dxs rows come straight back via the
    # same window gather as the forward reconstruction
    idx = _window_index(offsets, n_pad - cap, e, cap)
    dxs = jnp.take(dblk.reshape(e * cap, d), idx, axis=0, mode="fill",
                   fill_value=0)
    dxs_pad = jnp.pad(dxs, ((0, cap), (0, 0))).astype(xs_pad.dtype)
    f0 = lambda a: np.zeros(a.shape, jax.dtypes.float0)  # noqa: E731
    return (dxs_pad, dwi.astype(wi.dtype), dwg.astype(wg.dtype),
            dwo.astype(wo.dtype), f0(offsets), f0(group_sizes))


_grouped_core.defvjp(_grouped_core_fwd, _grouped_core_bwd)


def _grouped_mm_capacity(xs, wi, wg, wo, group_sizes, act_name, cap):
    """Capacity-windowed grouped matmul (§Perf iter 5).

    xs (N, d) is sorted by expert with group offsets from
    ``group_sizes``; each expert processes a fixed ``cap``-row window at
    its offset (tokens over capacity are dropped — standard capacity-
    factor routing).  FLOPs are E·cap·d·f ∝ active tokens, unlike
    ``lax.ragged_dot`` which XLA lowers to a DENSE (N × E·d·f) masked
    dot — the single biggest waste in the MoE baselines (HLO/model
    flops ≈ E/k).
    """
    n, d = xs.shape
    e = wi.shape[0]
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(group_sizes)[:-1].astype(jnp.int32)])
    xs_pad = jnp.pad(xs, ((0, cap), (0, 0)))           # window overrun pad
    ys = _grouped_core(xs_pad, wi, wg, wo, offsets, group_sizes, cap,
                       act_name)
    idx = _window_index(offsets, n, e, cap)
    return ys.reshape(e * cap, d), idx


def _capacity_gather(ys_flat, idx, inv):
    """One fused gather: unsort ∘ capacity-reconstruct (index
    composition is free; a second materialized gather is not)."""
    return jnp.take(ys_flat, idx[inv], axis=0, mode="fill",
                    fill_value=0)


def _gathered_expert_mm(xf, tope, wi, wg, wo, act):
    """Decode-sized path: gather the k expert slices per token.
    xf (T, d); tope (T, k) -> (T, k, d).  Weight-gather traffic
    T·k·d·f ≪ dense compute for tiny T."""
    wi_g = wi[tope]                                     # (T, k, d, f)
    wg_g = wg[tope]
    wo_g = wo[tope]                                     # (T, k, f, d)
    h = act(jnp.einsum("td,tkdf->tkf", xf, wi_g)) \
        * jnp.einsum("td,tkdf->tkf", xf, wg_g)
    return jnp.einsum("tkf,tkfd->tkd", h, wo_g)


def _route(p, xf, k):
    logits = xf.astype(jnp.float32) @ p["router"]       # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    topg, tope = jax.lax.top_k(gates, k)                # (T, k)
    topg = topg / jnp.clip(topg.sum(-1, keepdims=True), 1e-9)
    return topg, tope


def _moe_local(cfg: ArchConfig, p: Params, xf: jax.Array,
               impl: str) -> jax.Array:
    """Per-shard MoE body: xf (T, d) -> (T, d) (output may be partial
    over the f-sharded contraction; callers psum)."""
    mo: MoECfg = cfg.moe
    t, d = xf.shape
    k = mo.top_k
    topg, tope = _route(p, xf, k)
    a = act_fn(cfg.act)

    if impl == "gather" or (impl == "auto" and t <= 256):
        y = _gathered_expert_mm(xf, tope, p["wi"], p["wg"], p["wo"], a)
    else:
        flat_e = tope.reshape(-1)                       # (T*k,)
        order = jnp.argsort(flat_e)                     # stable group sort
        inv = jnp.argsort(order)
        token_idx = (jnp.arange(t * k) // k)[order]
        xs = xf[token_idx]                              # (T*k, d) sorted
        group_sizes = jnp.bincount(flat_e, length=mo.n_experts)
        if impl == "ragged":
            h = (a(_ragged_expert_mm(xs, p["wi"], group_sizes))
                 * _ragged_expert_mm(xs, p["wg"], group_sizes))
            ys = _ragged_expert_mm(h, p["wo"], group_sizes)
            y = ys[inv].reshape(t, k, d)
        else:                                           # capacity (default)
            cap = _capacity(t * k, mo.n_experts)
            ys_flat, idx = _grouped_mm_capacity(
                xs, p["wi"], p["wg"], p["wo"], group_sizes, cfg.act, cap)
            y = _capacity_gather(ys_flat, idx, inv).reshape(t, k, d)

    # combine in the activation dtype: an f32 upcast here sends f32
    # cotangents into the bf16 stacked expert buffer and XLA then
    # round-trips the WHOLE buffer through convert every scan step
    # (§Perf iter 8)
    out = jnp.einsum("tkd,tk->td", y, topg.astype(y.dtype)) \
        .astype(xf.dtype)
    if mo.n_shared:
        out = out + mlp_apply(cfg, p["shared"], xf)
    return out


def _batch_axes(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def moe_apply(cfg: ArchConfig, p: Params, x: jax.Array,
              impl: str = "auto") -> jax.Array:
    """x: (B, S, d) -> (B, S, d).

    On a mesh with a model axis, runs the tensor-parallel MoE under
    shard_map: tokens stay on their data shard, every device computes
    the f-slice of every expert it owns, and ONE (T,d) psum over
    'model' finishes the layer — no token all-to-all, no global sort
    collectives, flops ∝ active experts (capacity-factor windows).
    Off-mesh (tests, 1 device) the same body runs locally."""
    b, s, d = x.shape
    mesh = get_ambient_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1

    if tp <= 1 or (cfg.moe.d_ff_expert % tp) != 0:
        return _moe_local(cfg, p, x.reshape(b * s, d), impl) \
            .reshape(b, s, d)

    from jax.sharding import PartitionSpec as P
    ba = _batch_axes(mesh)
    dp = 1
    for a in ba:
        dp *= mesh.shape[a]
    if b % dp:                       # e.g. long_500k batch 1: tokens
        ba = ()                      # replicated over the data axes

    # inner checkpoint: recompute the expert blocks in the backward
    # pass instead of stashing (periods × E × cap × d) activations —
    # the dots-saveable period policy would otherwise save every
    # expert matmul output (§Perf iter 6)
    local = jax.checkpoint(
        lambda p_loc, xf: _moe_local(cfg, p_loc, xf, impl))

    def body(x_loc, p_loc):
        bb, ss, dd = x_loc.shape
        out = local(p_loc, x_loc.reshape(bb * ss, dd))
        out = jax.lax.psum(out, "model")
        return out.reshape(bb, ss, dd)

    p_specs = {
        "router": P(None, None),
        "wi": P(None, None, "model"), "wg": P(None, None, "model"),
        "wo": P(None, "model", None),
    }
    if cfg.moe.n_shared:
        shared = {"wi": P(None, "model"), "wo": P("model", None)}
        if "wg" in p["shared"]:
            shared["wg"] = P(None, "model")
        p_specs["shared"] = shared
    fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(ba if ba else None, None, None),
                                     p_specs),
                           out_specs=P(ba if ba else None, None, None),
                           check_vma=False)
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(fn(x, {k_: p[k_] for k_ in p_specs}),
                           "scan_out")


def moe_aux_loss(cfg: ArchConfig, p: Params, x: jax.Array) -> jax.Array:
    """Load-balancing auxiliary loss (Switch-style): E·Σ_e f_e·P_e."""
    mo = cfg.moe
    t = x.shape[0] * x.shape[1]
    logits = x.reshape(t, -1).astype(jnp.float32) @ p["router"]
    gates = jax.nn.softmax(logits, axis=-1)
    _, tope = jax.lax.top_k(gates, mo.top_k)
    frac = jnp.bincount(tope.reshape(-1), length=mo.n_experts) / (t * mo.top_k)
    prob = gates.mean(0)
    return mo.n_experts * jnp.sum(frac * prob)

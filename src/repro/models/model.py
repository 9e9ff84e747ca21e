"""Model assembly: block -> period -> scan -> LM heads.

Layers are grouped into repeating *periods* (cfg.pattern) and stacked with
``lax.scan`` so 60-layer configs compile as one period body + loop — this
keeps HLO size and CPU compile time bounded for the dry-runs.

Train/serve entry points:
  forward(cfg, params, batch)                 -> final hidden states
  lm_loss(cfg, params, batch)                 -> scalar loss (chunked xent)
  prefill(cfg, params, batch, s_max)          -> (logits_last, cache)
  decode_step(cfg, params, token, pos, cache) -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import attention, attn_cache_init, attn_params
from .common import (ArchConfig, BlockSpec, Params, apply_norm, dense_init,
                     embed_init, norm_params, softcap, split_keys)
from .moe import mlp_apply, mlp_params, moe_apply, moe_params
from .ssm import (mamba_mixer, mamba_params, mamba_state_init, mlstm_mixer,
                  mlstm_params, mlstm_state_init, slstm_mixer, slstm_params,
                  slstm_state_init)


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------
def block_params(cfg: ArchConfig, spec: BlockSpec, key) -> Params:
    ks = split_keys(key, 4)
    p: Params = {"norm1": norm_params(cfg, cfg.d_model)}
    if spec.mixer == "attn":
        p["mixer"] = attn_params(cfg, ks[0])
    elif spec.mixer == "mamba":
        p["mixer"] = mamba_params(cfg, ks[0])
    elif spec.mixer == "mlstm":
        p["mixer"] = mlstm_params(cfg, ks[0])
    elif spec.mixer == "slstm":
        p["mixer"] = slstm_params(cfg, ks[0])
    else:
        raise ValueError(spec.mixer)
    if spec.mlp != "none":
        p["norm2"] = norm_params(cfg, cfg.d_model)
        p["mlp"] = moe_params(cfg, ks[1]) if spec.mlp == "moe" else \
            mlp_params(cfg, ks[1])
    if cfg.post_block_norm:
        p["postnorm1"] = norm_params(cfg, cfg.d_model)
        if spec.mlp != "none":
            p["postnorm2"] = norm_params(cfg, cfg.d_model)
    return p


def block_cache_init(cfg: ArchConfig, spec: BlockSpec, batch: int,
                     s_max: int) -> Params:
    if spec.mixer == "attn":
        return attn_cache_init(cfg, batch, s_max)
    if spec.mixer == "mamba":
        return mamba_state_init(cfg, batch)
    if spec.mixer == "mlstm":
        return mlstm_state_init(cfg, batch)
    return slstm_state_init(cfg, batch)


def block_apply(cfg: ArchConfig, spec: BlockSpec, p: Params, x: jax.Array,
                positions: jax.Array, cache: Optional[Params]
                ) -> Tuple[jax.Array, Optional[Params]]:
    h = apply_norm(cfg, p["norm1"], x)
    if spec.mixer == "attn":
        y, new_cache = attention(cfg, spec, p["mixer"], h, positions, cache)
    elif spec.mixer == "mamba":
        y, new_cache = mamba_mixer(cfg, p["mixer"], h, cache)
    elif spec.mixer == "mlstm":
        y, new_cache = mlstm_mixer(cfg, p["mixer"], h, cache)
    else:
        y, new_cache = slstm_mixer(cfg, p["mixer"], h, cache)
    if cfg.post_block_norm:
        y = apply_norm(cfg, p["postnorm1"], y)
    x = x + y
    if spec.mlp != "none":
        h = apply_norm(cfg, p["norm2"], x)
        y = moe_apply(cfg, p["mlp"], h) if spec.mlp == "moe" else \
            mlp_apply(cfg, p["mlp"], h)
        if cfg.post_block_norm:
            y = apply_norm(cfg, p["postnorm2"], y)
        x = x + y
    return x, new_cache


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------
def init_params(cfg: ArchConfig, key) -> Params:
    ks = split_keys(key, 5 + len(cfg.prefix))
    p: Params = {}
    if cfg.frontend in ("tokens", "vlm"):
        p["embed"] = embed_init(ks[0], (cfg.vocab, cfg.d_model))
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.vocab))
    p["final_norm"] = norm_params(cfg, cfg.d_model)

    def one_period(k):
        kk = split_keys(k, cfg.period)
        return {f"b{i}": block_params(cfg, spec, kk[i])
                for i, spec in enumerate(cfg.pattern)}

    period_keys = jnp.stack(split_keys(ks[2], cfg.n_periods))
    p["periods"] = jax.vmap(one_period)(period_keys)
    if cfg.prefix:
        p["prefix"] = {f"b{i}": block_params(cfg, spec, ks[5 + i])
                       for i, spec in enumerate(cfg.prefix)}
    if cfg.mtp:  # deepseek-v3 multi-token-prediction block
        p["mtp"] = block_params(cfg, BlockSpec(mixer="attn", mlp="dense"),
                                ks[3])
        p["mtp_norm"] = norm_params(cfg, cfg.d_model)
    return p


def init_cache(cfg: ArchConfig, batch: int, s_max: int) -> Params:
    def one_period(_):
        return {f"b{i}": block_cache_init(cfg, spec, batch, s_max)
                for i, spec in enumerate(cfg.pattern)}
    cache: Params = {"periods": jax.vmap(one_period)(jnp.arange(cfg.n_periods))}
    if cfg.prefix:
        cache["prefix"] = {f"b{i}": block_cache_init(cfg, spec, batch, s_max)
                           for i, spec in enumerate(cfg.prefix)}
    return cache


def _embed_input(cfg: ArchConfig, params: Params, batch: Dict[str, Any]
                 ) -> jax.Array:
    if cfg.frontend == "embeddings":            # musicgen: stub frontend
        return batch["embeds"].astype(jnp.bfloat16)
    tok = batch["tokens"]
    x = params["embed"][tok]
    if cfg.frontend == "vlm" and "patch_embeds" in batch:
        # pixtral stub: precomputed patch embeddings prepended
        x = jnp.concatenate(
            [batch["patch_embeds"].astype(x.dtype), x], axis=1)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _unembed(cfg: ArchConfig, params: Params) -> jax.Array:
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["unembed"]


def _remat_policy(cfg: ArchConfig):
    """Per-period remat policy (§Perf iteration 2).

    'full'  — save period inputs only; backward recomputes everything
              (min HBM capacity, max recompute traffic).
    'dots'  — save matmul outputs + named scan outputs ('scan_out');
              backward skips re-running projections AND the sequential/
              associative recurrences — these dominate recompute traffic
              for the SSM archs and cost (B,S,d)-sized stash each.
    'names' — save ONLY named outputs; for MoE archs the dots policy
              reaches inside the expert scan and stacks every
              per-expert matmul across layers (a (periods,E,cap,d)
              stash — §Perf iter 8), so deepseek/jamba use this.
    """
    pol = getattr(cfg, "remat_policy", "dots")
    if pol == "full":
        return None
    cp = jax.checkpoint_policies
    if pol == "names":
        return cp.save_only_these_names("scan_out")
    return cp.save_from_both_policies(
        cp.checkpoint_dots_with_no_batch_dims,
        cp.save_only_these_names("scan_out"))


def forward(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
            cache: Optional[Params] = None, remat: bool = True
            ) -> Tuple[jax.Array, Optional[Params]]:
    with jax.named_scope("model.embed"):
        x = _embed_input(cfg, params, batch)
    with jax.named_scope("model.blocks"):
        x, out_cache = _blocks(cfg, params, batch, x, cache, remat)
    return x, out_cache


def _blocks(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
            x: jax.Array, cache: Optional[Params], remat: bool
            ) -> Tuple[jax.Array, Optional[Params]]:
    """The prefix blocks, the scanned periods and the final norm."""
    b, s, _ = x.shape
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    new_prefix = None
    if cfg.prefix:
        new_prefix = {}
        for i, spec in enumerate(cfg.prefix):
            pc = None if cache is None else cache["prefix"][f"b{i}"]
            x, nc = block_apply(cfg, spec, params["prefix"][f"b{i}"], x,
                                positions, pc)
            new_prefix[f"b{i}"] = nc

    def period_fn(x, inp):
        pp, pc = inp
        ncs = {}
        for i, spec in enumerate(cfg.pattern):
            x, nc = block_apply(cfg, spec, pp[f"b{i}"], x, positions,
                                None if pc is None else pc[f"b{i}"])
            ncs[f"b{i}"] = nc
        return x, (ncs if pc is not None else 0)

    if remat and cache is None:
        period_fn = jax.checkpoint(period_fn,
                                   policy=_remat_policy(cfg))

    xs = (params["periods"], None if cache is None else cache["periods"])
    x, new_caches = jax.lax.scan(period_fn, x, xs)
    x = apply_norm(cfg, params["final_norm"], x)
    if cache is None:
        return x, None
    out_cache: Dict[str, Any] = {"periods": new_caches}
    if cfg.prefix:
        out_cache["prefix"] = new_prefix
    return x, out_cache


# ---------------------------------------------------------------------------
# loss (chunked cross-entropy: never materializes (B,S,V) logits)
# ---------------------------------------------------------------------------
def _xent_chunk(cfg: ArchConfig, w: jax.Array, x: jax.Array,
                labels: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, Cs, d), labels: (B, Cs) with -1 = ignore."""
    logits = (x.astype(jnp.float32) @ w.astype(jnp.float32))
    logits = softcap(logits, cfg.final_softcap)
    lse = jax.nn.logsumexp(logits, axis=-1)
    lab = jnp.maximum(labels, 0)
    ll = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
    valid = labels >= 0
    return jnp.sum((lse - ll) * valid), jnp.sum(valid)


def lm_loss(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
            chunk: int = 512, remat: bool = True) -> jax.Array:
    x, _ = forward(cfg, params, batch, remat=remat)
    with jax.named_scope("model.head"):
        return _head_loss(cfg, params, batch, x, chunk, remat)


def _head_loss(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
               x: jax.Array, chunk: int, remat: bool) -> jax.Array:
    labels = batch["labels"]
    w = _unembed(cfg, params)
    b, s, d = x.shape
    nchunks = max(s // chunk, 1)
    cs = s // nchunks
    xc = x[:, :nchunks * cs].reshape(b, nchunks, cs, d).swapaxes(0, 1)
    lc = labels[:, :nchunks * cs].reshape(b, nchunks, cs).swapaxes(0, 1)

    def body(acc, inp):
        xs_, ls_ = inp
        l, n = _xent_chunk(cfg, w, xs_, ls_)
        return (acc[0] + l, acc[1] + n), None

    fn = jax.checkpoint(body) if remat else body
    (tot, cnt), _ = jax.lax.scan(fn, (jnp.zeros(()), jnp.zeros(())), (xc, lc))
    loss = tot / jnp.maximum(cnt, 1.0)
    if cfg.mtp:  # predict t+2 through one extra block (weight 0.3)
        pos = batch.get("positions")
        if pos is None:
            pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, _ = block_apply(cfg, BlockSpec(mixer="attn", mlp="dense"),
                           params["mtp"], x, pos, None)
        h = apply_norm(cfg, params["mtp_norm"], h)
        lab2 = jnp.concatenate(
            [labels[:, 1:], -jnp.ones((b, 1), labels.dtype)], axis=1)
        hc = h[:, :nchunks * cs].reshape(b, nchunks, cs, d).swapaxes(0, 1)
        l2c = lab2[:, :nchunks * cs].reshape(b, nchunks, cs).swapaxes(0, 1)
        (tot2, cnt2), _ = jax.lax.scan(fn, (jnp.zeros(()), jnp.zeros(())),
                                       (hc, l2c))
        loss = loss + 0.3 * tot2 / jnp.maximum(cnt2, 1.0)
    return loss


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
def prefill(cfg: ArchConfig, params: Params, batch: Dict[str, Any],
            s_max: int, return_hidden: bool = False):
    """Full-sequence forward building the KV/state cache; returns logits of
    the last position only.

    ``return_hidden=True`` additionally returns the final-norm hidden
    states (B, S, d) — the per-token features the serving Gram cache
    accumulates (positions past each prompt's true length hold padding
    activations; callers mask by length).  The extra output is free:
    ``x`` is already computed for the logits head."""
    if cfg.frontend == "embeddings":
        b, s = batch["embeds"].shape[:2]
    else:
        b, s = batch["tokens"].shape
        if cfg.frontend == "vlm" and "patch_embeds" in batch:
            s += batch["patch_embeds"].shape[1]
    cache = init_cache(cfg, b, s_max)
    x, cache = forward(cfg, params, batch, cache=cache, remat=False)
    w = _unembed(cfg, params)
    logits = softcap(x[:, -1:].astype(jnp.float32) @ w.astype(jnp.float32),
                     cfg.final_softcap)
    if return_hidden:
        return logits, cache, x
    return logits, cache


def decode_step(cfg: ArchConfig, params: Params, token: jax.Array,
                pos: jax.Array, cache: Params
                ) -> Tuple[jax.Array, Params]:
    """One token per sequence: token (B, 1) int32, pos (B, 1) positions."""
    if cfg.frontend == "embeddings":
        batch = {"embeds": token, "positions": pos}   # (B,1,d) stub frames
    else:
        batch = {"tokens": token, "positions": pos}
    x, cache = forward(cfg, params, batch, cache=cache, remat=False)
    w = _unembed(cfg, params)
    logits = softcap(x.astype(jnp.float32) @ w.astype(jnp.float32),
                     cfg.final_softcap)
    return logits, cache

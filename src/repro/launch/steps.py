"""Jit-able train / serve step factories.

``make_train_step`` builds the full production step: microbatched gradient
accumulation (lax.scan), global-norm clipping, DP gradient psum implied by
GSPMD sharding, optimizer update (AdamW / AdamW-8bit / Muon-SYRK), and
metric outputs.  ``make_prefill_step`` / ``make_decode_step`` are the
serving entry points.  All are pure functions of (params, opt_state, batch)
suitable for ``jax.jit`` with explicit in/out shardings.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig
from repro.models.model import decode_step as _decode
from repro.models.model import lm_loss
from repro.models.model import prefill as _prefill
from repro.optim import AdamW, Muon


def describe_blas_routing(params_shape, mesh, axis: str = "model",
                          limit: int = 12, grad: bool = True):
    """Routing table for the optimizer's symmetric kernels: one line per
    distinct trailing-2D parameter shape, showing which `repro.blas`
    path (dense / pallas / 1d / 2d / 3d) the NS Gram SYRK takes on this
    mesh — and, with ``grad=True``, which route its cotangent SYMM takes
    when the step is differentiated (the backward obeys the same Thm 9
    bounds; see blas/grad.py).  Printed at startup by launch/train.py
    for muon runs."""
    from repro import blas
    if axis not in mesh.shape:
        return [f"  (mesh has no {axis!r} axis: all shapes route dense)"]
    shapes = sorted({tuple(sorted(int(s) for s in x.shape[-2:]))
                     for x in jax.tree.leaves(params_shape)
                     if len(x.shape) >= 2})
    lines = []
    for n1, n2 in shapes[:limit]:
        text = blas.explain("syrk", n1, n2, mesh=mesh, axis=axis,
                            grad=grad)
        lines.extend("  " + ln for ln in text.splitlines())
    if len(shapes) > limit:
        lines.append(f"  ... ({len(shapes) - limit} more shapes)")
    return lines


def make_optimizer(cfg: ArchConfig, name: str = "adamw", lr: float = 3e-4,
                   mesh=None, track_gram: bool = False):
    """``track_gram``: EMA a packed momentum-Gram per 2D matrix param in
    the Muon state (``MuonState.gram`` — m(m+1)/2 words each, stored as
    typed ``PackedTriangle`` leaves that the checkpoint layer persists
    packed).  Ignored by the AdamW family."""
    gd = 0.99 if track_gram else None
    if name == "adamw":
        return AdamW(lr=lr)
    if name == "adamw8bit":
        return AdamW(lr=lr, quantize_moments=True)
    if name == "muon":
        return Muon(lr=2e-2, mode="reference", gram_decay=gd)
    if name == "muon-syrk":
        return Muon(lr=2e-2, mode="syrk-1d", mesh=mesh, gram_decay=gd)
    raise ValueError(name)


def _clip_by_global_norm(grads: Any, max_norm: float) -> Tuple[Any, jax.Array]:
    gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / (gn + 1e-9))
    return jax.tree.map(lambda g: g * scale, grads), gn


def make_train_step(cfg: ArchConfig, optimizer, *, microbatches: int = 1,
                    clip_norm: float = 1.0, loss_chunk: int = 512,
                    compressor=None) -> Callable:
    """Returns train_step(params, opt_state, batch) -> (params, opt_state,
    metrics).  ``microbatches`` > 1 scans gradient accumulation over the
    leading batch split (activation memory /= microbatches).

    ``compressor`` (e.g. distributed.ErrorFeedbackInt8): when given,
    ``opt_state`` is the pair (optimizer state, EF state) and gradients
    pass through int8 quantize/dequantize with error feedback before the
    optimizer — the numerics of a compressed DP all-reduce."""

    def loss_fn(params, batch):
        return lm_loss(cfg, params, batch, chunk=loss_chunk)

    def train_step(params, opt_state, batch):
        if microbatches > 1:
            def split(x):
                b = x.shape[0]
                return x.reshape((microbatches, b // microbatches)
                                 + x.shape[1:])
            mb = jax.tree.map(split, batch)

            def acc_fn(carry, mbatch):
                loss_sum, gacc = carry
                with jax.named_scope("train.loss"):
                    loss, g = jax.value_and_grad(loss_fn)(params, mbatch)
                gacc = jax.tree.map(
                    lambda a, b_: a + b_.astype(jnp.float32), gacc, g)
                return (loss_sum + loss, gacc), None

            gzero = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(acc_fn, (jnp.zeros(()), gzero),
                                            mb)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        else:
            with jax.named_scope("train.loss"):
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)

        with jax.named_scope("train.clip"):
            grads, gnorm = _clip_by_global_norm(grads, clip_norm)
        if compressor is not None:
            inner, ef = opt_state
            grads, ef = compressor.compress(grads, ef)
            new_params, new_inner = optimizer.update(grads, inner, params)
            new_opt = (new_inner, ef)
        else:
            new_params, new_opt = optimizer.update(grads, opt_state,
                                                   params)
        metrics = {"loss": loss, "grad_norm": gnorm}
        return new_params, new_opt, metrics

    return train_step


def make_prefill_step(cfg: ArchConfig, s_max: int,
                      return_hidden: bool = False) -> Callable:
    """``return_hidden=True`` makes the step return (logits, cache,
    hidden) with hidden the final-norm activations (B, S, d) — the
    features the serving Gram cache EMAs; padded positions carry
    garbage, mask by prompt length."""
    def prefill_step(params, batch):
        return _prefill(cfg, params, batch, s_max=s_max,
                        return_hidden=return_hidden)
    return prefill_step


def make_decode_step(cfg: ArchConfig) -> Callable:
    def serve_step(params, token, pos, cache):
        logits, cache = _decode(cfg, params, token, pos, cache)
        next_token = jnp.argmax(logits[:, -1], axis=-1)[:, None] \
            .astype(jnp.int32)
        return next_token, logits, cache
    return serve_step

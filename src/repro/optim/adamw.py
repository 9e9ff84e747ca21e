"""AdamW with bf16 params / f32 moments and optional 8-bit moment
quantization (block-wise absmax) — the quantized mode roughly halves
optimizer-state HBM, which is what lets the ≥200B archs fit train_4k on a
256-chip pod (see EXPERIMENTS §Dry-run memory notes)."""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jax.Array
    m: Any
    v: Any
    m_scale: Any = None        # per-block absmax scales when quantized
    v_scale: Any = None


@dataclass(frozen=True)
class AdamW:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    quantize_moments: bool = False
    qblock: int = 256

    # -- quantization helpers -------------------------------------------
    def _q(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        flat = x.reshape(-1)
        pad = -flat.shape[0] % self.qblock
        flat = jnp.pad(flat, (0, pad)).reshape(-1, self.qblock)
        scale = jnp.max(jnp.abs(flat), axis=1, keepdims=True) / 127.0 + 1e-12
        q = jnp.clip(jnp.round(flat / scale), -127, 127).astype(jnp.int8)
        return q, scale.astype(jnp.float32)

    def _dq(self, q: jax.Array, scale: jax.Array, shape) -> jax.Array:
        flat = (q.astype(jnp.float32) * scale).reshape(-1)
        return flat[:math.prod(shape)].reshape(shape)

    # -- api --------------------------------------------------------------
    def init(self, params: Any) -> AdamWState:
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if not self.quantize_moments:
            return AdamWState(step=jnp.zeros((), jnp.int32), m=zeros, v=zeros)
        qm = jax.tree.map(lambda z: self._q(z), zeros)
        m = jax.tree.map(lambda t: t[0], qm,
                         is_leaf=lambda x: isinstance(x, tuple))
        s = jax.tree.map(lambda t: t[1], qm,
                         is_leaf=lambda x: isinstance(x, tuple))
        return AdamWState(step=jnp.zeros((), jnp.int32), m=m, v=m,
                          m_scale=s, v_scale=s)

    @jax.named_scope("optim.adamw")
    def update(self, grads: Any, state: AdamWState, params: Any,
               lr_scale: jax.Array = 1.0) -> Tuple[Any, AdamWState]:
        step = state.step + 1
        b1c = 1 - self.b1 ** step.astype(jnp.float32)
        b2c = 1 - self.b2 ** step.astype(jnp.float32)

        if not self.quantize_moments:
            m = jax.tree.map(
                lambda mm, g: self.b1 * mm + (1 - self.b1)
                * g.astype(jnp.float32), state.m, grads)
            v = jax.tree.map(
                lambda vv, g: self.b2 * vv + (1 - self.b2)
                * jnp.square(g.astype(jnp.float32)), state.v, grads)
            new_state = AdamWState(step=step, m=m, v=v)
        else:
            m = jax.tree.map(
                lambda q, s, g: self.b1 * self._dq(q, s, g.shape)
                + (1 - self.b1) * g.astype(jnp.float32),
                state.m, state.m_scale, grads)
            # v is stored quantized in sqrt-domain (second moments span many
            # orders of magnitude; linear int8 is too coarse)
            v = jax.tree.map(
                lambda q, s, g: self.b2
                * jnp.square(self._dq(q, s, g.shape))
                + (1 - self.b2) * jnp.square(g.astype(jnp.float32)),
                state.v, state.v_scale, grads)
            qm = jax.tree.map(self._q, m)
            qv = jax.tree.map(lambda vv: self._q(jnp.sqrt(vv)), v)
            new_state = AdamWState(
                step=step,
                m=jax.tree.map(lambda t: t[0], qm,
                               is_leaf=lambda x: isinstance(x, tuple)),
                v=jax.tree.map(lambda t: t[0], qv,
                               is_leaf=lambda x: isinstance(x, tuple)),
                m_scale=jax.tree.map(lambda t: t[1], qm,
                                     is_leaf=lambda x: isinstance(x, tuple)),
                v_scale=jax.tree.map(lambda t: t[1], qv,
                                     is_leaf=lambda x: isinstance(x, tuple)))

        def upd(p, mm, vv):
            mhat = mm / b1c
            vhat = vv / b2c
            delta = mhat / (jnp.sqrt(vhat) + self.eps) \
                + self.weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32)
                    - self.lr * lr_scale * delta).astype(p.dtype)

        new_params = jax.tree.map(upd, params, m, v)
        return new_params, new_state

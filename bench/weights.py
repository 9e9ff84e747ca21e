"""Weights made on the device from a seed, in one jitted call.

A configuration's plain reference lists its parameters as a *layout*:
``(path, shape, dtype, rule)`` per leaf.  Each leaf is drawn from a key
folded from the seed and the leaf's path, so the program under test and
the reference, given the same layout and seed, hold the same numbers
without either taking them from the other.

Rules (from the configuration's ``init``): ``{"fan_in": true}`` draws a
truncated normal scaled by 1/sqrt(rows of the trailing 2-D slice);
``{"mean": a, "std": b}`` draws a + b·(truncated normal).
"""
from __future__ import annotations

import functools
import zlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Leaf = Tuple[str, Tuple[int, ...], str, Dict[str, Any]]


def seed_key(seed: int) -> jax.Array:
    """A key that keeps all 64 bits of ``seed``."""
    seed %= 2 ** 64
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def leaf_key(base: jax.Array, path: str) -> jax.Array:
    return jax.random.fold_in(base, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def sample(key: jax.Array, shape: Sequence[int], dtype: str,
           rule: Dict[str, Any]) -> jax.Array:
    x = jax.random.truncated_normal(key, -2.0, 2.0, tuple(shape),
                                    jnp.float32)
    if rule.get("fan_in"):
        x = x / jnp.sqrt(jnp.float32(shape[-2]))
    else:
        x = rule.get("mean", 0.0) + rule.get("std", 1.0) * x
    return x.astype(dtype)


def nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    """{"a/b/c": v} -> {"a": {"b": {"c": v}}}."""
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree: Any) -> Dict[str, Any]:
    """Inverse of :func:`nest` for a tree of dicts."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", k)) for k in path)] = leaf
    return out


def generate(seed: int, layout: List[Leaf],
             shardings: Optional[Any] = None) -> Dict[str, Any]:
    """Every leaf of ``layout`` in one jitted call, placed at
    ``shardings`` (a tree like the result) when given."""
    def make(base):
        return nest({p: sample(leaf_key(base, p), s, d, r)
                     for p, s, d, r in layout})
    # the key is an argument, so one compiled program serves every seed
    return jax.jit(make, out_shardings=shardings)(seed_key(seed))


def change_norm(seed: int, leaf: Leaf, value: jax.Array) -> jax.Array:
    """Frobenius norm of ``value`` minus the leaf as :func:`generate`
    made it from ``seed``, without keeping the initial weights: the leaf
    is drawn again inside the program that subtracts it."""
    path, shape, dtype, rule = leaf
    key = leaf_key(seed_key(seed), path)
    return _change_norm(value, key, tuple(shape), dtype,
                        tuple(sorted(rule.items())))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _change_norm(value, key, shape, dtype, rule):
    d = value.astype(jnp.float32) \
        - sample(key, shape, dtype, dict(rule)).astype(jnp.float32)
    return jnp.sqrt(jnp.sum(jnp.square(d)))

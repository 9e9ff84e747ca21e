"""MoE implementations: capacity-windowed and gathered paths vs the
ragged reference, plus the tensor-parallel shard_map path vs local."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import moe
from repro.models.common import ArchConfig, BlockSpec, MoECfg

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(e=8, k=2, f=32, d=16, shared=0):
    return ArchConfig(
        name="moe-test", n_layers=2, d_model=d, n_heads=2, n_kv_heads=2,
        d_ff=f, vocab=64, act="silu",
        pattern=(BlockSpec(mixer="attn", mlp="moe"),),
        moe=MoECfg(n_experts=e, top_k=k, n_shared=shared, d_ff_expert=f))


def _params(cfg, key):
    return moe.moe_params(cfg, key)


def test_capacity_matches_ragged_when_no_overflow():
    cfg = _cfg()
    p = _params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 64, cfg.d_model),
                          jnp.float32)
    y_ref = moe.moe_apply(cfg, p, x, impl="ragged")
    # capacity 2.0x mean + rounding: random routing at T=128, E=8 can
    # overflow; verify agreement on the NON-dropped tokens instead by
    # using a huge factor via monkeypatch
    old = moe.CAPACITY_FACTOR
    moe.CAPACITY_FACTOR = 50.0
    try:
        y_cap = moe.moe_apply(cfg, p, x, impl="capacity")
    finally:
        moe.CAPACITY_FACTOR = old
    np.testing.assert_allclose(np.asarray(y_cap), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


def test_capacity_default_drops_are_bounded():
    """With factor 2.0, dropped tokens exist but are rare (< 15%)."""
    cfg = _cfg(e=8, k=2)
    p = _params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(2), (4, 128, cfg.d_model))
    y_ref = moe.moe_apply(cfg, p, x, impl="ragged")
    y_cap = moe.moe_apply(cfg, p, x, impl="capacity")
    same = np.isclose(np.asarray(y_cap), np.asarray(y_ref),
                      rtol=2e-3, atol=2e-3).all(axis=-1)
    assert same.mean() > 0.85, f"too many dropped tokens: {same.mean()}"


def test_gather_path_matches_ragged():
    cfg = _cfg(e=8, k=2)
    p = _params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(3), (2, 8, cfg.d_model))
    y_ref = moe.moe_apply(cfg, p, x, impl="ragged")
    y_g = moe.moe_apply(cfg, p, x, impl="gather")
    np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


def test_shared_experts_added():
    cfg = _cfg(e=4, k=1, shared=1)
    p = _params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(4), (1, 8, cfg.d_model))
    y = moe.moe_apply(cfg, p, x, impl="gather")
    y_no_shared = moe.moe_apply(
        cfg, {**p, "shared": jax.tree.map(jnp.zeros_like, p["shared"])},
        x, impl="gather")
    assert not np.allclose(np.asarray(y), np.asarray(y_no_shared))


def test_capacity_gradients_flow():
    cfg = _cfg()
    p = _params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(5), (1, 32, cfg.d_model))

    def loss(pp):
        return jnp.sum(moe.moe_apply(cfg, pp, x, impl="capacity") ** 2)

    g = jax.grad(loss)(p)
    gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0


_TP_CHILD = r"""
import jax, jax.numpy as jnp, numpy as np, sys
sys.path.insert(0, %r)
from repro.models import moe
from tests.test_moe_impls import _cfg, _params

cfg = _cfg(e=8, k=2, f=32, d=16, shared=1)
p = _params(cfg, jax.random.key(0))
x = jax.random.normal(jax.random.key(1), (4, 64, cfg.d_model), jnp.float32)
y_local = moe.moe_apply(cfg, p, x, impl="gather")

from repro.compat import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
with jax.set_mesh(mesh):
    y_tp = jax.jit(lambda pp, xx: moe.moe_apply(cfg, pp, xx,
                                                impl="gather"))(p, x)
np.testing.assert_allclose(np.asarray(y_tp), np.asarray(y_local),
                           rtol=2e-3, atol=2e-3)
print("TP-MOE-OK")
"""


def test_tp_shard_map_matches_local():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + ":" + ROOT
    out = subprocess.run(
        [sys.executable, "-c",
         _TP_CHILD % (os.path.join(ROOT, "src"),)],
        capture_output=True, text=True, env=env, timeout=600,
        cwd=ROOT)
    assert "TP-MOE-OK" in out.stdout, out.stderr[-3000:]


def test_capacity_custom_vjp_matches_ragged_grads():
    """Custom-VJP capacity grads == autodiff ragged grads (ample cap)."""
    cfg = _cfg(e=4, k=2, f=16, d=8)
    p = _params(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(6), (1, 32, cfg.d_model))
    old = moe.CAPACITY_FACTOR
    moe.CAPACITY_FACTOR = 50.0
    try:
        def loss(pp, impl):
            return jnp.sum(moe.moe_apply(cfg, pp, x, impl=impl) ** 2)

        g_cap = jax.grad(lambda pp: loss(pp, "capacity"))(p)
        g_rag = jax.grad(lambda pp: loss(pp, "ragged"))(p)
    finally:
        moe.CAPACITY_FACTOR = old
    for (k1, a), (k2, b) in zip(
            jax.tree_util.tree_flatten_with_path(g_cap)[0],
            jax.tree_util.tree_flatten_with_path(g_rag)[0]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-2,
                                   err_msg=jax.tree_util.keystr(k1))

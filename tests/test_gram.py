"""Gram monitor on the comm-optimal SYRK: numerics + regime + summaries."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packing import unpack_tril
from repro.optim.gram import (GramMonitor, packed_add_diag, packed_gram,
                              whitening_factor, whitening_from_packed)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_packed_gram_matches_dense():
    x = jax.random.normal(jax.random.key(0), (12, 64))
    g = packed_gram(x)
    dense = unpack_tril(g, 12, diag=True, symmetric=True)
    want = np.asarray(x @ x.T) / 64
    np.testing.assert_allclose(np.asarray(dense), want, rtol=1e-5,
                               atol=1e-5)


def test_monitor_ema_and_summaries():
    mon = GramMonitor(decay=0.5)
    k = jax.random.key(1)
    for i in range(4):
        x = jax.random.normal(jax.random.fold_in(k, i), (8, 32))
        mon.update("layer0", x)
    s = mon.summaries("layer0")
    assert s["trace"] > 0 and s["fro"] > 0
    assert 1.0 <= s["effective_rank"] <= 8.0
    assert s["packed_words"] == 36 and s["dense_words"] == 64
    assert mon.regime("layer0", n_tokens=32, P_=2) == "case 1"


def test_whitening_factor_whitens():
    """G^{-1/2}·X has ~identity Gram."""
    x = jax.random.normal(jax.random.key(2), (6, 4096))
    mon = GramMonitor(decay=0.0)
    mon.update("l", x)
    w = whitening_factor(mon, "l")
    xw = w @ x
    gram = np.asarray(xw @ xw.T) / 4096
    np.testing.assert_allclose(gram, np.eye(6), atol=0.15)


def test_whitening_eigh_no_eps_double_count():
    """The eigh oracle computes (G + eps·I)^{-1/2} exactly: for a
    diagonal G the factor is analytic.  The old code thresholded at
    eps AND added eps inside the rsqrt (and zeroed directions the
    regularizer had just made invertible) — this pins the fix."""
    d, eps = 5, 1e-2
    evs = np.array([2.0, 1.0, 0.5, 1e-3, 0.0], np.float32)
    packed = np.zeros(d * (d + 1) // 2, np.float32)
    i = np.arange(d)
    packed[i * (i + 3) // 2] = evs
    w = np.asarray(whitening_from_packed(jnp.asarray(packed), d, eps=eps,
                                         method="eigh"))
    want = np.diag(1.0 / np.sqrt(evs + eps))
    np.testing.assert_allclose(w, want, rtol=1e-5, atol=1e-6)


def _ns_vs_eigh(d, n, eps, seed, **kw):
    x = jax.random.normal(jax.random.key(seed), (d, n))
    g = packed_gram(x)
    we = whitening_from_packed(g, d, eps=eps, method="eigh")
    wn = whitening_from_packed(g, d, eps=eps, method="ns", **kw)
    rel = float(jnp.linalg.norm(wn - we) / jnp.linalg.norm(we))
    pe = packed_add_diag(g.astype(jnp.float32), d, eps)
    evs = np.linalg.eigvalsh(np.asarray(unpack_tril(pe, d, diag=True,
                                                    symmetric=True)))
    return rel, float(evs.max() / evs.min())


def test_whitening_ns_matches_eigh_documented_tolerance():
    """The documented contract of whitening_from_packed: NS agrees with
    the eigh oracle to 1e-3 for cond <= 1e4 and 1e-2 out to ~1e6, on
    both the dense and the (interpret=True) Pallas-tiles route."""
    for kw in ({}, {"interpret": True}):
        rel, cond = _ns_vs_eigh(32, 40, 1e-3, seed=7, **kw)
        assert cond < 1e4 and rel < 1e-3, (rel, cond)
        rel, cond = _ns_vs_eigh(16, 8, 1e-5, seed=3, **kw)
        assert 1e4 < cond < 1e6 and rel < 1e-2, (rel, cond)


def test_whitening_ns_iters_stable_past_convergence():
    """The coupled iteration is a stable fixed point: extra iterations
    after convergence change nothing (the one-sided form this replaced
    diverged to NaN here)."""
    x = jax.random.normal(jax.random.key(5), (32, 40))
    g = packed_gram(x)
    w30 = whitening_from_packed(g, 32, eps=1e-3, method="ns", iters=30)
    w60 = whitening_from_packed(g, 32, eps=1e-3, method="ns", iters=60)
    assert np.all(np.isfinite(np.asarray(w60)))
    np.testing.assert_allclose(np.asarray(w30), np.asarray(w60),
                               rtol=0, atol=1e-6)


def test_whitening_ns_dense_free_on_tiles_route():
    """On the Pallas route the NS refresh never calls unpack_tril (the
    packed Gram reaches the kernel as TriTiles) and traces no eigh —
    the jaxpr-asserted dense-free contract of the serving cache."""
    import repro.core.packing as packing
    import repro.optim.gram as gm
    d = 32
    g = packed_gram(jax.random.normal(jax.random.key(0), (d, 40)))
    orig = packing.unpack_tril

    def boom(*a, **k):
        raise AssertionError("unpack_tril reached on the tiles route")
    gm.unpack_tril = packing.unpack_tril = boom
    try:
        jaxpr = jax.make_jaxpr(lambda p: gm.whitening_from_packed(
            p, d, method="ns", iters=5, interpret=True))(g)
    finally:
        gm.unpack_tril = packing.unpack_tril = orig
    assert "eigh" not in str(jaxpr)


def test_whitening_factor_bf16_state_upcast():
    """bf16 monitor state is upcast explicitly; the factor is f32 and
    still whitens."""
    x = jax.random.normal(jax.random.key(9), (8, 2048))
    mon = GramMonitor(decay=0.0, out_dtype=jnp.bfloat16)
    mon.update("l", x)
    assert mon._state["l"].dtype == jnp.bfloat16
    w = whitening_factor(mon, "l")
    assert w.dtype == jnp.float32
    xw = w @ x
    gram = np.asarray(xw @ xw.T) / 2048
    np.testing.assert_allclose(gram, np.eye(8), atol=0.2)


_DIST = r"""
import jax, jax.numpy as jnp, numpy as np, sys
sys.path.insert(0, %r)
from repro.optim.gram import packed_gram
from repro.core.packing import unpack_tril
from repro.compat import make_mesh
mesh = make_mesh((4,), ("model",))
x = jax.random.normal(jax.random.key(0), (16, 128))
g = packed_gram(x, mesh)
dense = unpack_tril(g, 16, diag=True, symmetric=True)
np.testing.assert_allclose(np.asarray(dense), np.asarray(x @ x.T) / 128,
                           rtol=1e-4, atol=1e-4)
print("GRAM-1D-OK")
"""


def test_packed_gram_distributed_1d_syrk():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", _DIST % (os.path.join(ROOT, "src"),)],
        capture_output=True, text=True, env=env, timeout=600)
    assert "GRAM-1D-OK" in out.stdout, out.stderr[-2000:]

"""Optimizer tests: AdamW numerics, Muon NS orthogonality, and equality of
the comm-optimal 1D NS vs the reference NS (checked in a subprocess with
multiple fake devices)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim import AdamW, Muon, orthogonalize_reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_adamw_reduces_quadratic():
    opt = AdamW(lr=0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([2.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.2


def test_adamw_quantized_close_to_fp32():
    k = jax.random.key(0)
    w0 = jax.random.normal(k, (64, 64))
    p1, p2 = {"w": w0}, {"w": w0}
    o1 = AdamW(lr=0.01, weight_decay=0.0)
    o2 = AdamW(lr=0.01, weight_decay=0.0, quantize_moments=True)
    s1, s2 = o1.init(p1), o2.init(p2)
    for i in range(10):
        g = {"w": jax.random.normal(jax.random.key(i), (64, 64))}
        p1, s1 = o1.update(g, s1, p1)
        p2, s2 = o2.update(g, s2, p2)
    err = float(jnp.abs(p1["w"] - p2["w"]).max())
    assert err < 0.05, err


def test_ns_orthogonalizes():
    g = jax.random.normal(jax.random.key(0), (32, 64), jnp.float32)
    sv_in = np.linalg.svd(np.asarray(g), compute_uv=False)
    assert sv_in.max() / sv_in.min() > 3  # input is NOT near-orthogonal
    o = orthogonalize_reference(g, steps=5)
    sv = np.linalg.svd(np.asarray(o), compute_uv=False)
    # Muon's quintic NS drives singular values into ~[0.68, 1.14] (it
    # deliberately overshoots for speed; it does not converge to exactly 1)
    assert sv.min() > 0.5 and sv.max() < 1.3, sv


def test_muon_step_runs():
    opt = Muon(lr=0.02, mode="reference")
    params = {"w": jax.random.normal(jax.random.key(0), (16, 32)),
              "scale": jnp.ones((8,))}
    state = opt.init(params)
    grads = jax.tree.map(jnp.ones_like, params)
    new_params, state = opt.update(grads, state, params)
    assert new_params["w"].shape == (16, 32)
    assert not np.allclose(np.asarray(new_params["w"]),
                           np.asarray(params["w"]))


def test_muon_stacked_params():
    opt = Muon(lr=0.02, mode="reference")
    params = {"periods": jax.random.normal(jax.random.key(0), (3, 16, 32))}
    state = opt.init(params)
    grads = jax.tree.map(jnp.ones_like, params)
    new_params, _ = opt.update(grads, state, params)
    assert new_params["periods"].shape == (3, 16, 32)
    # each stacked slice is orthogonalized on its own
    want = jnp.stack([orthogonalize_reference(grads["periods"][i])
                      for i in range(3)])
    np.testing.assert_allclose(
        np.asarray(params["periods"] - new_params["periods"]),
        np.asarray(0.02 * want), rtol=1e-5, atol=1e-6)


def test_1d_ns_matches_reference_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.compat import make_mesh
from repro.optim import orthogonalize_1d, orthogonalize_reference
mesh = make_mesh((4,), ("model",))
g = jax.random.normal(jax.random.key(0), (24, 64), jnp.float32)
ref = orthogonalize_reference(g, steps=5)
got = orthogonalize_1d(g, mesh, "model", steps=5)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)
print("OK muon-1d")
# stacked slices ride the batched mesh wires and match per-slice NS
gs = jax.random.normal(jax.random.key(1), (3, 2, 24, 64), jnp.float32)
want = jnp.stack([jnp.stack([orthogonalize_reference(gs[i, j], steps=5)
                             for j in range(2)]) for i in range(3)])
got = orthogonalize_reference(gs, steps=5, mesh=mesh, axis="model")
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)
print("OK muon-stacked-mesh")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK muon-1d" in out.stdout
    assert "OK muon-stacked-mesh" in out.stdout

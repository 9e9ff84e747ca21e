"""Pallas kernel tests: shape/dtype sweeps vs the pure-jnp ref.py oracles
(interpret mode executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis",
                    reason="property-based tests need the hypothesis "
                           "dev dependency (requirements-dev.txt)")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import blas
from repro.kernels.ref import symm_ref, syr2k_ref, syrk_ref

jax.config.update("jax_enable_x64", False)

SHAPES = [(16, 16), (32, 16), (16, 48), (64, 32), (48, 80)]
DTYPES = [jnp.float32, jnp.bfloat16]
#: the Pallas route of repro.blas at 16x16 tiles, kernel bodies interpreted
BLK = dict(tile=(16, 16), interpret=True)


def _rand(shape, seed, dtype):
    x = np.random.default_rng(seed).standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_syrk_kernel(shape, dtype):
    a = _rand(shape, 0, dtype)
    got = blas.syrk(a, **BLK)
    want = syrk_ref(a)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, **_tol(dtype))
    # strict upper triangle zero (packed-output contract)
    assert (np.triu(np.asarray(got, np.float32), 1) == 0).all()


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_syr2k_kernel(shape, dtype):
    a, b = _rand(shape, 1, dtype), _rand(shape, 2, dtype)
    got = blas.syr2k(a, b, **BLK)
    want = syr2k_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, **_tol(dtype))


@pytest.mark.parametrize("n1,n2", [(16, 16), (32, 48), (48, 32), (80, 16)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_symm_kernel(n1, n2, dtype):
    a = _rand((n1, n1), 3, dtype)
    b = _rand((n1, n2), 4, dtype)
    got = blas.symm(a, b, **BLK)
    want = symm_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32), want, **_tol(dtype))


def test_unaligned_shapes_padded():
    # the route pads to tile multiples and slices back
    a = _rand((20, 24), 5, jnp.float32)
    got = blas.syrk(a, **BLK)
    np.testing.assert_allclose(np.asarray(got), syrk_ref(a), rtol=2e-5,
                               atol=2e-5)
    s = _rand((20, 20), 6, jnp.float32)
    b = _rand((20, 8), 7, jnp.float32)
    np.testing.assert_allclose(np.asarray(blas.symm(s, b, **BLK)),
                               symm_ref(s, b), rtol=2e-5, atol=2e-5)


@settings(max_examples=10, deadline=None)
@given(nt=st.integers(1, 4), nk=st.integers(1, 4), seed=st.integers(0, 99))
def test_syrk_property(nt, nk, seed):
    a = _rand((nt * 16, nk * 16), seed, jnp.float32)
    got = blas.syrk(a, **BLK)
    np.testing.assert_allclose(np.asarray(got), syrk_ref(a), rtol=3e-5,
                               atol=3e-5)


def test_block_size_sweep():
    a = _rand((64, 64), 8, jnp.float32)
    want = syrk_ref(a)
    for bm, bk in [(8, 8), (16, 32), (32, 16), (64, 64)]:
        got = blas.syrk(a, tile=(bm, bk), interpret=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=3e-5,
                                   atol=3e-5)


def test_symm_reads_only_tril():
    # poison the upper triangle: result must be unchanged
    n1 = 32
    a = np.asarray(_rand((n1, n1), 9, jnp.float32)).copy()
    b = _rand((n1, 16), 10, jnp.float32)
    a_poison = a + np.triu(np.full((n1, n1), 1e6, np.float32), 1)
    got = blas.symm(jnp.asarray(a_poison), b, **BLK)
    np.testing.assert_allclose(np.asarray(got), symm_ref(jnp.asarray(a), b),
                               rtol=2e-5, atol=2e-5)

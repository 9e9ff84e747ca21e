"""repro.blas unified dispatch: routing decisions + numerics vs the
kernels/ref.py oracles (single-process paths; mesh paths run in
subprocesses via dist_checks.py so fake-device XLA flags never leak)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import blas
from repro.core.packing import tril_size
from repro.kernels.ref import symm_ref, syr2k_ref, syrk_ref

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = dict(rtol=3e-5, atol=3e-5)
BF16_TOL = dict(rtol=2e-2, atol=2e-2)


def _rand(shape, seed, dtype=jnp.float32):
    x = np.random.default_rng(seed).standard_normal(shape)
    return jnp.asarray(x.astype(np.float32), dtype=dtype)


# ---------------------------------------------------------------------------
# routing decisions (pure logic)
# ---------------------------------------------------------------------------
def test_small_shapes_route_dense():
    r = blas.plan_route("syrk", 24, 24)
    assert r.path == "dense"


def test_explicit_tile_routes_pallas():
    r = blas.plan_route("syrk", 24, 24, tile=(16, 16))
    assert r.path == "pallas" and r.tiles == (16, 16)
    r = blas.plan_route("symm", 64, 32, interpret=True)
    assert r.path == "pallas"


def test_batched_mesh_falls_back_to_dense():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    r = blas.plan_route("syrk", 16, 64, batch=True, mesh=mesh)
    assert r.path == "dense"


def test_unknown_op_rejected():
    with pytest.raises(ValueError):
        blas.plan_route("gemm", 8, 8)


def test_fill_validated():
    with pytest.raises(ValueError):
        blas.syrk(_rand((8, 8), 0), fill="upper")


# ---------------------------------------------------------------------------
# dense path numerics
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape", [(16, 16), (32, 16), (16, 48), (20, 24)])
def test_syrk_dense_matches_oracle(shape):
    a = _rand(shape, 0)
    np.testing.assert_allclose(np.asarray(blas.syrk(a)),
                               np.asarray(syrk_ref(a)), **TOL)


def test_syr2k_dense_matches_oracle():
    a, b = _rand((24, 16), 1), _rand((24, 16), 2)
    np.testing.assert_allclose(np.asarray(blas.syr2k(a, b)),
                               np.asarray(syr2k_ref(a, b)), **TOL)


def test_symm_dense_matches_oracle_and_reads_only_tril():
    s = np.asarray(_rand((20, 20), 3)).copy()
    b = _rand((20, 8), 4)
    poisoned = s + np.triu(np.full((20, 20), 1e6, np.float32), 1)
    got = blas.symm(jnp.asarray(poisoned), b)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(symm_ref(jnp.asarray(s), b)),
                               **TOL)


def test_fills_consistent():
    a = _rand((20, 24), 5)
    tril = np.asarray(blas.syrk(a, fill="tril"))
    full = np.asarray(blas.syrk(a, fill="full"))
    packed = np.asarray(blas.syrk(a, fill="packed"))
    assert packed.shape == (tril_size(20),)
    np.testing.assert_allclose(np.tril(full), tril, **TOL)
    np.testing.assert_allclose(full, full.T, **TOL)
    ii, jj = np.tril_indices(20)
    np.testing.assert_allclose(packed, tril[ii, jj], **TOL)


# ---------------------------------------------------------------------------
# dtype contract
# ---------------------------------------------------------------------------
def test_bf16_accumulates_f32_by_default():
    a = _rand((32, 64), 6, jnp.bfloat16)
    out = blas.syrk(a)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(syrk_ref(a)), **BF16_TOL)


def test_out_dtype_cast():
    a = _rand((16, 16), 7)
    assert blas.syrk(a, out_dtype=jnp.bfloat16).dtype == jnp.bfloat16
    assert blas.symm(_rand((16, 16), 8), a,
                     out_dtype=jnp.float16).dtype == jnp.float16


def test_old_ops_wrappers_preserve_f32():
    """The Pallas route keeps the f32 accumulation of bf16 inputs unless
    asked to cast (the contract the old kernel wrappers held)."""
    a = _rand((32, 16), 9, jnp.bfloat16)
    out = blas.syrk(a, tile=(16, 16), interpret=True)
    assert out.dtype == jnp.float32
    assert blas.syrk(a, tile=(16, 16), interpret=True,
                     out_dtype=jnp.bfloat16).dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# pallas path (explicit tiles force it on CPU interpret)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("op", ["syrk", "syr2k", "symm"])
def test_pallas_path_matches_oracle(op):
    a, b = _rand((48, 32), 10), _rand((48, 32), 11)
    s = _rand((48, 48), 12)
    if op == "syrk":
        got = blas.syrk(a, tile=(16, 16), interpret=True)
        want = syrk_ref(a)
    elif op == "syr2k":
        got = blas.syr2k(a, b, tile=(16, 16), interpret=True)
        want = syr2k_ref(a, b)
    else:
        got = blas.symm(s, b, tile=(16, 16), interpret=True)
        want = symm_ref(s, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# ---------------------------------------------------------------------------
# batching
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("pallas", [False, True])
def test_batched_syrk(pallas):
    kw = dict(tile=(16, 16), interpret=True) if pallas else {}
    a = _rand((3, 32, 16), 13)
    got = np.asarray(blas.syrk(a, **kw))
    want = np.stack([np.asarray(syrk_ref(x)) for x in a])
    np.testing.assert_allclose(got, want, **TOL)


def test_batched_symm_multi_leading_dims():
    s = _rand((2, 2, 16, 16), 14)
    b = _rand((2, 2, 16, 8), 15)
    got = np.asarray(blas.symm(s, b))
    want = np.stack([[np.asarray(symm_ref(s[i, j], b[i, j]))
                      for j in range(2)] for i in range(2)])
    np.testing.assert_allclose(got, want, **TOL)


def test_batch_dim_mismatch_rejected():
    with pytest.raises(ValueError):
        blas.symm(_rand((2, 16, 16), 16), _rand((3, 16, 8), 17))


def test_jit_and_vmap_compose():
    a = _rand((4, 24, 16), 18)
    f = jax.jit(jax.vmap(lambda x: blas.syrk(x, fill="full")))
    got = np.asarray(f(a))
    want = np.stack([np.asarray(x @ x.T) for x in np.asarray(a)])
    np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# autotuner cache
# ---------------------------------------------------------------------------
def test_autotune_disk_cache_roundtrip(tmp_path, monkeypatch):
    from repro.blas import autotune
    monkeypatch.setenv("REPRO_BLAS_CACHE_DIR", str(tmp_path))
    autotune.clear_cache()
    calls = []

    def runner(bm, bk):
        calls.append((bm, bk))
        blas.syrk(jnp.zeros((32, 32), jnp.float32), tile=(bm, bk),
                  interpret=True).block_until_ready()

    t1 = autotune.pick_tiles("syrk", 32, 32, "float32", "cpu",
                             mode="auto", runner=runner)
    assert calls, "measured mode must time candidates"
    on_disk = json.loads((tmp_path / "tiles.json").read_text())
    assert list(on_disk.values()) == [list(t1)]
    autotune.clear_cache()               # drop in-process, keep disk
    t2 = autotune.pick_tiles("syrk", 32, 32, "float32", "cpu",
                             mode="auto", runner=None)
    assert t2 == t1
    autotune.clear_cache(disk=True)


def test_heuristic_tiles_shrink_to_fit():
    assert blas.heuristic_tiles("syrk", 20, 24) == (32, 32)
    assert blas.heuristic_tiles("syrk", 4096, 512) == (1024, 512)


@pytest.mark.parametrize("op,n1,n2,tiles", [
    # the Newton–Schulz shapes of a 2048-wide model get the measured tile
    ("syrk", 2048, 5632, (1024, 512)),
    ("syrk", 2048, 2048, (1024, 1024)),
    ("syrk", 2048, 100352, (1024, 1024)),
    ("symm", 2048, 5632, (1024, 512)),
    ("symm", 2048, 2048, (1024, 1024)),
    ("symm", 2048, 100352, (1024, 1024)),
    # four streamed panels: SYR2K's column tile halves to fit VMEM
    ("syr2k", 2048, 5632, (1024, 512)),
    ("syr2k", 2048, 2048, (1024, 512)),
    # a side 512 does not divide pads only to the 128 rounding
    ("syrk", 640, 640, (128, 128)),
    ("symm", 640, 640, (128, 128)),
    ("syrk", 300, 200, (128, 256)),
    # one-column SYMM (the whitening server's matvec): one lane, no more
    ("symm", 2048, 1, (1024, 128)),
    ("symm", 640, 1, (128, 128)),
    # small shapes still shrink to fit
    ("syrk", 20, 24, (32, 32)),
    ("symm", 20, 5, (32, 32)),
    ("syr2k", 64, 100, (64, 128)),
])
def test_heuristic_tiles_rule(op, n1, n2, tiles):
    from repro.blas import autotune
    bm, bk = blas.heuristic_tiles(op, n1, n2)
    assert (bm, bk) == tiles
    # a side over one lane pads no further than a 128 tile would
    for n, t in ((n1, bm), (n2, bk)):
        if n > 128:
            assert -(-n // t) * t == -(-n // 128) * 128, (n, t)
    assert autotune._vmem(op, bm, bk) <= autotune.VMEM_BUDGET


@pytest.mark.parametrize("n1,n2", [(1024, 1536), (600, 1000)],
                         ids=["aligned", "padded"])
@pytest.mark.parametrize("op", ["syrk", "syr2k", "symm"])
def test_pallas_large_tile_matches_oracle(op, n1, n2):
    """(512, 512) tiles in interpret mode against the dense reference,
    on a shape the tiles divide and on one the wrapper pads."""
    tile = dict(tile=(512, 512), interpret=True)
    a, b = _rand((n1, n2), 20), _rand((n1, n2), 21)
    if op == "syrk":
        got, want = blas.syrk(a, **tile), syrk_ref(a)
    elif op == "syr2k":
        got, want = blas.syr2k(a, b, **tile), syr2k_ref(a, b)
    else:
        s = _rand((n1, n1), 22)
        got, want = blas.symm(s, b, **tile), symm_ref(s, b)
    # an n2-term f32 sum of unit-variance products: entries near zero
    # carry the rounding of terms up to sqrt(n2) in size
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-5, atol=1e-6 * n2)


# ---------------------------------------------------------------------------
# mesh routing paths (subprocess: fake devices must not leak)
# ---------------------------------------------------------------------------
def test_mesh_routes_numerics_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=12"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "dist_checks.py"),
         "--suite", "blas"],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"blas suite failed:\n{out.stdout}\n" \
                                f"{out.stderr}"
    assert "OK blas" in out.stdout

"""The packed triangle-block mesh wire (PR 4).

Single-device coverage of the new pieces — ``ShardedTriTiles`` (the
2D/3D wire format), its cached element↔(device, slot) index tables,
the one-time densify warning, and the bf16 packed Gram state — plus
the multi-device suite (`dist_checks.py --suite mesh_packed`: packed ==
dense parity on 1d/2d/3d incl. batched stacks and ragged n1, jaxpr
proofs that ``fill="packed"`` mesh routes keep the wire dense-free
forward and backward) run in a subprocess so fake-device XLA flags
never leak into this process.
"""
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import blas
from repro.blas import api
from repro.core.packing import ShardedTriTiles, TriTiles, tril_size
from repro.core.twodim import tb_flat_words, tb_pack_tables

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rand(shape, seed):
    x = np.random.default_rng(seed).standard_normal(shape)
    return jnp.asarray(x.astype(np.float32))


def _sym(s):
    return np.tril(s) + np.tril(s, -1).T


# ---------------------------------------------------------------------------
# tb_pack_tables: the element <-> (device, slot) bijection
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("c,n1", [(2, 36), (2, 34), (3, 72), (2, 7)])
def test_tb_pack_tables_bijective_and_bounded(c, n1):
    """Every element of the packed triangle maps to exactly one real
    slot of one device's extended triangle block, and no two elements
    collide — the layout really is an exact partition of the lower
    triangle across P = c(c+1) devices."""
    kidx, sidx = tb_pack_tables(c, n1)
    L = tril_size(n1)
    assert kidx.shape == sidx.shape == (L,)
    P = c * (c + 1)
    words = tb_flat_words(c, n1)
    assert kidx.min() >= 0 and kidx.max() < P
    assert sidx.min() >= 0 and sidx.max() < words
    flat = kidx.astype(np.int64) * words + sidx
    assert len(np.unique(flat)) == L, "element slots must not collide"
    # per-device ownership is balanced to ~n²/(2P) words
    counts = np.bincount(kidx, minlength=P)
    assert counts.max() <= words


def test_tb_pack_tables_cached():
    assert tb_pack_tables(2, 36)[0] is tb_pack_tables(2, 36)[0]
    with pytest.raises(ValueError):
        tb_pack_tables(2, 36)[0][0] = 1     # read-only


# ---------------------------------------------------------------------------
# ShardedTriTiles: round-trips, pytree, validation
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("c,n", [(2, 36), (2, 34), (3, 45)])
def test_sharded_tritiles_roundtrips(c, n):
    x = np.asarray(_rand((n, n), 0))
    st = ShardedTriTiles.from_tril(jnp.asarray(np.tril(x)), c)
    np.testing.assert_allclose(np.asarray(st.to_tril()), np.tril(x),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(st.to_full()), _sym(x),
                               atol=1e-6)
    p = st.to_packed()
    assert p.shape == (tril_size(n),)
    np.testing.assert_allclose(np.asarray(p),
                               np.tril(x)[np.tril_indices(n)], atol=1e-6)
    back = ShardedTriTiles.from_packed(p, n, c)
    np.testing.assert_allclose(np.asarray(back.off), np.asarray(st.off),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(back.diag), np.asarray(st.diag),
                               atol=1e-6)


@pytest.mark.parametrize("bm", [8, 16])
def test_sharded_tritiles_tritiles_interchange(bm):
    """Mesh wire <-> kernel wire without a dense detour."""
    n, c = 40, 2
    x = np.asarray(_rand((n, n), 1))
    st = ShardedTriTiles.from_tril(jnp.asarray(np.tril(x)), c)
    tt = st.to_tritiles(bm)
    assert isinstance(tt, TriTiles) and (tt.n, tt.bm) == (n, bm)
    np.testing.assert_allclose(np.asarray(tt.to_tril()), np.tril(x),
                               atol=1e-6)
    st2 = ShardedTriTiles.from_tritiles(tt, c)
    np.testing.assert_allclose(np.asarray(st2.to_packed()),
                               np.asarray(st.to_packed()), atol=1e-6)


def test_sharded_tritiles_pytree_and_astype():
    st = ShardedTriTiles.from_packed(jnp.arange(tril_size(20),
                                                dtype=jnp.float32), 20, 2)
    leaves, treedef = jax.tree_util.tree_flatten(st)
    assert len(leaves) == 2
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert (back.n, back.c) == (20, 2)
    bf = st.astype(jnp.bfloat16)
    assert bf.dtype == jnp.bfloat16 and bf.off.dtype == jnp.bfloat16


def test_sharded_tritiles_shape_validated():
    with pytest.raises(ValueError):
        ShardedTriTiles(jnp.zeros((6, 1, 5, 5)), jnp.zeros((6, 4, 4)),
                        n=20, c=2)          # diag nb mismatch


def test_sharded_tritiles_storage_approaches_half_dense():
    """The wire holds P·(T+1)·nb² -> n²/2 words as c grows (the
    diagonal-block padding overhead is an O(1/c) fraction)."""
    st = ShardedTriTiles.from_packed(jnp.zeros(tril_size(72)), 72, 3)
    wire_words = st.off.size + st.diag.size
    assert wire_words == st.num_devices * (st.T + 1) * st.nb ** 2
    assert wire_words < 0.65 * 72 * 72      # ~0.59·n² at c=3


# ---------------------------------------------------------------------------
# densify fallback: warn once, naming the route
# ---------------------------------------------------------------------------
def test_tritiles_densify_warns_once_naming_route():
    api._DENSIFY_WARNED.discard(("symm", "dense"))
    s, b = _rand((16, 16), 2), _rand((16, 4), 3)
    tt = TriTiles.from_tril(jnp.tril(s), 8)
    with pytest.warns(UserWarning, match="'dense' route"):
        blas.symm(tt, b)                    # tiny shape -> dense fallback
    with warnings.catch_warnings():
        warnings.simplefilter("error")      # second call must stay silent
        blas.symm(tt, b)


# ---------------------------------------------------------------------------
# the route table: one entry per mesh path the planner can emit
# ---------------------------------------------------------------------------
class _AxisMesh:
    """Stands in for a one-axis Mesh in routing decisions (plan_route
    reads only ``.shape``)."""

    def __init__(self, P):
        self.shape = {"x": P}


def test_route_table_has_exactly_the_planned_mesh_paths():
    from repro.blas import meshpath
    emitted = set()
    # 1d, ring, 2d, 3d, and (a small budget M) 3d-limited, batched or not
    for op in ("syrk", "syr2k", "symm"):
        for P, n1, n2, M in ((4, 16, 64, None), (8, 256, 256, None),
                             (6, 36, 6, None), (12, 24, 8, None),
                             (12, 24, 32, 60), (3, 96, 96, None),
                             (8, 64, 4096, None), (5, 24, 7, None)):
            for batch in (False, True):
                r = blas.plan_route(op, n1, n2, batch=batch,
                                    mesh=_AxisMesh(P), M=M)
                emitted.add(r.path)
    mesh_paths = emitted - {"dense", "pallas"}
    assert mesh_paths == {"1d", "ring", "2d", "3d", "3d-limited"}, emitted
    assert set(meshpath.WIRES) == mesh_paths
    # the grid families, and only they, emit the mesh-resident layout
    assert {p for p, w in meshpath.WIRES.items() if w.sharded} \
        == {"2d", "3d", "3d-limited"}


# ---------------------------------------------------------------------------
# bf16 packed Gram state (single-device side of the satellite)
# ---------------------------------------------------------------------------
def test_packed_gram_out_dtype_bf16():
    from repro.optim.gram import packed_gram
    x = _rand((12, 64), 4)
    g32 = np.asarray(packed_gram(x))
    gbf = packed_gram(x, out_dtype=jnp.bfloat16)
    assert gbf.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(gbf, np.float32), g32,
                               rtol=2e-2, atol=2e-2)
    # chunked: accumulate f32, narrow only the stored triangle
    gbf_c = packed_gram(x, chunk=16, out_dtype=jnp.bfloat16)
    assert gbf_c.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(gbf_c, np.float32), g32,
                               rtol=2e-2, atol=2e-2)


def test_gram_monitor_bf16_state_and_tritiles_exit():
    from repro.optim.gram import GramMonitor, whitening_factor
    x = _rand((8, 40), 5)
    mon32, monbf = GramMonitor(), GramMonitor(out_dtype=jnp.bfloat16)
    for m in (mon32, monbf):
        m.update("w", x)
        m.update("w", x * 0.5)
    assert monbf._state["w"].dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(monbf._state["w"], np.float32),
        np.asarray(mon32._state["w"]), rtol=2e-2, atol=2e-2)
    tt = monbf.tritiles("w", bm=8)
    assert isinstance(tt, TriTiles) and tt.dtype == jnp.bfloat16
    # summaries / whitening upcast internally and still work
    s = monbf.summaries("w")
    assert s["trace"] > 0
    w = whitening_factor(monbf, "w")
    assert w.dtype == jnp.float32 and w.shape == (8, 8)


# ---------------------------------------------------------------------------
# multi-device wire (subprocess: fake devices must not leak)
# ---------------------------------------------------------------------------
def test_mesh_packed_wire_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=12"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "dist_checks.py"),
         "--suite", "mesh_packed"],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"mesh_packed suite failed:\n{out.stdout}" \
                                f"\n{out.stderr}"
    assert "OK mesh_packed" in out.stdout

"""Packed-triangular storage as the end-to-end format: TriTiles, the
trigrid scheduler, the dense-free Pallas fill paths, and the
alpha/beta accumulate epilogue.

Covers the PR-3 contracts:
  * packed/tril/full parity across all three ops on dense vs
    pallas-interpret routes, including non-multiple-of-bm shapes
    (padding edge) and batched operands;
  * a jaxpr regression asserting the Pallas fill="packed" path contains
    no (n, n) dense intermediate (and fill="tril" nothing beyond the
    output assembly itself);
  * chunked beta=1 accumulation == one-shot on dense and pallas routes,
    with gradients through both operand and accumulator;
  * SYMM consuming a pre-packed TriTiles A (incl. gradients, which come
    back as TriTiles);
  * trigrid lookup-table caching;
  * optim.gram / optim.muon chunked-Gram parity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import blas
from repro.core.packing import (ShardedTriTiles, TriTiles, pack_tril,
                                packed_tile_indices, packed_to_tiles,
                                tile_tril_coords, tiles_to_packed,
                                tril_size, unpack_tril)
from repro.kernels import trigrid

TOL = dict(rtol=1e-4, atol=3e-5)
PALLAS = dict(tile=(16, 16), interpret=True)


def _rand(shape, seed):
    x = np.random.default_rng(seed).standard_normal(shape)
    return jnp.asarray(x.astype(np.float32))


def _sym(s):
    return np.tril(s) + np.tril(s, -1).T


def _to_fill(g, fill):
    if fill == "full":
        return _sym(np.tril(g))
    if fill == "packed":
        return g[np.tril_indices(g.shape[-1])]
    return np.tril(g)


# ---------------------------------------------------------------------------
# fill parity across routes, padding edge, batching
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n1", [48, 40])          # 40: non-multiple of bm=16
@pytest.mark.parametrize("fill", ["tril", "full", "packed"])
@pytest.mark.parametrize("route_kw", [{}, PALLAS],
                         ids=["dense", "pallas"])
def test_syrk_fill_parity(n1, fill, route_kw):
    a = _rand((n1, 32), 0)
    got = np.asarray(blas.syrk(a, fill=fill, **route_kw))
    want = _to_fill(np.asarray(a) @ np.asarray(a).T, fill)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("n1", [48, 40])
@pytest.mark.parametrize("fill", ["tril", "full", "packed"])
@pytest.mark.parametrize("route_kw", [{}, PALLAS],
                         ids=["dense", "pallas"])
def test_syr2k_fill_parity(n1, fill, route_kw):
    a, b = _rand((n1, 32), 1), _rand((n1, 32), 2)
    got = np.asarray(blas.syr2k(a, b, fill=fill, **route_kw))
    g = np.asarray(a) @ np.asarray(b).T
    np.testing.assert_allclose(got, _to_fill(g + g.T, fill), **TOL)


@pytest.mark.parametrize("n1", [48, 40])
@pytest.mark.parametrize("route_kw", [{}, PALLAS],
                         ids=["dense", "pallas"])
def test_symm_parity(n1, route_kw):
    s, b = _rand((n1, n1), 3), _rand((n1, 24), 4)
    got = np.asarray(blas.symm(s, b, **route_kw))
    np.testing.assert_allclose(got, _sym(np.asarray(s)) @ np.asarray(b),
                               **TOL)


@pytest.mark.parametrize("fill", ["tril", "full", "packed"])
def test_batched_fill_parity_pallas(fill):
    a = _rand((3, 40, 32), 5)
    got = np.asarray(blas.syrk(a, fill=fill, **PALLAS))
    want = np.stack([_to_fill(np.asarray(x) @ np.asarray(x).T, fill)
                     for x in a])
    np.testing.assert_allclose(got, want, **TOL)


# ---------------------------------------------------------------------------
# jaxpr regression: packed pallas path is dense-free
# ---------------------------------------------------------------------------
#: call wrappers re-emit their inner jaxpr's outputs — counting them
#: would double-count a single materialization
_WRAPPER_PRIMS = ("custom_vjp", "custom_jvp", "pjit", "closed_call",
                  "core_call", "remat")


def _square_vars(jaxpr, n):
    """All *producing* eqn output shapes in (closed) jaxpr whose
    trailing dims are (n, n), recursing into sub-jaxprs (custom_vjp
    bodies, pallas_call kernels, ...); call-wrapper primitives are
    skipped (their inner eqns are still walked)."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if not any(w in name for w in _WRAPPER_PRIMS):
                for v in eqn.outvars:
                    sh = tuple(getattr(v.aval, "shape", ()))
                    if len(sh) >= 2 and sh[-1] == n and sh[-2] == n:
                        found.append((name, sh))
            for val in eqn.params.values():
                if hasattr(val, "jaxpr"):
                    walk(val.jaxpr)
                elif hasattr(val, "eqns"):
                    walk(val)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("n1", [48, 40])
@pytest.mark.parametrize("op", ["syrk", "syr2k"])
def test_pallas_packed_path_has_no_dense_intermediate(op, n1):
    a = jnp.zeros((n1, 32), jnp.float32)
    if op == "syrk":
        fn = lambda x: blas.syrk(x, fill="packed", **PALLAS)  # noqa: E731
        jx = jax.make_jaxpr(fn)(a)
    else:
        fn = lambda x, y: blas.syr2k(x, y, fill="packed",   # noqa: E731
                                     **PALLAS)
        jx = jax.make_jaxpr(fn)(a, a)
    npad = -(-n1 // 16) * 16
    for n in {n1, npad}:
        sq = _square_vars(jx, n)
        assert not sq, f"dense ({n},{n}) intermediates on packed path: {sq}"


def test_pallas_tril_path_only_materializes_the_output():
    """tril output is (n, n) by definition, but the executor must not
    build anything square beyond the output assembly + final slice."""
    n1 = 40
    npad = 48
    a = jnp.zeros((n1, 32), jnp.float32)
    jx = jax.make_jaxpr(lambda x: blas.syrk(x, fill="tril", **PALLAS))(a)
    sq = _square_vars(jx, n1) + _square_vars(jx, npad)
    assert len(sq) <= 2, f"extra dense intermediates on tril path: {sq}"


def test_symm_tritiles_pallas_path_has_no_dense_intermediate():
    n1 = 48
    tt = TriTiles.from_packed(jnp.zeros(tril_size(n1), jnp.float32), n1, 16)
    b = jnp.zeros((n1, 32), jnp.float32)
    jx = jax.make_jaxpr(
        lambda t, y: blas.symm(TriTiles(t, n1, 16), y, **PALLAS))(
            tt.tiles, b)
    sq = _square_vars(jx, n1)
    assert not sq, f"TriTiles symm densified: {sq}"


def test_packed_grad_stays_packed_on_pallas_route():
    """The backward of a packed-fill Pallas SYRK must plan a Pallas SYMM
    (packed cotangent -> TriTiles -> packed-operand kernel) and its
    trace must stay free of (n, n) dense intermediates."""
    a = _rand((48, 32), 6)
    with blas.capture_routes() as log:
        jax.grad(lambda x: blas.syrk(x, fill="packed", **PALLAS).sum())(a)
    assert ("symm", "pallas") in [(r.op, r.path) for r in log]
    jx = jax.make_jaxpr(jax.grad(
        lambda x: blas.syrk(x, fill="packed", **PALLAS).sum()))(a)
    assert not _square_vars(jx, 48)


# ---------------------------------------------------------------------------
# TriTiles: round-trips and SYMM consumption
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [48, 40])
def test_tritiles_roundtrips(n):
    x = np.asarray(_rand((n, n), 7))
    tt = TriTiles.from_tril(jnp.asarray(np.tril(x)), 16)
    np.testing.assert_allclose(np.asarray(tt.to_tril()), np.tril(x),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(tt.to_full()), _sym(x), atol=1e-6)
    p = tt.to_packed()
    assert p.shape == (tril_size(n),)
    np.testing.assert_allclose(
        np.asarray(TriTiles.from_packed(p, n, 16).tiles),
        np.asarray(tt.tiles), atol=1e-6)
    # element<->tile tables agree with the dense definition
    np.testing.assert_allclose(np.asarray(p), np.tril(x)[np.tril_indices(n)],
                               atol=1e-6)


def test_tritiles_batched_and_pytree():
    x = _rand((2, 3, 32, 32), 8)
    tt = TriTiles.from_tril(jnp.tril(x), 16)
    assert tt.batch_shape == (2, 3)
    leaves, treedef = jax.tree_util.tree_flatten(tt)
    assert len(leaves) == 1
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert back.n == tt.n and back.bm == tt.bm
    np.testing.assert_allclose(np.asarray(tt.to_tril()),
                               np.tril(np.asarray(x)), atol=1e-6)


def test_tritiles_shape_validated():
    with pytest.raises(ValueError):
        TriTiles(jnp.zeros((3, 16, 16)), n=48, bm=16)   # needs T=6


@pytest.mark.parametrize("n1", [48, 40])
@pytest.mark.parametrize("route_kw", [{}, PALLAS],
                         ids=["dense", "pallas"])
def test_symm_accepts_tritiles(n1, route_kw):
    s, b = _rand((n1, n1), 9), _rand((n1, 24), 10)
    tt = TriTiles.from_tril(jnp.tril(s), 16)
    got = np.asarray(blas.symm(tt, b, **route_kw))
    np.testing.assert_allclose(got, _sym(np.asarray(s)) @ np.asarray(b),
                               **TOL)


def test_symm_tritiles_batched_pallas():
    s, b = _rand((3, 32, 32), 11), _rand((3, 32, 8), 12)
    tt = TriTiles.from_tril(jnp.tril(s), 16)
    got = np.asarray(blas.symm(tt, b, **PALLAS))
    want = np.stack([_sym(np.asarray(s[i])) @ np.asarray(b[i])
                     for i in range(3)])
    np.testing.assert_allclose(got, want, **TOL)


def test_symm_tritiles_grad_comes_back_as_tritiles():
    s, b = _rand((40, 40), 13), _rand((40, 24), 14)
    tt = TriTiles.from_tril(jnp.tril(s), 16)

    def loss(tiles, y):
        return jnp.sum(jnp.cos(blas.symm(TriTiles(tiles, 40, 16), y,
                                         **PALLAS)))

    gt, gb = jax.grad(loss, argnums=(0, 1))(tt.tiles, b)
    ref = jax.grad(
        lambda sd, y: jnp.sum(jnp.cos((jnp.tril(sd)
                                       + jnp.tril(sd, -1).T) @ y)),
        argnums=(0, 1))(jnp.tril(s), b)
    np.testing.assert_allclose(np.asarray(TriTiles(gt, 40, 16).to_tril()),
                               np.asarray(jnp.tril(ref[0])), **TOL)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(ref[1]), **TOL)


def test_symm_tritiles_shape_mismatch_rejected():
    tt = TriTiles.from_packed(jnp.zeros(tril_size(32)), 32, 16)
    with pytest.raises(ValueError):
        blas.symm(tt, jnp.zeros((48, 8)))


# ---------------------------------------------------------------------------
# alpha/beta accumulate epilogue
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fill", ["tril", "full", "packed"])
@pytest.mark.parametrize("route_kw", [{}, PALLAS],
                         ids=["dense", "pallas"])
def test_syrk_chunked_accumulation_matches_one_shot(fill, route_kw):
    """The acceptance contract: syrk(..., beta=1.0, c=prev) chunked over
    the contraction axis equals a one-shot SYRK to f32 tolerance."""
    x = _rand((40, 64), 15)
    one = np.asarray(blas.syrk(x, fill=fill, **route_kw))
    acc = None
    for i in range(4):
        acc = blas.syrk(x[:, i * 16:(i + 1) * 16], fill=fill, c=acc,
                        beta=None if acc is None else 1.0, **route_kw)
    np.testing.assert_allclose(np.asarray(acc), one, **TOL)


@pytest.mark.parametrize("route_kw", [{}, PALLAS],
                         ids=["dense", "pallas"])
def test_syr2k_chunked_accumulation_matches_one_shot(route_kw):
    x, y = _rand((32, 32), 16), _rand((32, 32), 17)
    one = np.asarray(blas.syr2k(x, y, fill="packed", **route_kw))
    acc = None
    for i in range(2):
        sl = slice(i * 16, (i + 1) * 16)
        acc = blas.syr2k(x[:, sl], y[:, sl], fill="packed", c=acc,
                         **route_kw)
    np.testing.assert_allclose(np.asarray(acc), one, **TOL)


def test_alpha_beta_scaling():
    x = _rand((24, 24), 18)
    c = _rand((24, 24), 19)
    c = c + c.T
    got = blas.syrk(x, fill="full", c=c, alpha=2.0, beta=0.5)
    want = 2 * np.asarray(x) @ np.asarray(x).T + 0.5 * np.asarray(c)
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


def test_accumulator_validation():
    x = _rand((16, 16), 20)
    with pytest.raises(ValueError):       # beta without c
        blas.syrk(x, beta=1.0)
    with pytest.raises(ValueError):       # wrong c shape for fill
        blas.syrk(x, fill="packed", c=jnp.zeros((16, 16)))


@pytest.mark.parametrize("route_kw", [{}, PALLAS],
                         ids=["dense", "pallas"])
def test_grad_through_accumulator(route_kw):
    x = _rand((24, 16), 21)
    cp = _rand((tril_size(24),), 22)

    def loss(xa, ca):
        return jnp.sum(jnp.sin(blas.syrk(xa, fill="packed", c=ca,
                                         **route_kw)))

    def ref(xa, ca):
        return jnp.sum(jnp.sin((xa @ xa.T)[jnp.tril_indices(24)] + ca))

    got = jax.grad(loss, argnums=(0, 1))(x, cp)
    want = jax.grad(ref, argnums=(0, 1))(x, cp)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **TOL)


@pytest.mark.parametrize("fill", ["tril", "full", "packed"])
def test_out_dtype_cast_runs_in_kernel_on_pallas(fill):
    """The epilogue casts in-kernel: the pallas_call output aval must
    already be bf16 (f32 tiles never hit HBM), and numerics must match
    the f32 result to bf16 tolerance."""
    x = _rand((32, 32), 26)
    got = blas.syrk(x, fill=fill, out_dtype=jnp.bfloat16, **PALLAS)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(blas.syrk(x, fill=fill, **PALLAS))
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2e-2, atol=2e-2)

    jx = jax.make_jaxpr(
        lambda t: blas.syrk(t, fill=fill, out_dtype=jnp.bfloat16,
                            **PALLAS))(x)
    pallas_out_dtypes = []

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                pallas_out_dtypes.extend(v.aval.dtype
                                         for v in eqn.outvars)
            for val in eqn.params.values():
                if hasattr(val, "jaxpr"):
                    walk(val.jaxpr)
                elif hasattr(val, "eqns"):
                    walk(val)

    walk(jx.jaxpr)
    assert pallas_out_dtypes and all(d == jnp.bfloat16
                                     for d in pallas_out_dtypes)


def test_from_tril_does_not_propagate_upper_nans():
    """'tril-valid' means the upper half may hold garbage — including
    NaN/inf, which a multiplicative mask would leak (0·NaN = NaN)."""
    x = np.asarray(_rand((40, 40), 27))
    poisoned = np.tril(x) + np.triu(np.full((40, 40), np.nan), 1)
    tt = TriTiles.from_tril(jnp.asarray(poisoned), 16)
    np.testing.assert_allclose(np.asarray(tt.to_tril()), np.tril(x),
                               atol=1e-6)
    assert not np.isnan(np.asarray(tt.to_full())).any()


# ---------------------------------------------------------------------------
# trigrid scheduler: shared tables, cached construction
# ---------------------------------------------------------------------------
def test_trigrid_tables_are_cached():
    assert trigrid.tri_coords(7)[0] is trigrid.tri_coords(7)[0]
    assert trigrid.symm_lookup(7)[0] is trigrid.symm_lookup(7)[0]
    assert tile_tril_coords(7) is tile_tril_coords(7)
    imap, jmap = trigrid.tri_coords(3)
    np.testing.assert_array_equal(imap, [0, 1, 1, 2, 2, 2])
    np.testing.assert_array_equal(jmap, [0, 0, 1, 0, 1, 2])


def test_trigrid_tables_read_only():
    imap, _ = trigrid.tri_coords(4)
    with pytest.raises(ValueError):
        imap[0] = 5


def test_packed_tile_index_tables_invert():
    p = np.arange(tril_size(40), dtype=np.float32)
    tiles = packed_to_tiles(jnp.asarray(p), 40, 16)
    back = tiles_to_packed(tiles, 40)
    np.testing.assert_array_equal(np.asarray(back), p)


# ---------------------------------------------------------------------------
# slice-granular converters: bit-for-bit vs the element-table reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [33, 40, 48])     # 33/40: ragged vs bm=16
def test_pack_unpack_match_element_reference(n):
    x = np.asarray(_rand((n, n), 30))
    i, j = np.tril_indices(n)
    p_ref = x[i, j]
    np.testing.assert_array_equal(np.asarray(pack_tril(jnp.asarray(x))),
                                  p_ref)
    full = np.zeros((n, n), np.float32)
    full[i, j] = p_ref
    np.testing.assert_array_equal(
        np.asarray(unpack_tril(jnp.asarray(p_ref), n, symmetric=False)),
        full)
    np.testing.assert_array_equal(
        np.asarray(unpack_tril(jnp.asarray(p_ref), n, symmetric=True)),
        full + full.T - np.diag(np.diag(full)))


def _tile_converter_cases():
    """(n, bm, batch, fill): bm = 16 unrolls into static slices, bm = 2
    (T > STATIC_TILE_MAX) takes the gather; ragged n against both."""
    seed = [(n, 16, (), "full") for n in (33, 40, 48)]
    for n in (33, 40, 48):
        yield pytest.param(n, 16, (), "full", id=str(n))
    grids = [(33, 16, "static-ragged33"), (40, 16, "static-ragged40"),
             (48, 16, "static"), (41, 16, "static-ragged41"),
             (48, 2, "gather"), (41, 2, "gather-ragged")]
    batches = [((), "unbatched"), ((3,), "batch1"), ((2, 3), "batch2")]
    for n, bm, grid in grids:
        for fill in ("tril", "full"):
            for batch, bid in batches:
                if (n, bm, batch, fill) not in seed:
                    yield pytest.param(n, bm, batch, fill,
                                       id=f"{grid}-{fill}-{bid}")


@pytest.mark.parametrize("n,bm,batch,fill", list(_tile_converter_cases()))
def test_tile_converters_match_element_reference(n, bm, batch, fill):
    """packed<->tiles and tiles<->dense (the Pallas route's fill glue and
    the TriTiles dense entry and exits) must agree bit-for-bit with the
    (kept, reference) per-element tables on ragged n, including zeroed
    padding slots, on both sides of the static-slice / gather choice and
    under leading batch dims; ``from_tril`` drops a NaN upper half."""
    from repro.blas.api import _fill_to_tiles, _tiles_to_fill
    from repro.core.packing import _unrolls, pack_tril_tiles, pad2d
    nt = -(-n // bm)
    assert _unrolls(nt, bm) == (bm == 16), (nt, bm)
    p = np.asarray(_rand(batch + (tril_size(n),), 31))
    tidx, ridx, cidx = packed_tile_indices(n, bm)
    ref = np.zeros(batch + (nt * (nt + 1) // 2, bm, bm), np.float32)
    ref[..., tidx, ridx, cidx] = p
    got = np.asarray(packed_to_tiles(jnp.asarray(p), n, bm))
    np.testing.assert_array_equal(got, ref)
    np.testing.assert_array_equal(
        np.asarray(tiles_to_packed(jnp.asarray(ref), n)), p)

    i, j = np.tril_indices(n)
    tril = np.zeros(batch + (n, n), np.float32)
    tril[..., i, j] = p
    full = tril.copy()
    full[..., j, i] = p
    want = full if fill == "full" else tril
    tiles = jnp.asarray(ref)
    np.testing.assert_array_equal(
        np.asarray(_tiles_to_fill(tiles, n, bm, fill)), want)
    tt = TriTiles(tiles, n, bm)
    np.testing.assert_array_equal(
        np.asarray(tt.to_full() if fill == "full" else tt.to_tril()), want)
    # the route applies the fill glue per matrix, vmapped over the stack
    flat = jnp.asarray(tril).reshape((-1, n, n))
    np.testing.assert_array_equal(np.asarray(jax.vmap(
        lambda x: _fill_to_tiles(x, n, bm, "tril"))(flat)).reshape(ref.shape),
        ref)
    padded = jax.vmap(lambda x: pad2d(x, bm, bm))(flat)
    np.testing.assert_array_equal(
        np.asarray(pack_tril_tiles(padded, bm)).reshape(ref.shape), ref)
    poisoned = tril + np.triu(np.full((n, n), np.nan, np.float32), 1) \
        if fill == "tril" else full
    back = TriTiles.from_tril(jnp.asarray(poisoned), bm)
    np.testing.assert_array_equal(np.asarray(back.tiles), ref)
    np.testing.assert_array_equal(
        np.asarray(back.to_full() if fill == "full" else back.to_tril()),
        want)


def test_converters_batched_match_element_reference():
    xb = np.asarray(_rand((2, 3, 40, 40), 32))
    i, j = np.tril_indices(40)
    pb = np.asarray(pack_tril(jnp.asarray(xb)))
    np.testing.assert_array_equal(pb, xb[..., i, j])
    tb = packed_to_tiles(jnp.asarray(pb), 40, 16)
    assert tb.shape == (2, 3, 6, 16, 16)
    np.testing.assert_array_equal(np.asarray(tiles_to_packed(tb, 40)), pb)
    ub = np.asarray(unpack_tril(jnp.asarray(pb), 40, symmetric=False))
    want = np.zeros_like(xb)
    want[..., i, j] = pb
    np.testing.assert_array_equal(ub, want)


@pytest.mark.parametrize("c,n", [(2, 36), (2, 9), (3, 100)])
def test_sharded_tritiles_matches_element_reference(c, n):
    """The block-granular ShardedTriTiles converters must reproduce the
    element-table tb_pack_tables layout exactly (incl. n not a multiple
    of the block grid and devices that own no diagonal block)."""
    from repro.core.twodim import tb_flat_words, tb_pack_tables
    p = np.asarray(_rand((tril_size(n),), 33))
    st = ShardedTriTiles.from_packed(jnp.asarray(p), n, c)
    np.testing.assert_array_equal(np.asarray(st.to_packed()), p)
    kidx, sidx = tb_pack_tables(c, n)
    Pn, T, nb = c * (c + 1), c * (c - 1) // 2, -(-n // (c * c))
    flat = np.zeros((Pn, tb_flat_words(c, n)), np.float32)
    flat[kidx, sidx] = p
    np.testing.assert_array_equal(
        np.asarray(st.off), flat[:, :T * nb * nb].reshape(Pn, T, nb, nb))
    np.testing.assert_array_equal(
        np.asarray(st.diag), flat[:, T * nb * nb:].reshape(Pn, nb, nb))


# ---------------------------------------------------------------------------
# jaxpr regression: converters and packed backward are slice-granular
# (no element-granular gather/scatter; tile/row-axis indexing only)
# ---------------------------------------------------------------------------
def _indexed_ops(jx):
    """(primitive, index_rows) for every gather/scatter in the jaxpr
    tree — ``index_rows`` is the number of independent start positions,
    i.e. the scatter/gather granularity (an element-granular op has one
    row per element; slice-granular ops have one per matrix/tile row)."""
    found = []

    def walk(j):
        for eqn in j.eqns:
            nm = eqn.primitive.name
            if nm == "gather" or nm.startswith("scatter"):
                idx_shape = tuple(eqn.invars[1].aval.shape)
                rows = int(np.prod(idx_shape[:-1])) if idx_shape else 1
                found.append((nm, rows))
            for val in eqn.params.values():
                if hasattr(val, "jaxpr"):
                    walk(val.jaxpr)
                elif hasattr(val, "eqns"):
                    walk(val)

    walk(jx.jaxpr)
    return found


def _max_slice_rows(n1, bm=16):
    """Slice-granular ceiling: one index row per (tile, intra-tile row)
    — far below the tril_size(n1) element count."""
    nt = -(-n1 // bm)
    return max(n1, nt * (nt + 1) // 2 * bm)


@pytest.mark.parametrize("n", [40, 48])
def test_converter_jaxprs_are_slice_granular(n):
    L = tril_size(n)
    p = jnp.zeros(L, jnp.float32)
    x = jnp.zeros((n, n), jnp.float32)
    cap = _max_slice_rows(n)
    assert cap < L / 4          # the bound actually separates the two
    fns = [
        (lambda v: pack_tril(v), x),
        (lambda v: unpack_tril(v, n, symmetric=True), p),
        (lambda v: packed_to_tiles(v, n, 16), p),
        (lambda v: tiles_to_packed(packed_to_tiles(v, n, 16), n), p),
        (jax.grad(lambda v: pack_tril(v).sum()), x),
        (jax.grad(lambda v: unpack_tril(v, n).sum()), p),
        (jax.grad(lambda v: packed_to_tiles(v, n, 16).sum()), p),
    ]
    for fn, arg in fns:
        ops = _indexed_ops(jax.make_jaxpr(fn)(arg))
        bad = [(nm, r) for nm, r in ops if r > cap]
        assert not bad, f"element-granular indexing: {bad}"


@pytest.mark.parametrize("route_kw", [{}, PALLAS],
                         ids=["dense", "pallas"])
@pytest.mark.parametrize("op", ["syrk", "syr2k"])
def test_packed_backward_jaxpr_is_scatter_free(op, route_kw):
    """The PR-5 acceptance: the packed backward trace contains no
    scatter with O(n²) index rows on ANY route (the dense route's
    pack/unpack and the Pallas route's tile converters are all
    slice-granular now)."""
    n1 = 48
    a = jnp.zeros((n1, 32), jnp.float32)
    if op == "syrk":
        fn = jax.grad(lambda x: blas.syrk(x, fill="packed",
                                          **route_kw).sum())
        jx = jax.make_jaxpr(fn)(a)
    else:
        fn = jax.grad(lambda x, y: blas.syr2k(x, y, fill="packed",
                                              **route_kw).sum())
        jx = jax.make_jaxpr(fn)(a, a)
    cap = _max_slice_rows(n1)
    bad = [(nm, r) for nm, r in _indexed_ops(jx)
           if nm.startswith("scatter") and r > cap]
    assert not bad, f"element-granular scatter in packed backward: {bad}"


def test_symm_tritiles_backward_jaxpr_is_scatter_free():
    n1 = 48
    tt = TriTiles.from_packed(jnp.zeros(tril_size(n1), jnp.float32),
                              n1, 16)
    b = jnp.zeros((n1, 32), jnp.float32)
    jx = jax.make_jaxpr(jax.grad(
        lambda t, y: blas.symm(TriTiles(t, n1, 16), y,
                               **PALLAS).sum(), argnums=(0, 1)))(
        tt.tiles, b)
    cap = _max_slice_rows(n1)
    bad = [(nm, r) for nm, r in _indexed_ops(jx)
           if nm.startswith("scatter") and r > cap]
    assert not bad, f"element-granular scatter in TriTiles symm bwd: {bad}"
    assert not _square_vars(jx, n1)     # and still no dense intermediate


# ---------------------------------------------------------------------------
# fused cotangent prologue: pallas-route grads == dense-route grads
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n1", [48, 40])
def test_fused_prologue_syrk_grad_matches_dense_route(n1):
    a = _rand((n1, 32), 34)
    gp = jax.grad(lambda x: jnp.sum(jnp.sin(
        blas.syrk(x, fill="packed", **PALLAS))))(a)
    gd = jax.grad(lambda x: jnp.sum(jnp.sin(
        blas.syrk(x, fill="packed"))))(a)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gd), **TOL)


@pytest.mark.parametrize("n1", [48, 40])
def test_fused_prologue_syr2k_grad_matches_dense_route(n1):
    a, b = _rand((n1, 32), 35), _rand((n1, 32), 36)
    loss = lambda kw: jax.grad(                            # noqa: E731
        lambda x, y: jnp.sum(jnp.cos(blas.syr2k(x, y, fill="packed",
                                                **kw))),
        argnums=(0, 1))(a, b)
    for g, w in zip(loss(PALLAS), loss({})):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **TOL)


def test_fused_prologue_symm_grad_matches_dense_route(n1=40):
    """SYMM's dA rides a packed SYR2K whose diagonal halving is the
    fused kernel epilogue on the Pallas route — grads must match the
    dense route bit-for-tolerance on both operands."""
    s, b = _rand((n1, n1), 37), _rand((n1, 24), 38)
    tt = TriTiles.from_tril(jnp.tril(s), 16)

    def grads(kw):
        return jax.grad(
            lambda t, y: jnp.sum(jnp.cos(blas.symm(TriTiles(t, n1, 16), y,
                                                   **kw))),
            argnums=(0, 1))(tt.tiles, b)

    for g, w in zip(grads(PALLAS), grads({})):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), **TOL)


def test_packed_diag_scale_mask_keeps_cotangent_dtype():
    from repro.blas.grad import _packed_diag_scale
    m = _packed_diag_scale(8, 2.0, jnp.bfloat16)
    assert m.dtype == jnp.dtype(jnp.bfloat16)
    assert _packed_diag_scale(8, 0.5).dtype == np.float32
    g = jnp.ones(tril_size(8), jnp.bfloat16)
    assert (g * jnp.asarray(m)).dtype == jnp.dtype(jnp.bfloat16)


# ---------------------------------------------------------------------------
# consumers: chunked Grams in optim
# ---------------------------------------------------------------------------
def test_packed_gram_chunked_matches_one_shot():
    from repro.optim.gram import packed_gram
    x = _rand((12, 64), 23)
    one = np.asarray(packed_gram(x))
    np.testing.assert_allclose(np.asarray(packed_gram(x, chunk=16)), one,
                               **TOL)
    np.testing.assert_allclose(np.asarray(packed_gram(x, chunk=100)), one,
                               **TOL)


def test_gram_monitor_chunked():
    from repro.optim.gram import GramMonitor
    x = _rand((8, 40), 24)
    m_one, m_chunk = GramMonitor(), GramMonitor(chunk=10)
    m_one.update("w", x)
    m_chunk.update("w", x)
    np.testing.assert_allclose(np.asarray(m_chunk._state["w"]),
                               np.asarray(m_one._state["w"]), **TOL)


def test_muon_ns_gram_chunked_matches():
    from repro.optim.muon import ns_iteration_reference
    x = _rand((12, 48), 25)
    one = np.asarray(ns_iteration_reference(x))
    got = np.asarray(ns_iteration_reference(x, gram_chunk=16))
    np.testing.assert_allclose(got, one, rtol=2e-4, atol=2e-4)

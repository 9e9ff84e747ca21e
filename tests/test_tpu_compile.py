"""Ahead-of-time compiles of the Pallas symmetric kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, so each
test compiles the routed ``repro.blas`` call for one chip of a described
``v5e:2x2`` at stablelm-1.6b's d_ff weight shape (the Newton–Schulz Gram
and update of a 2048x5632 weight) and checks that the program carries
the Mosaic kernel: at 128 tiles, and at the tiles ``heuristic_tiles``
picks, there and at the embedding's 2048x100352.  That catches what interpret mode cannot: block
shapes the TPU tiling refuses, VMEM overflow, unsupported ops.  Nothing
runs, so results and times are not checked here.

The module holds libtpu's compiler in its process; it is kept on one
xdist worker (the ``xdist_group`` mark under ``--dist loadgroup``, and
the module itself under ``--dist loadfile``).
"""
import jax
import jax.numpy as jnp
import pytest

from repro import blas

N1, N2 = 2048, 5632
KERNEL = dict(tile=(128, 128), interpret=False)

pytestmark = pytest.mark.xdist_group("libtpu")


def _ops(kernel):
    return {
        "syrk": (lambda a: blas.syrk(a, fill="full", **kernel), ("a",)),
        "syr2k": (lambda a, b: blas.syr2k(a, b, fill="full", **kernel),
                  ("a", "b")),
        "symm": (lambda s, b: blas.symm(s, b, **kernel), ("s", "b")),
    }


OPS = _ops(KERNEL)


@pytest.fixture(scope="module")
def topo():
    from jax._src import xla_bridge
    from jax.experimental import topologies
    if xla_bridge.get_tpu_library_path() is None:
        pytest.skip("no TPU compiler (libtpu) is installed")
    # compiling needs no chip, so another process holding libtpu's lock
    # (a chip job, a second test run) must not stop this one
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, ops, op, mode, dtype, n1, n2):
    call, names = ops[op]
    shapes = {"a": (n1, n2), "b": (n1, n2), "s": (n1, n1)}
    args = [jax.ShapeDtypeStruct(shapes[n], dtype, sharding=one_chip)
            for n in names]
    fn = call if mode == "fwd" else jax.grad(
        lambda *xs: jnp.sum(call(*xs)), tuple(range(len(names))))
    with blas.capture_routes() as log:
        lowered = jax.jit(fn).lower(*args)
    assert log and all(r.path == "pallas" for r in log), log
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    return log


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("mode", ["fwd", "grad"])
@pytest.mark.parametrize("op", sorted(OPS))
def test_kernel_compiles_for_v5e(one_chip, op, mode, dtype):
    _compile(one_chip, OPS, op, mode, dtype, N1, N2)


# the tiles the router picks on a TPU: large enough to need more than
# the default scoped VMEM, which the kernels then ask for
HEURISTIC_CASES = [(op, mode, dtype, N1, N2) for op in sorted(OPS)
                   for mode in ("fwd", "grad")
                   for dtype in (jnp.bfloat16, jnp.float32)] + \
    [(op, mode, jnp.float32, N1, 100352) for op in ("symm", "syrk")
     for mode in ("fwd", "grad")]


@pytest.mark.parametrize(
    "op,mode,dtype,n1,n2", HEURISTIC_CASES,
    ids=[f"{op}-{mode}-{jnp.dtype(dt).name}-{n1}x{n2}"
         for op, mode, dt, n1, n2 in HEURISTIC_CASES])
def test_kernel_compiles_for_v5e_at_heuristic_tiles(one_chip, op, mode,
                                                    dtype, n1, n2):
    tile = blas.heuristic_tiles(op, n1, n2)
    log = _compile(one_chip, _ops(dict(tile=tile, interpret=False)), op,
                   mode, dtype, n1, n2)
    assert log[0].tiles == tile, log


#: the ``bytes_limit`` a v5e's runtime reports for one chip
V5E_BYTES_LIMIT = 16_909_334_528


def test_mesh_cell_step_fits_a_v5e_2x2(topo, one_chip, monkeypatch):
    """The benchmark's ``pixtral-12b.muon-tp4`` train step, at published
    widths on a (data 1, model 4) mesh of the described chips, compiles
    with its NS products on the mesh wires (no Pallas kernel: one
    cannot be partitioned) and within one chip's memory, arguments and
    temporaries together, and every op it runs carries a scope.
    ``one_chip`` keeps the compile out of the persistent cache."""
    import pathlib

    import numpy as np
    from jax.sharding import Mesh
    repo = pathlib.Path(__file__).resolve().parents[1]
    monkeypatch.syspath_prepend(str(repo))
    from bench import spec
    cell = spec.load_cell(repo, "pixtral-12b.muon-tp4")
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # the program picks its kernels by backend: plan them for the TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step = cell.driver().Step(cell, mesh=mesh)

    def sds(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    job = cell.traffic
    batch = {k: jax.ShapeDtypeStruct((job["global_batch"], job["seq_len"]),
                                     jnp.int32, sharding=step.b_sh[k])
             for k in ("tokens", "labels")}
    with jax.set_mesh(mesh), blas.capture_routes() as log:
        lowered = step.jit_step.lower(sds(step.shape, step.p_sh),
                                      sds(step.state_shape, step.o_sh),
                                      batch)
    paths = {r.path for r in log}
    assert {"ring", "1d"} <= paths and "pallas" not in paths, paths
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    ma = compiled.memory_analysis()
    per_chip = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert per_chip < V5E_BYTES_LIMIT, per_chip
    # the traced run's breakdown finds a scope for every op that runs,
    # so its join does not refuse the mesh cell
    from bench import scopes
    hlo = scopes.hlo_ops(text)
    unscoped = [n for n in hlo.top if hlo.ops[n][0] not in
                ("parameter", "constant", "get-tuple-element", "tuple")
                and scopes.bucket(hlo.ops[n][1]) == "unattributed"]
    assert not unscoped, unscoped[:10]


#: one stacked MLP leaf of ``pixtral-12b.muon-tp4``'s Newton–Schulz
#: chain: ten 5120x14336 weights, their 5120x5120 Grams
RING_K, RING_N1, RING_N2 = 10, 5120, 14336


@pytest.mark.parametrize("op", ["syrk", "symm"])
def test_ring_dense_converters_have_no_loops_on_v5e_2x2(topo, one_chip, op):
    """The NS chain's dense ring calls — ``syrk(fill="full")`` for the
    Gram and dense-A ``symm`` for S·S — compiled for a (1, 4) mesh of
    the described chips at one MLP leaf's stacked shape, route to
    ``ring`` and convert between dense S and the slot stack with no
    serial loop: no ``while`` or ``scatter`` op lies under their scope.
    (The element-packed round trip lowers its slice-granular gathers
    and scatters to loops on the TPU: 22 under ``blas.syrk.ring``, 12
    under ``blas.symm.ring`` at this shape.)"""
    import pathlib
    import sys

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    repo = pathlib.Path(__file__).resolve().parents[1]
    if str(repo) not in sys.path:
        sys.path.insert(0, str(repo))
    from bench import scopes
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rep = NamedSharding(mesh, PartitionSpec())
    kw = dict(mesh=mesh, axis="model")
    if op == "syrk":
        fn = lambda a: blas.syrk(a, fill="full", **kw)       # noqa: E731
        shapes = [(RING_K, RING_N1, RING_N2)]
    else:
        fn = lambda s, b: blas.symm(s, b, **kw)               # noqa: E731
        shapes = [(RING_K, RING_N1, RING_N1)] * 2
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
            for s in shapes]
    with blas.capture_routes() as log:
        lowered = jax.jit(fn).lower(*args)
    assert [r.path for r in log] == ["ring"], log
    hlo = scopes.hlo_ops(lowered.compile().as_text())
    scope = f"blas.{op}.ring"
    loops = [n for n, (opcode, name) in hlo.ops.items()
             if opcode in ("while", "scatter") and scope in name]
    assert not loops, (len(loops), loops[:10])


#: ``stablelm-1.6b.muon``'s Newton–Schulz operands (the 24 stacked
#: 2048x5632 MLP weights and the 2048x100352 embedding), then one grid
#: on each side of the converters' static-slice / gather choice: 17
#: 1024-row tiles a side (T = 153, past ``STATIC_TILE_MAX``) still
#: unroll, 63 128-row tiles a side (T = 2016) take one gather
PALLAS_NS_CASES = [
    pytest.param((24,), 2048, 5632, id="mlp-24x2048x5632"),
    pytest.param((), 2048, 100352, id="embed-2048x100352"),
    pytest.param((), 17408, 1024, id="static-17408x1024"),
    pytest.param((), 8064, 1024, id="gather-8064x1024"),
]


@pytest.mark.parametrize("lead,n1,n2", PALLAS_NS_CASES)
@pytest.mark.parametrize("op", ["syrk", "symm"])
def test_pallas_dense_converters_have_no_loops_on_v5e(one_chip, op, lead,
                                                      n1, n2):
    """The NS chain's dense Pallas calls — ``syrk(fill="full")`` for the
    Gram and dense-A ``symm`` for S·S — compiled for one described v5e
    chip at the heuristic tiles move between the kernels' packed tiles
    and dense S in static whole-tile slices: no ``while``, ``scatter``
    or ``gather`` op lies under their scope.  (Whole-tile gathers and
    scatters over the tile grid lower to serial loops at 1024-row
    tiles: 10 ``while`` under ``blas.syrk.pallas`` and 8 under
    ``blas.symm.pallas`` on the MLP stack; 4 gathers and 2 scatters,
    and 4 gathers, on the embedding.)  Only a grid of 128-row tiles
    too large to unroll keeps its gather, which stays loop-free."""
    import pathlib
    import sys
    repo = pathlib.Path(__file__).resolve().parents[1]
    if str(repo) not in sys.path:
        sys.path.insert(0, str(repo))
    from bench import scopes
    from repro.core.packing import _unrolls
    tile = blas.heuristic_tiles(op, n1, n2)
    unrolled = _unrolls(-(-n1 // tile[0]), tile[0])
    assert unrolled == (n1 != 8064), tile
    kernel = dict(tile=tile, interpret=False)
    if op == "syrk":
        fn = lambda a: blas.syrk(a, fill="full", **kernel)   # noqa: E731
        shapes = [lead + (n1, n2)]
    else:
        fn = lambda s, b: blas.symm(s, b, **kernel)           # noqa: E731
        shapes = [lead + (n1, n1), lead + (n1, n2)]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    with blas.capture_routes() as log:
        lowered = jax.jit(fn).lower(*args)
    assert [(r.path, r.tiles) for r in log] == [("pallas", tile)], log
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    hlo = scopes.hlo_ops(text)
    scope = f"blas.{op}.pallas"
    moves = [opcode for opcode, name in hlo.ops.values()
             if opcode in ("while", "scatter", "gather") and scope in name]
    if unrolled:
        assert not moves, moves
    else:
        assert "gather" in moves and "while" not in moves, moves

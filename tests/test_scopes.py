"""The train step's named scopes, as a profiler trace reads them.

The compiled smoke-size stablelm-1.6b Muon step is joined to its own
HLO metadata by the benchmark's reader (``bench/scopes.py``): every
instruction that does work falls in a bucket, each matrix leaf has its
NS scope, and every symmetric-BLAS call names its op and route.  Also:
the Pallas kernels carry their names, and the data pipeline's host
spans show up in a trace.
"""
import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import blas
from repro.blas import routing
from repro.configs import get_smoke_config
from repro.kernels.symm import symm_tiles
from repro.kernels.syr2k import syr2k_tiles
from repro.kernels.syrk import syrk_tiles
from repro.launch.steps import make_optimizer, make_train_step
from repro.models.model import init_params
from repro.optim.muon import _is_matrix

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))
from bench import scopes  # noqa: E402

#: instructions that move or name data and do no work of their own
NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
           "copy")


@pytest.fixture(scope="module")
def step_hlo():
    cfg = get_smoke_config("stablelm-1.6b")
    opt = make_optimizer(cfg, "muon")
    step = make_train_step(cfg, opt, loss_chunk=16)
    shape = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    state = jax.eval_shape(opt.init, shape)
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    with routing.capture_routes() as log:
        text = jax.jit(step).lower(shape, state, batch).compile().as_text()
    return scopes.hlo_ops(text), shape, list(log)


def test_every_instruction_that_works_has_a_bucket(step_hlo):
    hlo, _, _ = step_hlo
    assert hlo.module == "jit_train_step"
    work = [n for n in hlo.top if hlo.ops[n][0] not in NO_WORK]
    buckets = {n: scopes.bucket(hlo.ops[n][1]) for n in work}
    assert [n for n, b in buckets.items() if b == "unattributed"] == []
    found = set(buckets.values())
    assert {"forward", "backward", "recompute", "clip",
            "optimizer"} <= found


def test_one_ns_scope_per_matrix_leaf(step_hlo):
    hlo, shape, _ = step_hlo
    want = {jax.tree_util.keystr(path, simple=True, separator=".")
            for path, p in jax.tree_util.tree_leaves_with_path(shape)
            if _is_matrix(p)}
    got = {m.group(1) for _, op_name in hlo.ops.values()
           for m in [scopes._LEAF.search(op_name)] if m}
    assert got == want
    assert "periods.b0.mlp.wi" in got


def test_every_blas_call_names_its_route(step_hlo):
    hlo, _, routes = step_hlo
    assert routes
    names = [op_name for _, op_name in hlo.ops.values()]
    for r in routes:
        scope = blas.api.route_scope(r)
        assert scope == f"blas.{r.op}.{r.path.split('-')[0]}"
        assert any(scope in n for n in names), scope
    # the NS chain's products all run inside a blas scope
    for n in hlo.top:
        opcode, op_name = hlo.ops[n]
        if opcode in ("dot", "convolution") and "optim." in op_name:
            assert scopes._BLAS.search(op_name), op_name


def test_backward_runs_under_the_routed_scope():
    """The custom-VJP backward of a SYRK is a SYMM through the same
    executors, so it carries ``blas.symm.<path>`` too."""
    x = jnp.ones((16, 24), jnp.float32)
    text = jax.jit(jax.value_and_grad(lambda x: blas.syrk(x).sum())) \
        .lower(x).compile().as_text()
    names = [n for _, n in scopes.hlo_ops(text).ops.values()]
    assert any("transpose(" in n and "blas.symm.dense" in n for n in names)
    assert any("blas.syrk.dense" in n for n in names)


def _panel():
    return jnp.ones((256, 128))


@pytest.mark.parametrize("name,call", [
    ("syrk", lambda: syrk_tiles(_panel(), interpret=True)),
    ("syr2k", lambda: syr2k_tiles(_panel(), _panel(), interpret=True)),
    ("symm", lambda: symm_tiles(jnp.ones((3, 128, 128)), _panel(),
                                interpret=True)),
])
def test_pallas_kernels_carry_their_names(name, call):
    eqns = [e for e in jax.make_jaxpr(call)().jaxpr.eqns
            if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in eqns] == [name]


def test_data_pipeline_spans_in_a_trace(tmp_path):
    from jax.profiler import ProfileData

    from repro.data import DataConfig, make_train_iterator
    dcfg = DataConfig(seq_len=16, global_batch=2, vocab_size=64, seed=3,
                      mean_doc_len=16)
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    jax.profiler.start_trace(str(tmp_path))
    it = make_train_iterator(dcfg, sharding=sharding)
    for _ in range(3):
        next(it)
    it.close()
    jax.profiler.stop_trace()
    path = next(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             for line in p.lines for e in line.events}
    assert {"repro.data.produce", "repro.data.put",
            "repro.data.wait"} <= names


# --------------------------------------------- the mesh cell's wires
MESH_CHILD = r"""
import json, pathlib, sys
root = pathlib.Path(sys.argv[1])
sys.path[:0] = [str(root), str(root / "src")]
from bench import scopes, spec
from repro.blas import routing
cell = spec.load_cell(root, "pixtral-12b-tiny.muon-tp4")
with routing.capture_routes() as log:
    text = scopes.compiled_step_text(cell)
json.dump({"text": text, "routes": sorted({(r.op, r.path) for r in log})},
          sys.stdout)
"""
MESH_PATHS = ("1d", "2d", "3d", "ring")


@pytest.fixture(scope="module")
def mesh_hlo(tmp_path_factory):
    """The compiled step of the benchmark's tiny mesh cell on 4 virtual
    CPU devices (a child process: the device count must not leak)."""
    import json
    import subprocess

    from bench.tests import rehearse
    root = rehearse.make_root(tmp_path_factory.mktemp("mesh"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", MESH_CHILD, str(root)],
                       capture_output=True, text=True, env=env, cwd=root,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout)
    return scopes.hlo_ops(got["text"]), [tuple(r) for r in got["routes"]]


def _wire_collectives(hlo):
    """(op_name, scopes) of every collective that runs inside a
    shard_map of the optimizer."""
    from bench import xplane
    return [(n, scopes.scopes_of(n)) for op, n in hlo.ops.values()
            if xplane.COLLECTIVE.search(op) and "shard_map" in n
            and scopes.bucket(n) == "optimizer"]


def test_muon_wire_collectives_carry_the_wire_scope(mesh_hlo):
    """Each collective of a shard_map in the optimizer belongs to
    exactly one wire: Muon's own (``optim.muon_1d``) or a repro.blas
    mesh route (``blas.<op>.<path>``)."""
    from repro.optim.muon import WIRE_SCOPE
    hlo, _ = mesh_hlo
    wires = _wire_collectives(hlo)
    muon = [n for n, s in wires if WIRE_SCOPE in s]
    assert muon, "Muon's 1d wire ran no collective"
    assert {"reduce-scatter", "all-gather"} <= {
        hlo.ops[k][0] for k, v in hlo.ops.items() if v[1] in muon}
    for n, s in wires:
        routes = [x for x in s if scopes._BLAS.fullmatch(x)]
        assert (WIRE_SCOPE in s) != bool(routes), n
        # Muon's wire sits inside its leaf's NS chain
        assert any(x.startswith("optim.muon.ns.") for x in s), n


def test_every_mesh_route_carries_its_scope(mesh_hlo):
    hlo, routes = mesh_hlo
    mesh = [(op, path) for op, path in routes
            if path.split("-")[0] in MESH_PATHS]
    assert mesh, routes
    names = [n for _, n in hlo.ops.values()]
    for op, path in mesh:
        scope = f"blas.{op}.{path.split('-')[0]}"
        assert any(scope in scopes.scopes_of(n) for n in names), scope
    for n, s in _wire_collectives(hlo):
        for x in s:
            m = scopes._BLAS.fullmatch(x)
            if m:
                assert m.group(2) in MESH_PATHS, n


def test_both_wires_are_filed_under_the_optimizer(mesh_hlo):
    from repro.optim.muon import WIRE_SCOPE
    hlo, _ = mesh_hlo
    ran = [hlo.ops[k][1] for k in hlo.top]
    on_wire = [n for n in ran
               if WIRE_SCOPE in scopes.scopes_of(n)
               or any(scopes._BLAS.fullmatch(x)
                      and x.rsplit(".", 1)[1] in MESH_PATHS
                      for x in scopes.scopes_of(n))]
    assert on_wire
    assert {scopes.bucket(n) for n in on_wire} == {"optimizer"}
    assert any(WIRE_SCOPE in scopes.label(n).split("/") for n in on_wire)

"""Distributed runtime: checkpoint atomicity/restart, elastic resharding,
straggler detection, int8 gradient compression."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed import (ErrorFeedbackInt8, StragglerMonitor,
                               checkpoint_bytes, compressed_allreduce,
                               dequantize_int8, latest_step, plan_mesh,
                               quantize_int8, reshard_tree,
                               restore_checkpoint, save_checkpoint,
                               wait_for_saves)
from repro.compat import make_mesh
from repro.distributed.compression import wire_bytes_per_device
from repro.distributed.elastic import spec_tree_like, validate_divisibility


# ------------------------------------------------------------------ #
# checkpoint
# ------------------------------------------------------------------ #

def _tree(seed=0):
    k = jax.random.key(seed)
    return {"w": jax.random.normal(k, (8, 16)),
            "stack": {"b": jnp.arange(5, dtype=jnp.int32)},
            "scalars": (jnp.float32(3.5), jnp.int32(7))}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 12, t)
    step, back = restore_checkpoint(str(tmp_path), jax.eval_shape(
        lambda: t))
    assert step == 12
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_latest(tmp_path):
    for s in (1, 2, 3, 4):
        save_checkpoint(str(tmp_path), s, _tree(s), keep=2)
    assert latest_step(str(tmp_path)) == 4
    kept = sorted(os.listdir(tmp_path))
    assert kept == ["step_00000003", "step_00000004"]


def test_checkpoint_async(tmp_path):
    save_checkpoint(str(tmp_path), 9, _tree(), blocking=False)
    wait_for_saves()
    assert latest_step(str(tmp_path)) == 9


def test_checkpoint_crc_detects_corruption(tmp_path):
    t = _tree()
    save_checkpoint(str(tmp_path), 1, t)
    d = os.path.join(tmp_path, "step_00000001")
    victim = next(f for f in sorted(os.listdir(d)) if f.endswith(".npy"))
    fn = os.path.join(d, victim)
    with open(fn, "r+b") as f:
        f.seek(-1, 2)
        last = f.read(1)
        f.seek(-1, 2)
        f.write(bytes([last[0] ^ 0xFF]))     # guaranteed bit flip
    with pytest.raises(IOError):
        restore_checkpoint(str(tmp_path), jax.eval_shape(lambda: t))


def test_checkpoint_interrupted_save_invisible(tmp_path):
    """A tmp dir without manifest must not count as a checkpoint."""
    save_checkpoint(str(tmp_path), 5, _tree())
    os.makedirs(os.path.join(tmp_path, "step_00000006.tmp-999"),
                exist_ok=True)
    assert latest_step(str(tmp_path)) == 5


def test_checkpoint_extra_metadata(tmp_path):
    save_checkpoint(str(tmp_path), 3, _tree(),
                    extra={"data_step": 3, "mesh": [2, 4]})
    with open(os.path.join(tmp_path, "step_00000003",
                           "manifest.json")) as f:
        m = json.load(f)
    assert m["extra"]["mesh"] == [2, 4]


def test_checkpoint_bf16_void_view_roundtrip(tmp_path):
    """ml_dtypes leaves hit np.save as raw void; restore must view them
    back bit-exactly."""
    import ml_dtypes
    x = (jnp.arange(37, dtype=jnp.float32) * 0.37).astype(jnp.bfloat16)
    save_checkpoint(str(tmp_path), 2, {"x": x})
    # the on-disk array really is void (the round-trip is non-trivial)
    d = os.path.join(tmp_path, "step_00000002")
    raw = np.load(os.path.join(d, next(f for f in os.listdir(d)
                                       if f.endswith(".npy"))))
    assert raw.dtype.kind == "V"
    _, back = restore_checkpoint(str(tmp_path), jax.eval_shape(
        lambda: {"x": x}))
    assert np.asarray(back["x"]).dtype == np.dtype(ml_dtypes.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(back["x"]).view(np.uint16),
        np.asarray(x).view(np.uint16))


def _sym(n, seed=0):
    a = np.random.default_rng(seed).standard_normal((n, n))
    return jnp.asarray((a + a.T) / 2, jnp.float32)


def test_checkpoint_packed_leaf_roundtrip(tmp_path):
    """Typed packed leaves store as ONE packed-vector file each (bf16 by
    default: < 0.30x the dense f32 bytes) and rebuild their layout."""
    from repro.core.packing import (PackedTriangle, ShardedTriTiles,
                                    TriTiles, pack_tril)
    n = 24
    s = _sym(n)
    tree = {"pt": PackedTriangle.from_dense(s),
            "tt": TriTiles.from_tril(jnp.tril(s), 8),
            "st": ShardedTriTiles.from_tril(jnp.tril(s), 2)}
    save_checkpoint(str(tmp_path), 1, tree)
    b = checkpoint_bytes(str(tmp_path))
    for k in ("pt", "tt", "st"):
        assert b["leaves"][k] <= 0.30 * n * n * 4, (k, b["leaves"][k])
    _, back = restore_checkpoint(str(tmp_path), tree)
    want = np.asarray(pack_tril(jnp.tril(s)), np.float32)
    for k in ("pt", "tt", "st"):
        got = back[k].vec if k == "pt" else back[k].to_packed()
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   rtol=1e-2, atol=1e-2)  # bf16 narrow
    # bit-exact when the narrow pass is disabled
    save_checkpoint(str(tmp_path), 2, tree, packed_dtype=None)
    _, back = restore_checkpoint(str(tmp_path), tree)
    np.testing.assert_array_equal(np.asarray(back["st"].to_packed()), want)


def test_checkpoint_packed_to_dense_like(tmp_path):
    """A packed-stored leaf restores into a dense like as the mirrored
    symmetric matrix (legacy consumer path)."""
    from repro.core.packing import PackedTriangle
    n = 16
    s = _sym(n, 3)
    save_checkpoint(str(tmp_path), 1, {"g": PackedTriangle.from_dense(s)},
                    packed_dtype=None)
    _, back = restore_checkpoint(
        str(tmp_path), {"g": jax.ShapeDtypeStruct((n, n), jnp.float32)})
    np.testing.assert_array_equal(np.asarray(back["g"]), np.asarray(s))


def test_retire_sweeps_orphaned_tmp_dirs(tmp_path):
    """Crash debris (tmp dirs from a dead pid) is swept by the next
    save's retention pass; a live writer's tmp dir is left alone."""
    save_checkpoint(str(tmp_path), 1, _tree())
    dead = os.path.join(tmp_path, "step_00000099.tmp-999999999-1")
    live = os.path.join(tmp_path, "step_00000098.tmp-1-1")  # pid 1: alive
    os.makedirs(dead)
    os.makedirs(live)
    save_checkpoint(str(tmp_path), 2, _tree())
    assert not os.path.exists(dead), "orphaned tmp dir must be swept"
    assert os.path.exists(live), "a live writer's tmp dir must survive"


# ------------------------------------------------------------------ #
# elastic
# ------------------------------------------------------------------ #

def test_plan_shape_factorizations():
    from repro.distributed import plan_shape
    assert plan_shape(8, max_model=4) == (2, 4)
    assert plan_shape(6, max_model=4, model_divides=9) == (2, 3)
    assert plan_shape(7, max_model=4) == (7, 1)      # prime -> 1D DP
    assert plan_shape(512, max_model=16) == (32, 16)


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >1 device")
def test_reshard_roundtrip_smaller_world(tmp_path):
    """Save on mesh A, restore & reshard on mesh B (elastic restart)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    ndev = jax.device_count()
    mesh_a = make_mesh((ndev,), ("model",))
    x = jnp.arange(ndev * 4.0).reshape(ndev, 4)
    xa = jax.device_put(x, NamedSharding(mesh_a, P("model", None)))
    save_checkpoint(str(tmp_path), 1, {"x": xa})

    half = max(ndev // 2, 1)
    mesh_b = make_mesh((half,), ("model",))
    _, back = restore_checkpoint(str(tmp_path),
                                 jax.eval_shape(lambda: {"x": x}))
    placed = reshard_tree(back, {"x": P("model", None)}, mesh_b)
    np.testing.assert_array_equal(np.asarray(placed["x"]), np.asarray(x))
    assert placed["x"].sharding.mesh.shape["model"] == half


def test_reshard_tritiles_bit_exact():
    """c=2 wire -> c=3 wire via the element bijection, bit-for-bit."""
    from repro.core.packing import ShardedTriTiles, pack_tril
    from repro.distributed import reshard_tritiles, wire_c
    assert (wire_c(8), wire_c(6), wire_c(12)) == (2, 2, 3)
    for n in (24, 22):                       # ragged n included
        s = _sym(n, n)
        packed = pack_tril(jnp.tril(s))
        st = ShardedTriTiles.from_packed(packed, n, 2)
        assert reshard_tritiles(st, 2) is st
        st3 = reshard_tritiles(st, 3)
        assert st3.c == 3
        np.testing.assert_array_equal(np.asarray(st3.to_packed()),
                                      np.asarray(packed))


def test_spec_tree_like_packed_aware():
    from jax.sharding import PartitionSpec as P
    from repro.core.packing import PackedTriangle, ShardedTriTiles
    st = ShardedTriTiles.from_tril(jnp.tril(_sym(12, 1)), 2)
    tree = {"s": st, "p": PackedTriangle.from_dense(_sym(8, 2)),
            "w": jnp.ones((3,))}
    specs = spec_tree_like(tree, shard_axis="x")
    assert isinstance(specs["s"], ShardedTriTiles)
    assert specs["s"].off == P("x") and specs["s"].diag == P("x")
    assert isinstance(specs["p"], PackedTriangle)
    assert specs["p"].vec == P() and specs["w"] == P()


def test_rebuild_replacement_shard_matches_layout():
    from repro.core.packing import ShardedTriTiles, pack_tril
    from repro.distributed import rebuild_replacement_shard
    n, c = 20, 2
    packed = pack_tril(jnp.tril(_sym(n, 5)))
    st = ShardedTriTiles.from_packed(packed, n, c)
    for k in range(c * (c + 1)):
        off, diag = rebuild_replacement_shard(packed, n, c, k)
        np.testing.assert_array_equal(np.asarray(off),
                                      np.asarray(st.off[k]))
        np.testing.assert_array_equal(np.asarray(diag),
                                      np.asarray(st.diag[k]))


def test_validate_divisibility():
    n = jax.device_count()
    mesh = plan_mesh(n, max_model=max(n // 2, 1))   # force dp >= 2
    ok, _ = validate_divisibility(mesh, global_batch=1024,
                                  model_dims=[64, 128])
    assert ok
    if mesh.shape["data"] > 1:
        bad, why = validate_divisibility(mesh, global_batch=3,
                                         model_dims=[64])
        assert not bad and "global_batch" in why


# ------------------------------------------------------------------ #
# straggler
# ------------------------------------------------------------------ #

def test_straggler_detection_and_escalation():
    mon = StragglerMonitor(window=32, threshold=2.0, patience=2,
                           warmup=4)
    evs = []
    for i in range(20):
        ev = mon.record(i, 0.1)
        assert ev is None
    # sustained 3x slowdown
    for i in range(20, 30):
        ev = mon.record(i, 0.3)
        if ev:
            evs.append(ev)
    assert evs, "sustained slowdown must trigger"
    assert evs[0].action == "warn"
    if len(evs) > 1:
        assert evs[1].action == "checkpoint"


def test_straggler_single_blip_no_event():
    mon = StragglerMonitor(window=32, threshold=2.0, patience=3,
                           warmup=4)
    for i in range(10):
        assert mon.record(i, 0.1) is None
    assert mon.record(10, 1.0) is None      # one blip < patience
    for i in range(11, 20):
        assert mon.record(i, 0.1) is None


# ------------------------------------------------------------------ #
# compression
# ------------------------------------------------------------------ #

def test_int8_quant_roundtrip_error_bound():
    x = jax.random.normal(jax.random.key(0), (1000,)) * 3.0
    q, s = quantize_int8(x, block=128)
    back = dequantize_int8(q, s, x.shape)
    # block-wise symmetric int8: |err| <= scale/2 = max|block|/254
    err = jnp.max(jnp.abs(back - x))
    assert err <= jnp.max(jnp.abs(x)) / 127.0


def test_error_feedback_accumulates_residual():
    """Sum of EF-compressed grads converges to sum of true grads."""
    comp = ErrorFeedbackInt8(block=64)
    params = {"w": jnp.zeros((64,))}
    state = comp.init(params)
    g = {"w": jnp.full((64,), 1e-3)}        # tiny grads, heavy quant err
    acc = jnp.zeros((64,))
    for _ in range(50):
        dq, state = comp.compress(g, state)
        acc = acc + dq["w"]
    np.testing.assert_allclose(np.asarray(acc),
                               np.full((64,), 50e-3), rtol=0.05)


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >1 device")
def test_compressed_allreduce_matches_mean():
    mesh = make_mesh((jax.device_count(),), ("data",))
    x = jax.random.normal(jax.random.key(1), (512,))
    out = compressed_allreduce(x, mesh, axis="data", block=128)
    # every device contributed the same x -> mean == x
    np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                               atol=float(jnp.max(jnp.abs(x))) / 50)


def test_error_feedback_sym_mask_packed_residual():
    """A sym-masked leaf quantizes in packed layout (residual is the
    n(n+1)/2 triangle) and still converges; output stays symmetric."""
    from repro.core.packing import tril_size
    n = 12
    s = _sym(n, 9)
    comp = ErrorFeedbackInt8(block=16, sym_mask={"g": True, "w": False})
    params = {"g": s, "w": jnp.zeros((8,))}
    state = comp.init(params)
    assert state.error["g"].shape == (tril_size(n),)
    g = {"g": s * 1e-3, "w": jnp.full((8,), 1e-3)}
    acc = jnp.zeros((n, n))
    for _ in range(50):
        dq, state = comp.compress(g, state)
        np.testing.assert_array_equal(np.asarray(dq["g"]),
                                      np.asarray(dq["g"]).T)
        acc = acc + dq["g"]
    np.testing.assert_allclose(np.asarray(acc), np.asarray(s) * 50e-3,
                               rtol=0.05, atol=1e-6)


def test_error_feedback_typed_packed_leaf():
    """PackedTriangle leaves flatten to their packed vec — EF compresses
    them packed with no mask at all."""
    from repro.core.packing import PackedTriangle, tril_size
    pt = PackedTriangle.from_dense(_sym(10, 4))
    comp = ErrorFeedbackInt8(block=16)
    state = comp.init({"p": pt})
    assert jax.tree.leaves(state.error)[0].shape == (tril_size(10),)
    dq, _ = comp.compress({"p": pt}, state)
    assert isinstance(dq["p"], PackedTriangle)
    np.testing.assert_allclose(np.asarray(dq["p"].vec),
                               np.asarray(pt.vec), rtol=0.05, atol=0.05)


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >1 device")
def test_compressed_allreduce_sym_matches_mean():
    from repro.core.packing import PackedTriangle
    from repro.distributed import compressed_allreduce_sym
    mesh = make_mesh((jax.device_count(),), ("data",))
    s = _sym(24, 6)
    out = compressed_allreduce_sym(s, mesh, axis="data", block=64)
    got = np.asarray(out)
    np.testing.assert_allclose(got, np.asarray(s),
                               atol=float(jnp.max(jnp.abs(s))) / 30)
    np.testing.assert_array_equal(got, got.T)
    pt = PackedTriangle.from_dense(s)
    o2 = compressed_allreduce_sym(pt, mesh, axis="data", block=64)
    assert isinstance(o2, PackedTriangle)
    np.testing.assert_allclose(np.asarray(o2.vec), np.asarray(pt.vec),
                               atol=float(jnp.max(jnp.abs(s))) / 30)


def test_wire_bytes_model():
    n, p = 1_000_000, 16
    c = wire_bytes_per_device(n, p, compressed=True)
    u = wire_bytes_per_device(n, p, compressed=False)
    assert u / c > 3.8        # ~3.94x saving
    # a symmetric leaf on the packed wire moves ~half the words
    from repro.core.packing import tril_size
    d = 1000
    s = wire_bytes_per_device(d * d, p, compressed=True, sym_n=d)
    full = wire_bytes_per_device(d * d, p, compressed=True)
    assert abs(s / full - tril_size(d) / (d * d)) < 1e-9


# ------------------------------------------------------------------ #
# data pipeline
# ------------------------------------------------------------------ #

def test_data_determinism_and_restart():
    from repro.data import DataConfig, make_train_iterator
    cfg = DataConfig(seq_len=64, global_batch=4, vocab_size=97, seed=3,
                     mean_doc_len=50, prefetch=1)
    it = make_train_iterator(cfg)
    batches = [next(it) for _ in range(6)]
    it.close()
    # restart from step 4 reproduces batches 4..5 exactly
    it2 = make_train_iterator(cfg, start_step=4)
    for want in batches[4:]:
        got = next(it2)
        np.testing.assert_array_equal(got["tokens"], want["tokens"])
        np.testing.assert_array_equal(got["labels"], want["labels"])
    it2.close()


def test_data_host_sharding_partitions_batch():
    from repro.data import DataConfig, make_train_iterator
    cfg = DataConfig(seq_len=32, global_batch=8, vocab_size=31, seed=1,
                     mean_doc_len=40, prefetch=1)
    its = [make_train_iterator(cfg, host_id=h, num_hosts=2)
           for h in range(2)]
    b0, b1 = next(its[0]), next(its[1])
    for it in its:
        it.close()
    assert b0["tokens"].shape == (4, 32)
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_data_labels_are_shifted_tokens():
    from repro.data import DataConfig, make_train_iterator
    cfg = DataConfig(seq_len=16, global_batch=2, vocab_size=11, seed=0,
                     mean_doc_len=30, prefetch=1)
    it = make_train_iterator(cfg)
    b = next(it)
    it.close()
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_pack_documents_no_padding():
    from repro.data import pack_documents
    docs = [np.arange(10), np.arange(20), np.arange(37)]
    rows = pack_documents(docs, seq_len=15, eos_id=0)
    assert all(r.shape == (16,) for r in rows)
    assert len(rows) == (10 + 20 + 37) // 16

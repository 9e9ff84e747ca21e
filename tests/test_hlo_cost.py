"""Validate the trip-count-corrected HLO cost analyzer against XLA's own
cost_analysis on unrolled (while-free) versions of the same program."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo_cost import analyze_hlo
from repro.compat import make_mesh


def _mlp_body(h, w):
    return jnp.tanh(h @ w), ()


def _scanned(h, ws, unroll):
    y, _ = jax.lax.scan(_mlp_body, h, ws, unroll=unroll)
    return jnp.sum(y * y)


N_LAYERS, B, D = 6, 32, 64


def _lower(unroll):
    x = jax.ShapeDtypeStruct((B, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((N_LAYERS, D, D), jnp.float32)
    return jax.jit(lambda h, w: _scanned(h, w, unroll)).lower(x, ws) \
        .compile()


def test_dot_flops_match_unrolled_cost_analysis():
    """analyzer(while version) ≈ XLA cost_analysis(unrolled version)."""
    comp_loop = _lower(unroll=1)
    comp_flat = _lower(unroll=N_LAYERS)

    mine = analyze_hlo(comp_loop.as_text())
    xla_flat = comp_flat.cost_analysis()
    xla_loop = comp_loop.cost_analysis()

    expected_dot_flops = N_LAYERS * 2 * B * D * D
    # XLA undercounts the loop version by ~N_LAYERS:
    assert xla_loop["flops"] < 2.5 * expected_dot_flops / N_LAYERS + 1e5
    # the unrolled XLA count includes elementwise; dot flops dominate
    assert xla_flat["flops"] >= expected_dot_flops
    # our corrected count matches the unrolled XLA count within 10%
    assert mine.total_flops == pytest.approx(
        xla_flat["flops"] + xla_flat.get("transcendentals", 0.0),
        rel=0.10)


def test_bytes_scale_with_trip_count():
    comp_loop = _lower(unroll=1)
    comp_flat = _lower(unroll=N_LAYERS)
    mine = analyze_hlo(comp_loop.as_text())
    xla_flat = comp_flat.cost_analysis()
    # bytes: our traffic model counts operands+results per op — the
    # unrolled XLA count should agree within 2x (fusion boundaries differ)
    assert mine.bytes_accessed == pytest.approx(
        xla_flat["bytes accessed"], rel=1.0)
    # and must be ~N_LAYERS larger than the naive loop-body-once count
    xla_loop = comp_flat  # noqa: F841
    assert mine.bytes_accessed > 2.5 * comp_loop.cost_analysis()[
        "bytes accessed"]


def test_unknown_trip_counter_zero_for_static_scan():
    comp_loop = _lower(unroll=1)
    mine = analyze_hlo(comp_loop.as_text())
    assert mine.unknown_trip_whiles == 0


def test_collectives_multiplied_by_trip_count():
    """A psum inside a scan body must be counted trip_count times."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices")
    ndev = jax.device_count()
    mesh = make_mesh((ndev,), ("model",))

    def body(h, w):
        y = h @ w                       # w col-sharded -> partial sums
        y = jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P(None, None)))
        return y, ()

    def f(h, ws):
        y, _ = jax.lax.scan(body, h, ws)
        return y

    T = 5
    x = jax.ShapeDtypeStruct((8, D), jnp.float32)
    ws = jax.ShapeDtypeStruct((T, D, D), jnp.float32)
    with jax.set_mesh(mesh):
        comp = jax.jit(
            f, in_shardings=(NamedSharding(mesh, P()),
                             NamedSharding(mesh, P(None, "model", None))),
            out_shardings=NamedSharding(mesh, P())).lower(x, ws).compile()
    mine = analyze_hlo(comp.as_text())
    total_coll = sum(mine.collective_counts.values())
    # at least T collectives once trip-multiplied (the partitioner may
    # add a couple outside the loop)
    assert total_coll >= T, (mine.collective_counts, comp.as_text()[:500])

"""The computation-optimal cyclic-shift (ring) mesh route (PR 8).

Single-process coverage of the planner gate and the ring schedule
tables — ``choose_algorithm`` plans ``kind="ring"`` exactly in the
computation-bound regime, the slot↔block converters are bijective at
odd and even P — plus the multi-device suite (``dist_checks.py
--suite ring``: dense == ring parity at odd/even P incl. ragged n1
and batched stacks, jaxpr-asserted dense-free packed wire forward and
backward, exactly ⌊P/2⌋ collective-permutes on the compiled wire,
backward-symm Route capture, and the ≤ 0.6× 2d per-device HLO flop
gate) run in subprocesses so fake-device XLA flags never leak here.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import ringpath
from repro.core.dispatch import (choose_algorithm, ring_nb,
                                 ring_working_set)
from repro.core.packing import tril_size

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# planner gate
# ---------------------------------------------------------------------------
def test_ring_planned_in_computation_bound_regime():
    # flop-heavy square-ish shapes at moderate P: ring takes over
    for n1, n2, P in [(256, 256, 8), (2048, 512, 8), (96, 96, 3),
                      (65536, 128, 2)]:
        ch = choose_algorithm(n1, n2, P, 1)
        assert ch.kind == "ring", (n1, n2, P, ch)
        assert (ch.p1, ch.p2, ch.idle) == (P, 1, 0)


def test_ring_not_planned_when_wire_bound_or_tiny():
    # case 1 (n2 >> n1): the 1d column split is already flop-optimal
    assert choose_algorithm(1024, 65536, 2, 1).kind == "1d"
    # n2 below the balance point: word-minimal families keep the shape
    assert choose_algorithm(65536, 32, 2, 1).kind != "ring"
    # tiny per-device blocks are wire-bound
    assert choose_algorithm(64, 4096, 16, 1).kind != "ring"
    # P = 1 has no ring
    assert choose_algorithm(4096, 4096, 1, 1).kind == "1d"


def test_ring_respects_memory_budget():
    n1, n2, P = 2048, 512, 8
    need = ring_working_set(n1, n2, P, 1)
    assert choose_algorithm(n1, n2, P, 1, M=int(need) + 1).kind == "ring"
    assert choose_algorithm(n1, n2, P, 1, M=int(need) // 2).kind != "ring"


def test_ring_nb_even_P_rounds_to_even():
    assert ring_nb(65, 2) == 34          # ragged, rounded to even
    assert ring_nb(100, 3) == 34         # odd P: plain ceil
    assert ring_nb(256, 8) == 32
    assert ring_nb(96, 6) == 16


def test_ring_predicted_words_1d_level():
    # the ring moves floor(P/2) shifts of the nb x n2 slice — far below
    # the 2d route's ~n1*n2/c at the same shape
    ch = choose_algorithm(2048, 512, 8, 1)
    assert ch.kind == "ring"
    assert ch.predicted_words == 4 * ring_nb(2048, 8) * 512


# ---------------------------------------------------------------------------
# schedule tables: the slot stacks tile the triangle exactly once
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("P", [2, 3, 4, 5, 8])
def test_ring_block_tables_cover_lower_triangle(P):
    """Every lower-triangular block (i, j) of the P x P block grid is
    produced by exactly one slot (or, at the antipodal distance of an
    even P, summed from the two half-slots of the partner pair)."""
    S = P // 2
    src1, src2, use2, trans = ringpath.ring_block_tables(P)
    nblk = P * (P + 1) // 2
    assert src1.shape == (nblk,)
    k = 0
    for i in range(P):
        for j in range(i + 1):
            d = i - j
            if P % 2 == 0 and d == S:
                assert use2[k], (i, j)
                assert src1[k] == i * (S + 1) + S
                assert src2[k] == j * (S + 1) + S
            elif d <= S:
                assert not use2[k]
                assert src1[k] == i * (S + 1) + d
                assert not trans[k]
            else:
                assert not use2[k]
                assert src1[k] == j * (S + 1) + (P - d)
                assert trans[k]
            k += 1


@pytest.mark.parametrize("P,n1", [(2, 64), (2, 65), (3, 96), (3, 100),
                                  (4, 128), (5, 161), (8, 256)])
def test_ring_stack_packed_round_trip(P, n1):
    """packed -> ring slot stacks -> packed is the identity on the
    triangle (the unpack tables invert the ownership tables), at odd
    and even P including ragged n1.

    ``packed_to_ring`` is the SYMM *input* convention: at even P both
    antipodal partners carry the full block (one transposed).  The
    compute-output convention that ``ring_stack_to_packed`` sums is
    half per partner (device i rows [h:], device j rows [:h],
    untransposed), so the even-P slot S is re-staged before inverting.
    """
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    packed = jnp.asarray(rng.standard_normal(tril_size(n1)), jnp.float32)
    slots = ringpath.packed_to_ring(packed, n1, P)
    S = P // 2
    nb = ring_nb(n1, P)
    assert slots.shape == (P, S + 1, nb, nb)
    if P % 2 == 0:
        sl = np.asarray(slots).copy()
        h = nb // 2
        for r in range(P):
            q = (r - S) % P
            if r < q:          # the partner holding the transposed copy
                blk = sl[r, S].T.copy()
                blk[h:] = 0.0
            else:
                blk = sl[r, S].copy()
                blk[:h] = 0.0
            sl[r, S] = blk
        slots = jnp.asarray(sl)
    back = ringpath.ring_stack_to_packed(slots, n1)
    np.testing.assert_allclose(np.asarray(back), np.asarray(packed),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# dense converters: whole-block equivalents of the packed round trip
# ---------------------------------------------------------------------------
DENSE_CASES = [(P, n1, k) for P in (2, 3, 4, 5, 6)
               for n1 in (8 * P, 8 * P + 3) for k in (None, 3)]


@pytest.mark.parametrize("P,n1,k", DENSE_CASES,
                         ids=[f"P{P}-n{n1}-{'b' + str(k) if k else 'flat'}"
                              for P, n1, k in DENSE_CASES])
def test_ring_dense_converters_match_packed_path(P, n1, k):
    """``ring_stack_to_full`` and ``dense_to_ring`` give, bit for bit,
    what the element-packed round trip gives: the exit against
    ``unpack_tril(ring_stack_to_packed(·))`` for the full and the tril
    fill, the entry against ``packed_to_ring(pack_tril(tril(·)))`` —
    at odd and even P, n1 divisible by P and ragged, with and without a
    leading batch dim.  The stack is random in every slot (diagonal
    upper halves and both antipodal half-slots included), so each
    converter must read exactly what the packed path reads."""
    import jax
    import jax.numpy as jnp

    from repro.core.packing import pack_tril, unpack_tril
    rng = np.random.default_rng(P * 1000 + n1)
    S = P // 2
    nb = ring_nb(n1, P)
    lead = (k,) if k else ()
    stack = jnp.asarray(rng.standard_normal((P,) + lead + (S + 1, nb, nb)),
                        jnp.float32)
    for symmetric in (True, False):
        got = jax.jit(lambda x: ringpath.ring_stack_to_full(
            x, n1, symmetric=symmetric))(stack)
        want = jax.jit(lambda x: unpack_tril(
            ringpath.ring_stack_to_packed(x, n1), n1,
            symmetric=symmetric))(stack)
        assert got.shape == lead + (n1, n1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    a = jnp.asarray(rng.standard_normal(lead + (n1, n1)), jnp.float32)
    a = a.at[..., 0, n1 - 1].set(jnp.nan)   # the upper half is never read
    got = jax.jit(lambda x: ringpath.dense_to_ring(x, P))(a)
    want = jax.jit(lambda x: ringpath.packed_to_ring(
        pack_tril(jnp.tril(x)), n1, P))(a)
    assert got.shape == (P,) + lead + (S + 1, nb, nb)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# multi-device suite (subprocess: fake devices must not leak)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("ndev", [8, 6])
def test_ring_route_subprocess(ndev):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "dist_checks.py"),
         "--suite", "ring"],
        capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"ring suite failed ({ndev} devices):\n" \
                                f"{out.stdout}\n{out.stderr}"
    assert "OK ring" in out.stdout

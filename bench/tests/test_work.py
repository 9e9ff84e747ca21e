"""Required-work counts against hand counts for stablelm-1.6b's shapes.

    python -m pytest -q bench/tests/test_work.py
"""
import json
import pathlib

import pytest

from bench import work
from bench.references import dense_decoder

BENCH = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cfg():
    return json.loads((BENCH / "configs" / "stablelm-1.6b.json").read_text())


@pytest.fixture(scope="module")
def shapes(cfg):
    return [s for _, s, _, _ in dense_decoder.layout(cfg)]


def test_ns_flop_matches_hand_count(shapes):
    # per NS iteration on m x n: SYRK m²n + SYMM(S,S) 2m³ + SYMM(Y,X) 2m²n
    def it(m, n):
        return 3 * m * m * n + 2 * m ** 3
    hand = 5 * (96 * it(2048, 2048)          # q, k, v, o of 24 layers
                + 72 * it(2048, 5632)        # wi, wg, wo of 24 layers
                + 2 * it(2048, 100352)       # embedding and head
                + 4 * it(24, 2048))          # stacked norm scales, biases
    got = work.calls_flop(work.ns_calls(shapes, ns_steps=5))
    assert got == hand
    assert got == pytest.approx(6.5e13, rel=0.01)


def test_model_flop_matches_hand_count(cfg):
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    n = 24 * per_layer + 2048 * 100352       # the embedding is a gather
    attn = 6 * 8 * 512 * 512 * 32 * 64 * 24
    got = work.model_flop(cfg, batch=8, seq=512)
    assert got == 6 * n * 8 * 512 + attn
    assert got == pytest.approx(3.6e13, rel=0.01)


def test_ns_matrices_follow_the_matrix_rule():
    got = work.ns_matrices([(100352, 2048), (24, 2048, 5632), (24, 2048),
                            (2048,), (4, 2048)])
    assert got == [(2048, 100352, 1), (2048, 5632, 24), (24, 2048, 1)]


@pytest.mark.parametrize("op,n1,n2,words", [
    ("syrk", 2048, 5632, 2048 * 5632 + 2048 * 2048),
    ("syr2k", 2048, 5632, 2 * 2048 * 5632 + 2048 * 2048),
    ("symm", 2048, 5632, 2048 * 2049 // 2 + 2 * 2048 * 5632),
])
def test_call_bytes_reads_operands_once(op, n1, n2, words):
    assert work.call_bytes(op, n1, n2, itemsize=4) == 4 * words


def test_packed_fill_writes_the_triangle():
    assert work.call_bytes("syrk", 4, 8, itemsize=1, fill="packed") \
        == 4 * 8 + 10


def test_roofline_takes_the_larger_bound():
    peak = {"flop_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_s(1000.0, 10.0, peak) == 10.0      # compute
    assert work.roofline_s(10.0, 1000.0, peak) == 100.0     # memory


def test_step_flop_totals(cfg, shapes):
    f = work.step_flop(cfg, shapes, 8, 512, 5)
    assert f["total"] == f["model"] + f["ns"]
    assert f["total"] == pytest.approx(1.01e14, rel=0.01)

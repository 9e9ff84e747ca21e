"""The trace reduction, on a synthetic trace and on a small trace
recorded on a TPU v5e (``data/v5e_syrk.xplane.pb``: two calls of one
jitted program holding a Pallas SYRK kernel, 256x384, under host spans
``bench.window``, ``bench.dispatch`` and ``bench.loss_read``).

    python -m pytest -q bench/tests/test_trace.py
"""
import pathlib

import pytest

from bench import xplane
from bench.xplane import Op, Trace

DATA = pathlib.Path(__file__).resolve().parent / "data"

KERNEL = ('%k.1 = f32[3,128,128]{2,1,0} custom-call(f32[256,384]{1,0} %a), '
          'custom_call_target="tpu_custom_call"')


def test_parse_op_names_and_opcodes():
    assert xplane.parse_op("%fusion.1 = f32[2]{0} fusion(f32[2]{0} %x), "
                          "kind=kLoop") == ("fusion.1", "fusion")
    assert xplane.parse_op("%copy-start.2 = (s32[3]{0}, u32[]) copy-start("
                          "s32[3]{0} %c)") == ("copy-start.2", "copy-start")
    assert xplane.classify(KERNEL) == ("k.1 custom-call tpu_custom_call",
                                      "pallas")
    assert xplane.classify("%all-gather.3 = f32[8]{0} all-gather(f32[2]{0} "
                          "%x)")[1] == "collective"
    assert xplane.classify("%fusion.9 = f32[8]{0} fusion(%y)")[1] == "compute"


def test_interval_helpers():
    assert xplane.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xplane.clip([(0, 5), (8, 20)], 2, 10) == [(2, 5), (8, 10)]
    assert xplane.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]


def _synthetic():
    ops = [
        # a while loop holding a kernel and a collective
        Op("while.1 while", 100, 700, "compute"),
        Op("k.1 custom-call tpu_custom_call", 100, 300, "pallas"),
        Op("all-reduce.1 all-reduce", 300, 500, "collective"),
        Op("fusion.2 fusion", 500, 700, "compute"),
        # after an idle gap
        Op("fusion.3 fusion", 800, 1000, "compute"),
    ]
    ops = xplane.nest(ops)
    ops.append(Op("all-gather-start.1 all-gather-start", 850, 950,
                  "collective", spans_async=True))
    return Trace(devices={"/device:TPU:0": ops},
                 spans=[("bench.window", 0, 1000),
                        ("bench.dispatch", 0, 100),
                        ("bench.next_batch", 700, 800)])


def test_reduce_synthetic():
    r = xplane.reduce(_synthetic())
    d = r["devices"][0]
    assert r["window_s"] == pytest.approx(1000e-9)
    assert d["busy_s"] == pytest.approx(800e-9)         # 100-700, 800-1000
    assert d["pallas_s"] == pytest.approx(200e-9)
    assert d["collective_s"] == pytest.approx(300e-9)   # 300-500, 850-950
    # the while loop holds the all-reduce but does not hide it; the
    # async all-gather runs under fusion.3
    assert d["exposed_collective_s"] == pytest.approx(200e-9)
    ops = dict(r["device_ops"])
    assert ops["while.1 while"] == pytest.approx(0.0)    # its self time
    assert ops["k.1 custom-call tpu_custom_call"] == pytest.approx(200e-9)
    assert r["idle_gaps"][0] == ["bench.dispatch", pytest.approx(100e-9)]
    assert r["idle_gaps"][1] == ["bench.next_batch", pytest.approx(100e-9)]


def test_reduce_without_window_is_empty():
    tr = _synthetic()
    tr.spans = tr.spans[1:]
    assert xplane.reduce(tr) == {}


def test_recorded_v5e_trace():
    tr = xplane.load(DATA / "v5e_syrk.xplane.pb")
    assert list(tr.devices) == ["/device:TPU:0"]
    kinds = {o.kind for o in tr.devices["/device:TPU:0"]}
    assert kinds == {"pallas", "compute"}
    names = {n for n, _, _ in tr.spans}
    assert {"bench.window", "bench.dispatch", "bench.loss_read"} <= names
    r = xplane.reduce(tr)
    d = r["devices"][0]
    assert 0 < d["pallas_s"] < d["busy_s"] < r["window_s"]
    assert d["collective_s"] == 0
    assert r["device_ops"][0][0].endswith("custom-call tpu_custom_call")
    assert len(r["idle_gaps"]) <= 10
    assert all(g[0].startswith("bench.") or g[0] == "no bench span"
               for g in r["idle_gaps"])

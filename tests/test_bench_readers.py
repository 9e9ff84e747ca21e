"""The per-layer readers of the mesh cell, ``pixtral-12b.muon-tp4``
(``bench/metrics/``), on a hand-built run: a trace of two chips whose
ops run under a ``repro.blas`` ring route, Muon's 1d wire, the model's
loss and no scope, joined to a matching HLO text by
``bench/scopes.py``.
"""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench import scopes, spec, xplane  # noqa: E402
from bench.xplane import Op, nest  # noqa: E402

CELL = "pixtral-12b.muon-tp4"
NS = "jit(train_step)/optim.muon/optim.muon.ns"
RING = f"{NS}.periods.b0.mlp.wi/ns.gram/blas.syrk.ring"
MUON_1D = f"{NS}.embed/optim.muon_1d/shard_map/while/body/ns.gram"
LOSS = "jit(train_step)/train.loss/jvp(model.blocks)"


def _instr(name, opcode, op_name):
    return (f'  %{name} = f32[8]{{0}} {opcode}(%Arg_0.1), '
            f'metadata={{op_name="{op_name}"}}')


def _hlo_text():
    lines = [
        _instr("dot.1", "dot", f"{RING}/dot_general"),
        _instr("collective-permute.2", "collective-permute",
               f"{RING}/ppermute"),
        _instr("reduce-scatter.3", "reduce-scatter",
               f"{MUON_1D}/reduce_scatter"),
        _instr("dot.4", "dot", f"{MUON_1D}/dot_general"),
        _instr("all-reduce.5", "all-reduce", f"{LOSS}/psum"),
        _instr("add.6", "add", "jit(train_step)/add"),
    ]
    return "\n".join(
        ["HloModule jit_train_step, is_scheduled=true", "",
         "ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {",
         '  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="p"}']
        + lines + ["  ROOT %out.9 = f32[8]{0} copy(%add.6)", "}", ""])


# (name, opcode, kind, start, end) in ns; both chips run the same ops,
# the second 10% longer
OPS = [("dot.1", "dot", "compute", 0, 100),
       ("collective-permute.2", "collective-permute", "collective", 100, 160),
       ("reduce-scatter.3", "reduce-scatter", "collective", 160, 200),
       ("dot.4", "dot", "compute", 200, 300),
       ("all-reduce.5", "all-reduce", "collective", 300, 400),
       ("add.6", "add", "compute", 400, 500)]
OPTIMIZER_NS = 300     # dot.1, the permute, the reduce-scatter, dot.4
COLLECTIVE_NS = 200    # the permute, the reduce-scatter, the all-reduce


def _timeline():
    devices, modules = {}, {}
    for i, f in enumerate((1.0, 1.1)):
        dev = f"/device:TPU:{i}"
        devices[dev] = nest([Op(f"{n} {op}", a * f, b * f, kind)
                             for n, op, kind, a, b in OPS])
        modules[dev] = [("jit_train_step(3)", 0, 600, str(i))]
    return scopes.Timeline(devices=devices, modules=modules,
                           spans=[("bench.window", 0, 600)])


def _ctx(tmp_path, monkeypatch, hlo_text=None):
    tl = _timeline()
    trace_dir = tmp_path / CELL / "trace"
    trace_dir.mkdir(parents=True)
    (trace_dir / "host.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(scopes, "load", lambda path: tl)
    monkeypatch.setattr(xplane, "load", lambda path: tl)
    monkeypatch.setattr(scopes, "compiled_step_text",
                        lambda cell: hlo_text or _hlo_text())
    run = {"trace": {"dir": str(trace_dir), "span": "bench.window",
                     "steps": 2, "window_s": 2.0},
           "work": {"step_flop": 4.0e14},
           "memory": [{"device": i, "peak_bytes_in_use": b,
                       "bytes_limit": 16_000}
                      for i, b in enumerate((4_000, 6_000, 5_000, 0))]}
    return {"run": run, "trace": xplane.reduce(tl), "chips": 4,
            "peak": {"flop_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def _reader(name):
    return spec.load_module(spec.BENCH / "metrics" / f"{name}.py", name)


# the second chip runs 1.1x the first: its mean over chips is 1.05x
@pytest.mark.parametrize("name,want", [
    ("model_device_s", 100 * 1.05e-9 / 2),
    ("optimizer_device_s", OPTIMIZER_NS * 1.05e-9 / 2),
    ("collective_frac", COLLECTIVE_NS * 1.05 / 600),
    ("exposed_collective_frac", COLLECTIVE_NS * 1.05 / 600),
    ("device_idle_frac", 1 - 500 * 1.05 / 600),
    ("step_mfu", 100 * 4.0e14 * 2 / (2.0 * 4 * 197e12)),
    ("hbm_peak_frac", 6_000 / 16_000),
])
def test_reader_on_the_mesh_trace(tmp_path, monkeypatch, name, want):
    assert _reader(name).read(_ctx(tmp_path, monkeypatch)) == \
        pytest.approx(want)


def test_join_labels_each_wire_op(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch)
    assert _reader("optimizer_device_s").read(ctx) is not None
    labels = ctx["scopes"]["join"].ops
    assert labels["reduce-scatter.3 reduce-scatter"] == \
        "optim.muon.ns.embed/optim.muon_1d/ns.gram"
    assert labels["dot.1 dot"] == \
        "optim.muon.ns.periods.b0.mlp.wi/ns.gram/blas.syrk.ring"
    # the breakdown's top ops carry the same labels
    named = dict(ctx["trace"]["device_ops"])
    assert named["optim.muon.ns.embed/optim.muon_1d/ns.gram "
                 "reduce-scatter.3 reduce-scatter"] > 0


@pytest.mark.parametrize("name", ["model_device_s", "optimizer_device_s",
                                  "collective_frac",
                                  "exposed_collective_frac",
                                  "device_idle_frac"])
def test_reader_is_none_without_a_trace(tmp_path, monkeypatch, name):
    ctx = _ctx(tmp_path, monkeypatch)
    ctx["trace"] = {}
    assert _reader(name).read(ctx) is None


def test_step_mfu_is_none_without_a_trace(tmp_path, monkeypatch):
    ctx = _ctx(tmp_path, monkeypatch)
    ctx["run"]["trace"] = None
    assert _reader("step_mfu").read(ctx) is None


@pytest.mark.parametrize("name", ["model_device_s", "optimizer_device_s"])
def test_scoped_reader_is_none_when_the_join_refuses(tmp_path, monkeypatch,
                                                     name):
    # the loss's op named as another opcode than the trace's
    text = _hlo_text().replace(" all-reduce(", " all-gather(")
    ctx = _ctx(tmp_path, monkeypatch, hlo_text=text)
    assert _reader(name).read(ctx) is None
    assert ctx["scopes"] is None


@pytest.mark.parametrize("name", ["collective_frac",
                                  "exposed_collective_frac"])
def test_collective_reader_is_none_with_no_collective(tmp_path, monkeypatch,
                                                      name):
    ctx = _ctx(tmp_path, monkeypatch)
    for d in ctx["trace"]["devices"]:
        d["collective_s"] = d["exposed_collective_s"] = 0.0
    assert _reader(name).read(ctx) is None

"""The join of a trace with the compiled step's HLO metadata
(``bench/scopes.py``), on synthetic traces and on the recorded v5e
trace (``data/v5e_syrk.xplane.pb``).

    python -m pytest -q bench/tests/test_scopes.py
"""
import pathlib

import pytest

from bench import scopes, spec, work
from bench.xplane import Op, nest

#: instructions that move or name data and do no work of their own
NO_WORK = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
           "copy")

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
NS = "jit(train_step)/optim.muon/optim.muon.ns.periods.b0.mlp.wi/while/body"
LOSS = "jit(train_step)/train.loss"

HLO_TEXT = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %negate.1 = f32[8]{0} negate(%param_0), metadata={op_name="jit(train_step)/train.clip/neg"}
}

%body.2 (arg.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg.1), index=1
  %copy.4 = f32[8]{0} copy(%gte.1)
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.1, %copy.4)
}

%cond.3 (arg.2: (s32[], f32[8])) -> pred[] {
  %arg.2 = (s32[], f32[8]{0}) parameter(0)
  ROOT %lt.1 = pred[] constant(false)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="params['w']"}
  %add.1 = f32[8]{0} add(%Arg_0.1, %Arg_0.1), metadata={op_name="jit(train_step)/optim.muon/add"}
  %convert.2 = f32[8]{0} convert(%add.1)
  %fusion.3 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %wrapped.5 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %sub.6 = f32[8]{0} subtract(%wrapped.5, %fusion.3), metadata={op_name="jit(train_step)/train.loss/transpose(jvp(model.blocks))/sub"}
  %init.7 = (s32[], f32[8]{0}) tuple(%Arg_0.1, %sub.6)
  %while.8 = (s32[], f32[8]{0}) while(%init.7), condition=%cond.3, body=%body.2, metadata={op_name="jit(train_step)/train.loss/jvp(model.blocks)/while"}
  ROOT %out.9 = f32[8]{0} get-tuple-element(%while.8), index=1
}
"""


def test_hlo_ops_names_every_instruction():
    hlo = scopes.hlo_ops(HLO_TEXT)
    assert hlo.module == "jit_train_step"
    ops = hlo.ops
    assert ops["add.1"] == ("add", "jit(train_step)/optim.muon/add")
    # no metadata: from its operand, from its fused computation, from
    # the loop that runs its computation
    assert ops["convert.2"][1] == ops["add.1"][1]
    assert ops["fusion.3"][1] == "jit(train_step)/train.clip/neg"
    assert ops["copy.4"][1] == ops["while.8"][1]
    assert ops["while.8"][0] == "while"
    # the entry computation and the loop's body and condition run as
    # ops; the fused computation does not
    assert {"add.1", "while.8", "copy.4", "lt.1"} <= set(hlo.top)
    assert "negate.1" not in hlo.top


@pytest.mark.parametrize("op_name,bucket,label", [
    (f"{LOSS}/jvp(model.blocks)/while/body/closed_call/dot_general",
     "forward", "train.loss[forward]/model.blocks"),
    (f"{LOSS}/transpose(jvp(model.head))/while/body/checkpoint/mul",
     "backward", "train.loss[backward]/model.head"),
    (f"{LOSS}/transpose(jvp(model.blocks))/while/body/closed_call/"
     "checkpoint/rematted_computation/add",
     "recompute", "train.loss[recompute]/model.blocks"),
    ("jit(train_step)/train.clip/mul", "clip", "train.clip"),
    (f"{NS}/closed_call/ns.apply/blas.symm.pallas/pallas_call",
     "optimizer", "optim.muon.ns.periods.b0.mlp.wi/ns.apply/"
     "blas.symm.pallas"),
    # nested model scopes: the inner one that extends an outer one
    # stands for both
    (f"{LOSS}/jvp(model.blocks)/while/body/model.moe/model.moe.route/"
     "dot_general", "forward", "train.loss[forward]/model.blocks/"
     "model.moe.route"),
    (f"{LOSS}/transpose(jvp(model.blocks))/model.moe/mul", "backward",
     "train.loss[backward]/model.blocks/model.moe"),
    ("jit(train_step)/sin", "other", ""),
    ("", "unattributed", ""),
])
def test_buckets_and_labels(op_name, bucket, label):
    assert scopes.bucket(op_name) == bucket
    assert scopes.label(op_name) == label


def _hlo():
    return {
        "while.1": ("while", f"{NS}"),
        "k.1": ("custom-call", f"{NS}/ns.gram/blas.syrk.pallas/pallas_call"),
        "pad.1": ("fusion", f"{NS}/ns.gram/blas.syrk.pallas/pad"),
        "k.2": ("custom-call", f"{NS}/ns.apply/blas.symm.pallas/pallas_call"),
        "fusion.2": ("fusion", f"{LOSS}/jvp(model.blocks)/add"),
        "fusion.3": ("fusion", f"{LOSS}/transpose(jvp(model.blocks))/mul"),
        "fusion.4": ("fusion", "jit(train_step)/train.clip/mul"),
    }


def _timeline(extra=()):
    ops = nest([
        Op("while.1 while", 0, 400, "compute"),
        Op("k.1 custom-call tpu_custom_call", 0, 100, "pallas"),
        Op("pad.1 fusion", 100, 150, "compute"),
        Op("k.2 custom-call tpu_custom_call", 150, 400, "pallas"),
        Op("fusion.2 fusion", 400, 600, "compute"),
        Op("fusion.3 fusion", 600, 900, "compute"),
        Op("fusion.4 fusion", 900, 1000, "compute"),
        # another program's op, outside the step's module
        Op("fusion.9 fusion", 1100, 1200, "compute"),
    ] + list(extra))
    return scopes.Timeline(
        devices={DEV: ops},
        modules={DEV: [("jit_train_step(7)", 0, 1000, "1"),
                       ("jit_other(8)", 1100, 1200, "2")]})


STEPS = {DEV: [(0, 1000)]}


def test_attribute_self_time_into_buckets():
    j = scopes.attribute(_timeline(), _hlo(), STEPS, (0, 1300))
    ns = 1e-9
    assert j.buckets["optimizer"] == pytest.approx(400 * ns)
    assert j.buckets["forward"] == pytest.approx(200 * ns)
    assert j.buckets["backward"] == pytest.approx(300 * ns)
    assert j.buckets["clip"] == pytest.approx(100 * ns)
    assert j.buckets["unattributed"] == 0
    assert sum(j.buckets.values()) == pytest.approx(j.busy_s)
    assert j.leaves == {"periods.b0.mlp.wi": pytest.approx(400 * ns)}
    assert j.products["ns.gram"] == pytest.approx(150 * ns)
    assert j.products["ns.apply"] == pytest.approx(250 * ns)
    assert j.products["rest"] == pytest.approx(0)     # the loop's own
    assert j.kernel == {"syrk": pytest.approx(100 * ns),
                        "symm": pytest.approx(250 * ns)}
    assert j.glue == {"syrk": pytest.approx(50 * ns)}
    assert j.ops["k.2 custom-call tpu_custom_call"] == \
        "optim.muon.ns.periods.b0.mlp.wi/ns.apply/blas.symm.pallas"
    assert j.refusal() is None


def test_labels_keep_each_scope_path_s_self_time():
    j = scopes.attribute(_timeline(), _hlo(), STEPS, (0, 1300))
    ns = 1e-9
    leaf = "optim.muon.ns.periods.b0.mlp.wi"
    assert j.labels == {
        leaf: pytest.approx(0),           # the loop's own
        f"{leaf}/ns.gram/blas.syrk.pallas": pytest.approx(150 * ns),
        f"{leaf}/ns.apply/blas.symm.pallas": pytest.approx(250 * ns),
        "train.loss[forward]/model.blocks": pytest.approx(200 * ns),
        "train.loss[backward]/model.blocks": pytest.approx(300 * ns),
        "train.clip": pytest.approx(100 * ns)}
    assert sum(j.labels.values()) == pytest.approx(
        j.busy_s - j.buckets["unattributed"])


def test_attribute_clips_to_the_window():
    j = scopes.attribute(_timeline(), _hlo(), STEPS, (500, 950))
    assert j.buckets["forward"] == pytest.approx(100e-9)
    assert j.buckets["backward"] == pytest.approx(300e-9)
    assert j.buckets["clip"] == pytest.approx(50e-9)
    assert j.buckets["optimizer"] == 0
    assert j.busy_s == pytest.approx(450e-9)


def test_an_opcode_that_differs_refuses_the_join():
    hlo = dict(_hlo(), **{"fusion.4": ("dot", "jit(train_step)/"
                                       "train.clip/dot")})
    j = scopes.attribute(_timeline(), hlo, STEPS, (0, 1000))
    assert j.mismatches == [("fusion.4", "dot", "fusion")]
    assert "opcode" in j.refusal()


def test_unattributed_time_over_the_limit_refuses_the_join():
    # an op the HLO does not name, 5 ns of 1005: under the limit
    tl = _timeline([Op("copy.1 copy", 1000, 1005, "compute")])
    j = scopes.attribute(tl, _hlo(), {DEV: [(0, 1005)]}, (0, 1005))
    assert j.unattributed_share() == pytest.approx(5 / 1005)
    assert j.refusal() is None
    j = scopes.attribute(_timeline(), dict(_hlo(), **{"fusion.4": None}),
                         STEPS, (0, 1000))
    assert j.buckets["unattributed"] == pytest.approx(100e-9)
    assert "unattributed" in j.refusal()


def test_a_program_without_scopes_gives_no_join():
    """A program that names no scopes (an older commit) reads as
    ``other`` throughout, and the join says so instead of reading 0."""
    hlo = {k: (v[0], "jit(train_step)/while") for k, v in _hlo().items()}
    j = scopes.attribute(_timeline(), hlo, STEPS, (0, 1000))
    assert j.buckets["other"] == pytest.approx(1000e-9)
    assert "scopes" in j.refusal()


def test_per_op_roofline_gives_back_the_pallas_roofline():
    """syrk_roofline and symm_roofline, weighted by each op's kernel
    time, give back pallas_roofline when the kernels under
    ``blas.*.pallas`` are all the Pallas time."""
    j = scopes.attribute(_timeline(), _hlo(), STEPS, (0, 1000))
    calls = [work.Call("syrk", 256, 512, 2, 5),
             work.Call("symm", 256, 256, 2, 5),
             work.Call("symm", 256, 512, 2, 5)]
    peak = {"flop_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    kernel = sum(j.kernel.values())
    ctx = {"scopes": {"join": j, "steps": 2}, "peak": peak, "chips": 1,
           "run": {"work": {"pallas_calls": calls},
                   "trace": {"steps": 2}},
           "trace": {"devices": [{"pallas_s": kernel}]}}
    syrk = scopes.pallas_roofline(ctx, "syrk")
    symm = scopes.pallas_roofline(ctx, "symm")
    need = work.calls_roofline_s(calls[:1], peak) * 2
    assert syrk == pytest.approx(100 * need / j.kernel["syrk"])
    whole = spec.load_module(spec.BENCH / "metrics" / "pallas_roofline.py",
                             "pallas_roofline").read(ctx)
    assert (syrk * j.kernel["syrk"] + symm * j.kernel["symm"]) / kernel \
        == pytest.approx(whole)
    for name in ("syrk_roofline", "symm_roofline", "model_device_s",
                 "optimizer_device_s"):
        reader = spec.load_module(spec.BENCH / "metrics" / f"{name}.py",
                                  name)
        assert reader.read(ctx) > 0
    ctx["scopes"] = None                  # a refused join: no numbers
    assert scopes.pallas_roofline(ctx, "syrk") is None


def test_label_gaps_after_the_clock_offset():
    ops = nest([Op("a.1 fusion", 0, 100, "compute"),
                Op("b.1 fusion", 300, 400, "compute"),
                Op("c.1 fusion", 402, 500, "compute")])
    tl = scopes.Timeline(devices={DEV: ops}, host_spans=[
        # host clock = device clock + 1000
        ("bench.window", 1000, 1500),
        ("bench.next_batch", 1090, 1310),
        ("repro.data.wait", 1100, 1300),
        ("repro.data.produce", 1050, 1120)])
    gaps = scopes.label_gaps(tl, (1000, 1500), (995, 1005))
    # the innermost span that covers the gap, not the one that starts
    # before it; the 2 ns gap is shorter than the 10 ns uncertainty
    assert gaps == [["repro.data.wait", pytest.approx(200e-9)],
                    ["unresolved", pytest.approx(2e-9)]]


def test_clock_offset_bounds():
    tl = scopes.Timeline(
        modules={DEV: [("m(1)", 100, 200, "7"), ("m(1)", 500, 600, "8")]},
        done=[(1300, "7"), (1650, "8")], launches=[1050, 1480])
    # from module 7: >= 1050 - 100, <= 1300 - 200; module 8 tightens
    # both: >= 1480 - 500, <= 1650 - 600
    assert scopes.clock_offset(tl) == (980, 1050)
    tl.done = [(1100, "7")]               # bounds that cross: no offset
    assert scopes.clock_offset(tl) is None


def test_recorded_v5e_trace_has_offset_bounds():
    tl = scopes.load(DATA / "v5e_syrk.xplane.pb")
    assert [m[0] for m in tl.modules[DEV]] == \
        ["jit__lambda(7610979309733675956)"] * 2
    lo, hi = scopes.clock_offset(tl)
    # host minus device, about a millisecond, known to under a ms
    assert 0.5e6 < lo < hi < 3e6 and hi - lo < 1e6
    window = [(a, b) for n, a, b in tl.spans if n == "bench.window"][0]
    gaps = scopes.label_gaps(tl, window, (lo, hi))
    assert gaps[0][0] == "bench.loss_read"
    assert all(g[0].startswith(("bench.", "repro.")) or g[0] == "unresolved"
               for g in gaps)


# ------------------------------------------ a join on a compiled step
@pytest.fixture(scope="module")
def smoke_join():
    """The compiled smoke-size stablelm-1.6b Muon step, joined to a
    trace that runs each of its working instructions once, one after
    another, each for its own length."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.launch.steps import make_optimizer, make_train_step
    from repro.models.model import init_params
    cfg = get_smoke_config("stablelm-1.6b")
    opt = make_optimizer(cfg, "muon")
    step = make_train_step(cfg, opt, loss_chunk=16)
    shape = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
    state = jax.eval_shape(opt.init, shape)
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    hlo = scopes.hlo_ops(jax.jit(step).lower(shape, state, batch)
                         .compile().as_text())
    ops, t = [], 0
    for i, name in enumerate(hlo.top):
        opcode = hlo.ops[name][0]
        if opcode in NO_WORK:
            continue
        ops.append(Op(f"{name} {opcode}", t, t + 10 + i % 7, "compute"))
        t = ops[-1].end
    tl = scopes.Timeline(devices={DEV: nest(ops)},
                         modules={DEV: [(hlo.module, 0, t, "1")]})
    return scopes.attribute(tl, hlo.ops, {DEV: [(0, t)]}, (0, t))


def test_labels_add_up_to_the_buckets_on_a_compiled_step(smoke_join):
    j = smoke_join
    assert j.refusal() is None
    assert sum(j.labels.values()) == pytest.approx(
        j.busy_s - j.buckets["unattributed"])

    def under(prefix):
        return sum(v for k, v in j.labels.items()
                   if k.split("/")[0].startswith(prefix))
    assert under("train.loss[") == pytest.approx(
        sum(j.buckets[b] for b in scopes.MODEL))
    assert under("train.loss[") > 0
    assert under("optim.") == pytest.approx(j.buckets["optimizer"])
    assert under("optim.") > 0
    assert under("train.clip") == pytest.approx(j.buckets["clip"])


# ------------------------------------------------- wire_device_s
WIRE_OPS = [  # (name, opcode, op_name, ns)
    ("dot.1", "dot", f"{NS}/ns.gram/blas.syrk.ring/dot_general", 100),
    ("cp.2", "collective-permute", f"{NS}/ns.gram/blas.syrk.ring/ppermute",
     60),
    ("ar.3", "all-reduce", f"{NS}/ns.apply/blas.symm.1d/psum", 40),
    ("rs.4", "reduce-scatter", "jit(train_step)/optim.muon/optim.muon.ns."
     "embed/optim.muon_1d/shard_map/ns.gram/reduce_scatter", 30),
    ("k.5", "custom-call", f"{NS}/ns.square/blas.symm.pallas/pallas_call",
     70),
    ("add.6", "add", "jit(train_step)/optim.muon/add", 20),
    ("ag.7", "all-gather", f"{LOSS}/jvp(model.blocks)/all_gather", 50),
]


def _wire_ctx():
    ops, hlo, t = [], {}, 0
    for name, opcode, op_name, ns in WIRE_OPS:
        ops.append(Op(f"{name} {opcode}", t, t + ns, "compute"))
        hlo[name] = (opcode, op_name)
        t += ns
    tl = scopes.Timeline(devices={DEV: nest(ops)})
    j = scopes.attribute(tl, hlo, {DEV: [(0, t)]}, (0, t))
    return {"scopes": {"join": j, "steps": 2}}


def _reader(name):
    return spec.load_module(spec.BENCH / "metrics" / f"{name}.py", name)


def test_wire_device_s_reads_both_wires():
    ctx = _wire_ctx()
    wire = _reader("wire_device_s").read(ctx)
    # the ring and 1d routes and Muon's 1d wire; not the Pallas route,
    # the optimizer's own add or the model's all-gather
    assert wire == pytest.approx((100 + 60 + 40 + 30) * 1e-9 / 2)
    assert wire < _reader("optimizer_device_s").read(ctx)


def test_wire_device_s_is_none_without_a_wire():
    j = scopes.attribute(_timeline(), _hlo(), STEPS, (0, 1000))
    reader = _reader("wire_device_s")
    assert reader.read({"scopes": {"join": j, "steps": 2}}) is None
    assert reader.read({"scopes": None}) is None


def test_scope_s_matches_labels_by_pattern():
    ctx = _wire_ctx()
    assert scopes.scope_s(ctx, r"^train\.loss\[forward\]") == \
        pytest.approx(50e-9 / 2)
    assert scopes.scope_s(ctx, r"ns\.square") == pytest.approx(70e-9 / 2)
    assert scopes.scope_s(ctx, r"model\.moe") is None

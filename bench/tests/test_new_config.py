"""A configuration added to the benchmark as new files and new
``BENCHMARK.json`` entries alone: its tiny copy, its own count of the
model's required work and a per-layer metric on the program's scopes
come from its own files, and no file the benchmark has is edited.
Also the program overrides a configuration may nest.

    python -m pytest -q bench/tests/test_new_config.py

Runs are child processes (``rehearse.run_cell``) whose look for a chip
is replaced, so the rest of a run is the real one.
"""
import dataclasses
import hashlib
import json
import re
import shutil
import types

import pytest

from bench import scopes, spec, work
from bench.drivers import train_step
from bench.tests import rehearse
from bench.xplane import Op, nest

REPO = rehearse.REPO
NEW = "dense-probe"
CELL = f"{NEW}.muon"
TINY = f"{NEW}-tiny.muon"
METRIC = "blocks_device_s"
# stablelm-1.6b's keys at tiny widths of their own
TINY_SIZES = {
    "config": {"hidden_size": 32, "num_hidden_layers": 3,
               "num_attention_heads": 2, "num_key_value_heads": 2,
               "head_dim": 16, "intermediate_size": 96, "vocab_size": 256},
    "overrides": {"d_model": 32, "n_layers": 3, "n_heads": 2,
                  "n_kv_heads": 2, "head_dim": 16, "d_ff": 96,
                  "vocab": 256}}
REFERENCE = '''"""dense_decoder under another name, with a model count of its own."""
from bench.references.dense_decoder import follow, layout  # noqa: F401


def model_flop(cfg, batch, seq):
    return 7.0 * batch * seq * cfg["hidden_size"]
'''
READER = '''"""blocks_device_s: device self time per traced step under
``model.blocks``."""
from bench import scopes


def read(ctx):
    return scopes.scope_s(ctx, r"(^|/)model\\.blocks(/|$)")
'''


def _digest(path):
    return hashlib.sha256(path.read_bytes()).hexdigest()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("new_config")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp / "cache"))
        root = rehearse.make_root(tmp)
        b = root / "bench"
        cfg = json.loads((b / "configs" / "stablelm-1.6b.json").read_text())
        cfg.update(name=NEW, reference="dense_probe", tiny=TINY_SIZES)
        (b / "configs" / f"{NEW}.json").write_text(json.dumps(cfg))
        (b / "references" / "dense_probe.py").write_text(REFERENCE)
        (b / "metrics" / f"{METRIC}.py").write_text(READER)
        shutil.copy(b / "limits" / "stablelm-1.6b.muon.json",
                    b / "limits" / f"{CELL}.json")
        bench = json.loads((root / "BENCHMARK.json").read_text())
        bench["configs"].append({"name": NEW, "source": "test",
                                 "file": f"bench/configs/{NEW}.json",
                                 "reduced": [], "why": "test"})
        w = {"name": CELL, "config": NEW, "traffic": "muon", "chips": 1,
             "why": "test"}
        bench["workloads"].append(w)
        bench["per_layer"].append({
            "name": METRIC, "unit": "s", "better": "lower",
            "source": "device_trace", "layer": "model", "moves": "step_s",
            "workloads": [CELL]})
        assert rehearse.add_tiny_cell(root, bench, w) == TINY
        (root / "BENCHMARK.json").write_text(json.dumps(bench))
        yield root


def test_no_file_of_the_benchmark_is_edited(root):
    for f in (REPO / "bench").rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts \
                and "data" not in f.parts:
            assert _digest(root / f.relative_to(REPO)) == _digest(f), f
    bench = json.loads((root / "BENCHMARK.json").read_text())
    orig = json.loads((REPO / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end"):
        assert bench[key][:len(orig[key])] == orig[key]
    assert [m["name"] for m in bench["per_layer"]][:len(orig["per_layer"])] \
        == [m["name"] for m in orig["per_layer"]]


def test_tiny_copy_comes_from_the_config_file(root):
    cfg = rehearse.tiny_config(NEW, root)
    assert "tiny" not in cfg
    assert {k: cfg[k] for k in TINY_SIZES["config"]} == TINY_SIZES["config"]
    assert cfg["program"]["overrides"] == TINY_SIZES["overrides"]


def test_a_nested_tiny_override_extends_the_config_s_own(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "moe.json").write_text(json.dumps({
        "hidden_size": 2048,
        "program": {"arch": "deepseek-v2-236b",
                    "overrides": {"n_layers": 27, "moe": {"n_experts": 8}}},
        "tiny": {"config": {"hidden_size": 64},
                 "overrides": {"n_layers": 3,
                               "moe": {"d_ff_expert": 32}}}}))
    cfg = rehearse.tiny_config("moe", tmp_path)
    assert cfg["hidden_size"] == 64
    assert cfg["program"]["overrides"] == {
        "n_layers": 3, "moe": {"n_experts": 8, "d_ff_expert": 32}}


def test_a_config_without_a_tiny_object_is_named(tmp_path):
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "bare.json").write_text(
        json.dumps({"program": {"arch": "stablelm-1.6b"}}))
    with pytest.raises(spec.SpecError, match="'tiny'"):
        rehearse.tiny_config("bare", tmp_path)


@pytest.mark.parametrize("trace", [0, 1])
def test_new_cell_runs(root, trace):
    rc, out, err, last = rehearse.run_cell(root, TINY, trace=trace)
    assert rc == 0, err[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    if trace:
        # the CPU trace has no TPU plane: the scope reader finds nothing
        # to read, and its metric is left out of the line
        assert METRIC not in last["metrics"]
    else:
        assert set(last["metrics"]) == {"step_s", "setup_s"}
    # its own model count, and the NS chains of its own tiny leaves
    got = json.loads(re.search(r"\[bench\] step_flop: (.*)", out).group(1))
    cfg = rehearse.tiny_config(NEW, root)
    model = 7.0 * 8 * 32 * cfg["hidden_size"]
    shapes = [s for _, s, _, _ in
              spec.load_cell(root, TINY).reference().layout(cfg)]
    ns = work.calls_flop(work.ns_calls(shapes, 5))
    assert got == {"model": model, "ns": ns, "total": model + ns}


def test_new_metric_reads_the_cell_s_own_scopes(root):
    """The new reader, on a join of the tiny cell's compiled step to a
    trace that runs each working instruction once."""
    cell = spec.load_cell(root, TINY)
    hlo = scopes.hlo_ops(scopes.compiled_step_text(cell))
    ops, t = [], 0
    for name in hlo.top:
        opcode = hlo.ops[name][0]
        if opcode not in ("parameter", "constant", "tuple",
                          "get-tuple-element", "bitcast"):
            ops.append(Op(f"{name} {opcode}", t, t + 10, "compute"))
            t += 10
    dev = "/device:TPU:0"
    tl = scopes.Timeline(devices={dev: nest(ops)})
    join = scopes.attribute(tl, hlo.ops, {dev: [(0, t)]}, (0, t))
    assert join.refusal() is None
    ctx = {"scopes": {"join": join, "steps": 2}}
    got = cell.metric_reader(METRIC).read(ctx)
    want = sum(v for k, v in join.labels.items()
               if "model.blocks" in k.split("/")) / 2
    assert got == pytest.approx(want) and got > 0


# ------------------------------------------------ nested overrides
def _cell(arch, overrides):
    return types.SimpleNamespace(
        config={"program": {"arch": arch, "overrides": overrides}})


def test_nested_overrides_replace_fields_of_the_held_dataclass():
    from repro.configs import get_config
    base = get_config("deepseek-v2-236b")
    cfg = train_step._program_config(_cell("deepseek-v2-236b", {
        "n_layers": 3, "moe": {"n_experts": 8}, "mla": {"kv_lora": 32}}))
    assert cfg.n_layers == 3
    assert cfg.moe == dataclasses.replace(base.moe, n_experts=8)
    assert cfg.mla == dataclasses.replace(base.mla, kv_lora=32)
    assert (cfg.d_model, cfg.vocab, cfg.attn_kind) == \
        (base.d_model, base.vocab, base.attn_kind)


def test_flat_overrides_are_applied_as_given():
    from repro.configs import get_config
    over = {"n_layers": 10, "frontend": "tokens", "n_frontend_tokens": 0}
    assert train_step._program_config(_cell("pixtral-12b", over)) == \
        dataclasses.replace(get_config("pixtral-12b"), **over)


@pytest.mark.parametrize("arch,overrides,named", [
    ("stablelm-1.6b", {"moe": {"n_experts": 8}}, "'moe'"),
    ("stablelm-1.6b", {"n_layer": 3}, "'n_layer'"),
    ("deepseek-v2-236b", {"moe": {"n_expert": 8}}, "'moe.n_expert'"),
    ("deepseek-v2-236b", {"d_model": {"x": 1}}, "'d_model'"),
])
def test_a_bad_override_is_refused_by_name(arch, overrides, named):
    with pytest.raises(ValueError, match=named):
        train_step._program_config(_cell(arch, overrides))

"""The harness on the CPU at a tiny size: it refuses to run without a
TPU, finds new files by name, and sees ``correct`` come out false when
the timed path is broken underneath.

    python -m pytest -q bench/tests/test_harness.py

Each run is a child process (``rehearse.run_cell``) whose look for a
chip is replaced, so the rest of a run is the real one.
"""
import hashlib
import json
import os
import pathlib
import subprocess
import sys

import pytest

from bench import run, spec
from bench.tests import rehearse

REPO = rehearse.REPO
CELLS = json.loads((REPO / "BENCHMARK.json").read_text())["workloads"]
TINY_1 = "stablelm-1.6b-tiny.muon"


def tiny(cell) -> str:
    return f"{cell['config']}-tiny.{cell['traffic']}"


# every fault a training cell can have: a step that returns its state
# unchanged, half of the batch left out, and on a mesh the exchange
# between chips left out (no token or answer is produced to alter)
FAULTS = [(tiny(c), c["chips"], f) for c in CELLS
          for f in ("frozen", "half_batch")
          + (("no_exchange",) if c["chips"] > 1 else ())]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp / "cache")
    return rehearse.make_root(tmp)


def _args(workload="stablelm-1.6b.muon"):
    return ["--workload", workload, "--seed", "3", "--seconds", "1",
            "--trace", "0"]


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py"] + _args(),
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


class _Dev:
    platform, device_kind, id = "tpu", "TPU v99 imaginary", 0


def test_exits_nonzero_on_an_unknown_device_kind(monkeypatch, capsys):
    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    assert run.main(_args()) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "TPU v99 imaginary" in out.err


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v99 imaginary")
    assert spec.load_peaks("TPU v5 lite")["flop_per_s"] == 197e12


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A directory that holds only BENCHMARK.json and bench/ has no
    program to run."""
    import shutil
    shutil.copytree(REPO / "bench", tmp_path / "bench")
    (tmp_path / "BENCHMARK.json").write_text(
        (REPO / "BENCHMARK.json").read_text())
    child = rehearse.CHILD
    p = subprocess.run([sys.executable, "-c", child, str(tmp_path), "none"]
                       + _args(), capture_output=True, text=True,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                PYTHONPATH=str(REPO)),
                       cwd=tmp_path, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert "no program under" in p.stderr


def _digest(path: pathlib.Path) -> str:
    return hashlib.sha256(path.read_bytes()).hexdigest()


def test_new_cells_traffic_and_metric_are_found_by_name(root):
    """A configuration, traffic mix, limits and per-layer metric added
    as new files and entries run without an existing file edited."""
    for f in (REPO / "bench").rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts \
                and "data" not in f.parts:
            assert _digest(root / f.relative_to(REPO)) == _digest(f), f
    bench = json.loads((root / "BENCHMARK.json").read_text())
    orig = json.loads((REPO / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads", "end_to_end"):
        assert bench[key][:len(orig[key])] == orig[key]
    # one more per-layer metric: a new reader and a new entry
    (root / "bench" / "metrics" / "window_steps.py").write_text(
        "def read(ctx):\n    return float(ctx['run']['attempted'])\n")
    bench["per_layer"].append({
        "name": "window_steps", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "train step", "moves": "step_s",
        "workloads": [TINY_1]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc, out, err, last = rehearse.run_cell(root, TINY_1, trace=1)
    assert rc == 0, err[-3000:]
    assert last["correct"] is True
    assert last["metrics"]["window_steps"]["value"] == last["attempted"]
    assert list(last)[-1] == "checks"
    assert set(last["checks"]) == {"loss_gap", "grad_gap", "update_gap"}


def test_sound_run_is_correct(root):
    rc, out, err, last = rehearse.run_cell(root, TINY_1)
    assert rc == 0, err[-3000:]
    assert last["correct"] is True and last["failed"] == 0
    assert set(last["metrics"]) == {"step_s", "setup_s"}
    assert last["attempted"] >= 1
    assert err.strip().splitlines()[-1].startswith("check update_gap")


@pytest.mark.parametrize("workload,chips,fault", FAULTS)
def test_planted_fault_is_not_correct(root, workload, chips, fault):
    rc, out, err, last = rehearse.run_cell(root, workload, chips=chips,
                                           fault=fault)
    assert rc == 0, err[-3000:]
    assert last["correct"] is False
    assert any(c["value"] > c["limit"] for c in last["checks"].values())


@pytest.mark.parametrize("variant", ["control", "ns_control"])
@pytest.mark.parametrize("cell", CELLS, ids=[c["name"] for c in CELLS])
def test_control_is_not_correct(cell, variant):
    """The reference one precision step down, everywhere or in the NS
    chain alone, put in the program's place, fails the cell's limits
    (tiny widths, on the CPU)."""
    import numpy as np
    from bench.drivers import train_step
    cfg = rehearse.tiny_config(cell["config"])
    ref = spec.load_module(
        REPO / "bench" / "references" / f"{cfg['reference']}.py",
        cfg["reference"])
    job = json.loads((REPO / "bench" / "traffic" /
                      f"{cell['traffic']}.json").read_text())
    limits = json.loads((REPO / "bench" / "limits" /
                         f"{cell['name']}.json").read_text())
    rng = np.random.default_rng(5)
    rows = rng.integers(0, cfg["vocab_size"], (3, 8, 33))
    batches = [{"tokens": r[:, :-1].astype(np.int32),
                "labels": r[:, 1:].astype(np.int32)} for r in rows]
    want = ref.follow(cfg, job, 5, batches)
    got = ref.follow(cfg, job, 5, batches, variant=variant)
    checks = train_step._compare(got, want, limits)
    assert any(v > lim for _, v, lim in checks), checks

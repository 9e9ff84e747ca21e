"""The benchmark's mesh cell, ``pixtral-12b.muon-tp4``, on the CPU at a
tiny size: its step runs on 4 virtual devices through ``bench/run.py``
(Muon's 1d wire and the ``repro.blas`` mesh routes), and the harness's
own comparison with ``bench/references/dense_decoder.py`` on seeded
random weights calls a sound run correct and each planted fault not.

Each run is a child process (``bench/tests/rehearse.py``) with its own
timeout, so a hang fails the test instead of the suite's clock.
"""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from bench.tests import rehearse  # noqa: E402

CELL = "pixtral-12b-tiny.muon-tp4"
CHILD_TIMEOUT = 150


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh_cell")
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp / "cache"))
        yield rehearse.make_root(tmp)


def _run(root, fault=None):
    rc, out, err, last = rehearse.run_cell(root, CELL, chips=4, fault=fault,
                                           timeout=CHILD_TIMEOUT)
    assert rc == 0, err[-3000:]
    assert set(last["checks"]) == {"loss_gap", "grad_gap", "update_gap"}
    return out, last


def test_sound_mesh_run_is_correct(root):
    out, last = _run(root)
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    assert '[bench] mesh: {"data": 1, "model": 4}' in out
    # the stacked weights' NS products ride the repro.blas mesh routes
    assert '"1d"' in out.split("[bench] routes:")[1].splitlines()[0]


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "no_exchange"])
def test_planted_fault_in_mesh_run_is_not_correct(root, fault):
    _, last = _run(root, fault)
    assert last["correct"] is False
    assert any(c["value"] > c["limit"] for c in last["checks"].values())


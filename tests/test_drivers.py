"""End-to-end driver tests: training loss goes down, crash/restart is
bit-exact, compression trains, serving completes requests."""
import jax
import numpy as np
import pytest

from repro.launch.train import build_argparser as train_ap, train
from repro.launch.serve import build_argparser as serve_ap, serve


def _train_args(**kw):
    base = ["--steps", "12", "--global-batch", "2", "--seq-len", "64",
            "--layers", "2", "--log-every", "100", "--loss-chunk", "64"]
    for k, v in kw.items():
        base += [f"--{k.replace('_', '-')}"]
        if v is not True:
            base += [str(v)]
    return train_ap().parse_args(base)


def test_train_loss_decreases():
    out = train(_train_args(steps=40))
    assert out["final_loss"] < out["first_loss"] - 0.1


def test_train_restart_bit_exact(tmp_path):
    ck = str(tmp_path / "ck")
    ref = train(_train_args(steps=16))
    with pytest.raises(RuntimeError, match="injected"):
        train(_train_args(steps=16, ckpt_dir=ck, ckpt_every=8,
                          fail_at=12))
    resumed = train(_train_args(steps=16, ckpt_dir=ck, ckpt_every=8))
    assert resumed["resumed"]
    assert resumed["final_loss"] == pytest.approx(ref["final_loss"],
                                                  abs=0.0)


@pytest.mark.parametrize("optimizer,compress", [
    ("adamw", False), ("muon", False), ("adamw8bit", False),
    ("adamw", True)], ids=["adamw", "muon", "adamw8bit", "adamw-int8grads"])
def test_train_step_compiles_once(optimizer, compress):
    """Params and optimizer state come back at the shardings the step
    was fed, so steps 1.. reuse step 0's executable."""
    kw = {"compress_grads": True} if compress else {}
    out = train(_train_args(steps=3, optimizer=optimizer, **kw))
    assert out["step_compiles"] == 1
    assert len(out["step_s"]) == 3


def test_train_with_compression():
    out = train(_train_args(steps=20, compress_grads=True))
    assert out["final_loss"] < out["first_loss"]


def test_train_with_muon_syrk():
    """The paper's SYRK/SYMM inside Newton–Schulz actually trains."""
    out = train(_train_args(steps=15, optimizer="muon-syrk", lr=0.02))
    assert np.isfinite(out["final_loss"])
    assert out["final_loss"] < out["first_loss"]


def test_serve_completes_all_requests():
    args = serve_ap().parse_args(
        ["--requests", "6", "--slots", "3", "--max-new", "8",
         "--s-max", "64"])
    out = serve(args)
    assert out["completed"] == 6
    assert out["total_new_tokens"] >= 6 * 8
    assert out["mean_ttft_s"] is not None


def test_serve_more_requests_than_slots_refills():
    args = serve_ap().parse_args(
        ["--requests", "5", "--slots", "2", "--max-new", "4",
         "--s-max", "64"])
    out = serve(args)
    assert out["completed"] == 5

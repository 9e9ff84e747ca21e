"""Automated chaos-recovery driver: device death → elastic resume.

    PYTHONPATH=src python -m repro.launch.recovery

Turns the manual story of ``examples/elastic_restart.py`` into a tested
path.  One call to :func:`run_recovery` runs the full sequence:

  1. **Phase 1** — train on ``devices`` fake devices with an armed
     ``train:step`` *kill* fault (:mod:`repro.distributed.faults`,
     delivered through ``REPRO_FAULTS`` so the subprocess injection is
     reproducible from env alone).  At ``kill_step`` the training loop
     raises :class:`~repro.distributed.faults.DeviceLossError` after
     flushing pending checkpoint writes — a host dropped out of the
     mesh mid-train.
  2. **Phase 2** — restart the same job on ``devices_after`` devices
     (the surviving world).  ``plan_mesh`` re-factorizes the mesh,
     ``restore_checkpoint`` + the PR-7 re-shard path place the saved
     state (packed Gram EMAs travel as triangle words), and
     ``verify_restored`` proves the restored tree — including the
     packed leaves — crc-matches the checkpoint bit-exactly before a
     single step runs.  The run then completes.

The driver parses both phases' output and returns a machine-checkable
summary (asserted in ``dist_checks --suite faults``).  Each phase runs
in a subprocess because a process' jax device count is fixed at first
init — exactly how a real restart looks to the scheduler.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from typing import Any, Dict, List, Optional

from ..distributed import faults

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _run_phase(ckpt_dir: str, ndev: int, extra_args: List[str],
               extra_env: Optional[Dict[str, str]] = None,
               *, steps: int, global_batch: int, seq_len: int,
               layers: int, ckpt_every: int, optimizer: str,
               track_gram: bool, timeout: float
               ) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"  # fake devices: never the chip
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop(faults.ENV_SPECS, None)            # phase 2 runs fault-free
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--steps", str(steps), "--global-batch", str(global_batch),
           "--seq-len", str(seq_len), "--layers", str(layers),
           "--ckpt-dir", ckpt_dir, "--ckpt-every", str(ckpt_every),
           "--log-every", str(max(ckpt_every, 1)), "--max-model", "2",
           "--optimizer", optimizer]
    if track_gram:
        cmd.append("--track-gram")
    cmd += extra_args
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def run_recovery(ckpt_dir: str, *, devices: int = 8,
                 devices_after: int = 6, steps: int = 40,
                 kill_step: int = 20, global_batch: int = 12,
                 seq_len: int = 128, layers: int = 2,
                 ckpt_every: int = 10, optimizer: str = "muon",
                 track_gram: bool = True, seed: int = 0,
                 timeout: float = 900.0) -> Dict[str, Any]:
    """Kill a device mid-train, shrink the world, resume, finish.

    Returns a summary dict::

        {"killed": True,            # phase 1 died of DeviceLossError
         "kill_step": 20,
         "resumed_step": 20,        # phase 2 restart point
         "verified_leaves": 246,    # verify_restored coverage
         "mismatches": 0,           # bit-exact incl. packed Gram EMAs
         "completed": True,         # phase 2 ran to `steps`
         "final": {...}}            # phase 2 [train] done payload

    Raises ``RuntimeError`` when either phase deviates from the script
    (no injected death, failed restart, restore mismatch).
    """
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    phase_kw = dict(steps=steps, global_batch=global_batch,
                    seq_len=seq_len, layers=layers,
                    ckpt_every=ckpt_every, optimizer=optimizer,
                    track_gram=track_gram, timeout=timeout)

    # -- phase 1: armed kill at kill_step --------------------------------
    chaos_env = faults.env_dict(
        [faults.FaultSpec(site="train:step", kind="kill",
                          step=kill_step)], seed=seed)
    p1 = _run_phase(ckpt_dir, devices, [], chaos_env, **phase_kw)
    if p1.returncode == 0 or "injected device loss" not in p1.stderr:
        raise RuntimeError(
            "phase 1 did not die of the injected device loss:\n"
            + p1.stderr[-2000:])

    # -- phase 2: resume on the surviving world --------------------------
    p2 = _run_phase(ckpt_dir, devices_after, [], None, **phase_kw)
    if p2.returncode != 0:
        raise RuntimeError("phase 2 (elastic resume) failed:\n"
                           + p2.stderr[-2000:])
    m_res = re.search(r"resumed from step (\d+)", p2.stdout)
    m_ver = re.search(r"restore verified: (\d+) leaves, (\d+) mismatch",
                      p2.stdout)
    m_done = re.search(r"\[train\] done: (\{.*\})", p2.stdout)
    if not (m_res and m_ver and m_done):
        raise RuntimeError("phase 2 output missing resume/verify/done "
                           "markers:\n" + p2.stdout[-2000:])
    mismatches = int(m_ver.group(2))
    if mismatches:
        raise RuntimeError(
            f"restored state NOT bit-exact: {mismatches} leaf "
            f"crc mismatches\n" + p2.stdout[-2000:])
    final = json.loads(m_done.group(1))
    return {"killed": True, "kill_step": kill_step,
            "resumed_step": int(m_res.group(1)),
            "verified_leaves": int(m_ver.group(1)),
            "mismatches": mismatches,
            "completed": final["steps"] + int(m_res.group(1)) == steps,
            "final": final}


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        description="chaos recovery: device kill -> elastic resume")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_recovery_demo")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--devices-after", type=int, default=6)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--kill-step", type=int, default=20)
    args = ap.parse_args(argv)
    out = run_recovery(args.ckpt_dir, devices=args.devices,
                       devices_after=args.devices_after,
                       steps=args.steps, kill_step=args.kill_step)
    print("[recovery]", json.dumps(out))


if __name__ == "__main__":
    main()

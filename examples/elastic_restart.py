"""Fault tolerance end to end: crash, shrink the world, resume — with
packed-native symmetric state.

    PYTHONPATH=src python examples/elastic_restart.py

Part 1 — training restart.  Phase 1 trains Muon (+ packed momentum-Gram
tracking, ``--track-gram``) on an 8-device mesh and CRASHES at step 20
(injected).  Phase 2 restarts the same job on a 6-device mesh (a host
lost): ``plan_mesh`` re-factorizes (4×2 → 3×2), ``restore_checkpoint``
+ resharding place the saved state — the Gram EMAs travel as packed
triangle words, never densified — and the data pipeline seeks to the
restart step.  The run completes with a continuous loss curve.

Part 2 — elastic re-shard of the triangle-block wire.  A
``ShardedTriTiles`` accumulator saved on the P = c(c+1) = 6 wire of the
8-device world restores bit-exactly on the 6-device world (same c = 2)
AND on a 12-device world (c = 3: every block changes owner), both
through the block-granular element↔(device,slot) bijection — no dense
n×n is ever built (see distributed/elastic.py).

(Each phase runs in a subprocess because a process' jax device count is
fixed at first init.)
"""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CKPT = "/tmp/repro_elastic_demo"
PACKED_CKPT = "/tmp/repro_elastic_demo_packed"


def run_phase(ndev: int, extra):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"  # fake devices: never the chip
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    cmd = [sys.executable, "-m", "repro.launch.train",
           "--steps", "40", "--global-batch", "12", "--seq-len", "128",
           "--layers", "2", "--ckpt-dir", CKPT, "--ckpt-every", "10",
           "--log-every", "10", "--max-model", "2",
           "--optimizer", "muon", "--track-gram"] + extra
    p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=900)
    print(p.stdout)
    return p


def run_packed_phase(ndev: int, phase: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"  # fake devices: never the chip
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--phase", phase],
                       env=env, capture_output=True, text=True,
                       timeout=300)
    print(p.stdout)
    assert p.returncode == 0, p.stderr[-2000:]
    return p


def _packed_phase(phase: str):
    """Runs INSIDE the per-world subprocess."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.packing import ShardedTriTiles, pack_tril
    from repro.distributed import (checkpoint_bytes, restore_checkpoint,
                                   save_checkpoint, wire_c)

    ndev = jax.device_count()
    c = wire_c(ndev)
    n = 48
    if phase == "save":
        a = jax.random.normal(jax.random.key(7), (n, n))
        sym = (a + a.T) / 2
        st = ShardedTriTiles.from_tril(jnp.tril(sym), c)
        # packed_dtype=None: keep f32 words so the re-shard parity check
        # below is bit-exact (default bf16 narrowing gives the 4x bytes
        # saving instead — see the README bytes table)
        save_checkpoint(PACKED_CKPT, 1, {"acc": st, "dense_ref": sym},
                        packed_dtype=None)
        b = checkpoint_bytes(PACKED_CKPT)
        print(f"[packed] saved on P={ndev} (c={c}): acc "
              f"{b['leaves']['acc']} B packed f32 vs dense_ref "
              f"{b['leaves']['dense_ref']} B dense f32")
        return
    # restore on a different world: the like carries THIS world's c
    like = {"acc": ShardedTriTiles.from_tril(jnp.zeros((n, n)), c),
            "dense_ref": jax.ShapeDtypeStruct((n, n), jnp.float32)}
    step, back = restore_checkpoint(PACKED_CKPT, like)
    ref = np.asarray(back["dense_ref"])
    got = np.asarray(back["acc"].to_packed())
    want = np.asarray(pack_tril(jnp.asarray(ref)))
    np.testing.assert_array_equal(got, want)
    print(f"[packed] restored on P={ndev} (c={c}): bit-exact "
          f"re-shard of {got.shape[0]}-word triangle OK")


def main():
    shutil.rmtree(CKPT, ignore_errors=True)
    shutil.rmtree(PACKED_CKPT, ignore_errors=True)
    print("=== phase 1: 8 devices, injected crash at step 20 ===")
    p = run_phase(8, ["--fail-at", "20"])
    assert "injected failure" in p.stderr, p.stderr[-2000:]

    print("=== phase 2: restart on 6 devices (elastic) ===")
    p = run_phase(6, [])
    assert p.returncode == 0, p.stderr[-2000:]
    assert "resumed from step" in p.stdout
    print("elastic restart OK")

    print("=== phase 3: packed wire saved at P=8 (c=2) ===")
    run_packed_phase(8, "save")
    print("=== phase 4: bit-exact restore at P=6 (c=2) ===")
    run_packed_phase(6, "restore")
    print("=== phase 5: bit-exact restore at P=12 (c=3) ===")
    run_packed_phase(12, "restore")
    print("packed elastic re-shard OK")


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--phase":
        _packed_phase(sys.argv[2])
    else:
        main()

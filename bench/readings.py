"""Read the numbers that decide a cell's ``correct`` over many seeds, in
one process, to set their limits.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        --variants control,ns_control,half_batch --variant-seeds 3 \
        --out <file.json>

For each seed the program's step (built once) is driven through its
checked steps, exactly as ``bench/run.py`` drives it, and compared with
the plain reference: these are the sound runs, whose largest reading
is a limit's lower end.  For the first ``--variant-seeds`` seeds each
variant of the reference (``control``: one precision step down;
``ns_control``: the NS chain alone one step down; ``half_batch``;
``no_exchange``) is put in the program's place and
compared with the reference in the same way: the control's smallest
reading is a limit's upper end, and each fault has to fail a limit.
A step that returns its state unchanged reads 1 on ``grad_gap`` and
``update_gap`` by construction and needs no run.  Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="control,half_batch")
    ap.add_argument("--variant-seeds", type=int, default=3)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(ROOT, args.workload)
    try:
        run.check_device(cell.chips)
    except run.DeviceError as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    run.enable_cache()
    import jax
    driver = cell.driver()
    ref_mod = cell.reference()
    step = driver.Step(cell)
    shards = step.mesh.shape["model"]
    seeds = [int(s) for s in args.seeds.split(",")]
    variants = [v for v in args.variants.split(",") if v]
    out = {"workload": cell.name, "limits": cell.limits, "runs": []}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        step.start(seed)
        with jax.set_mesh(step.mesh):
            prog = step.checked_steps()
        step.free()
        t_prog = time.perf_counter() - t
        ref = ref_mod.follow(cell.config, cell.traffic, seed,
                             prog["batches"], exchange_shards=shards)
        t_ref = time.perf_counter() - t - t_prog
        rec = {"seed": seed, "program": _values(driver, prog, ref, cell),
               "losses": prog["losses"], "reference_losses": ref["losses"],
               "program_s": t_prog, "reference_s": t_ref}
        if i < args.variant_seeds:
            for v in variants:
                got = ref_mod.follow(cell.config, cell.traffic, seed,
                                     prog["batches"], variant=v,
                                     exchange_shards=shards)
                rec[v] = _values(driver, got, ref, cell)
        out["runs"].append(rec)
        print(json.dumps(rec), flush=True)
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


def _values(driver, got, ref, cell):
    return {k: v for k, v, _ in driver._compare(got, ref, cell.limits)}


if __name__ == "__main__":
    sys.exit(main())

"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names its configuration and
traffic files; the traffic's ``kind`` picks ``bench/drivers/<kind>.py``,
which builds the program's step, warms it up, measures whole steps for
``--seconds``, and checks what the timed path produced against the
configuration's plain reference.  With ``--trace 0`` the result carries
the cell's end-to-end metrics; with ``--trace 1`` a short steady part of
the window is profiled and the per-layer metrics are read from it by
``bench/metrics/<metric>.py``.

There is no CPU fallback: without a TPU, with fewer chips than the cell
asks for, or with a ``device_kind`` missing from ``bench/peaks.json``,
it exits non-zero and prints no result.  The last line of stdout is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, in a traced run ``breakdown``, and last ``checks``, each
compared number beside its limit (also the last lines of stderr).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402


class DeviceError(RuntimeError):
    pass


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_device(chips: int):
    """The chips the cell asks for, and their peaks; raises otherwise."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise DeviceError(f"no TPU: JAX found {d.platform!r}")
    if len(devs) < chips:
        raise DeviceError(f"the cell needs {chips} chips, found {len(devs)}")
    try:
        peak = spec.load_peaks(d.device_kind)
    except spec.SpecError as e:
        raise DeviceError(str(e)) from e
    return devs[:chips], peak


def enable_cache() -> str:
    import jax
    from repro.launch.compile_cache import enable_compilation_cache
    path = enable_compilation_cache()
    # every program of a run, small ones too, is loaded from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def per_layer(cell, run, reduced, peak) -> dict:
    ctx = {"run": run, "trace": reduced, "peak": peak, "chips": cell.chips}
    out = {}
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.load_cell(ROOT, args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    try:
        devs, peak = check_device(cell.chips)
    except DeviceError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(f"[bench] cache {enable_cache()}")
    prof = ROOT / "artifacts" / "bench" / cell.name / "trace"
    shutil.rmtree(prof, ignore_errors=True)

    run = cell.driver().run(cell, args, T0, prof)

    for k, v in run["log"].items():
        print(f"[bench] {k}: {json.dumps(v)}")
    for m in run["memory"]:
        print(f"[bench] memory {json.dumps(m)}")
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs),
              "memory_peak_bytes": max(m["peak_bytes_in_use"]
                                       for m in run["memory"])}
    out = {"correct": run["correct"], "attempted": run["attempted"],
           "failed": run["failed"]}
    if args.trace:
        from bench import xplane
        found = xplane.find_xplane(prof)
        reduced = xplane.reduce_file(found, run["trace"]["span"]) \
            if found else {}
        print(f"[bench] trace {found}: {json.dumps(reduced)}")
        out["metrics"] = per_layer(cell, run, reduced, peak)
        if reduced:
            devices = reduced["devices"]
            device["busy_s"] = sum(x["busy_s"] for x in devices) / len(devices)
            device["window_s"] = reduced["window_s"]
        out["device"] = device
        if reduced:
            out["breakdown"] = {"device_ops": reduced["device_ops"],
                                "idle_gaps": reduced["idle_gaps"]}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in run["e2e"].items() if k in units}
        out["device"] = device
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, v, lim in run["checks"]}
    for k, v, lim in run["checks"]:
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

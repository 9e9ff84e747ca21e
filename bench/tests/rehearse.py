"""Helpers that drive ``bench/run.py`` on the CPU at a tiny size.

``make_root`` copies the benchmark into a temporary checkout (the
program's ``src`` linked in) and adds tiny cells beside the real ones,
each at the size its configuration file's ``tiny`` object gives;
``run_cell`` runs one cell there in a child process with the harness's
look for a chip replaced, optionally with a fault planted in the
program.  Tests only: the benchmark itself never runs without a TPU.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys
from typing import Dict, Optional

from bench import spec

REPO = pathlib.Path(__file__).resolve().parents[2]


def tiny_config(name: str, root: pathlib.Path = REPO) -> Dict:
    """Configuration ``name`` of the checkout ``root`` at the size its
    file's ``tiny`` object gives: ``tiny.config`` over the source keys,
    ``tiny.overrides`` over the program's overrides (a nested override
    merged into the one it extends)."""
    path = root / "bench" / "configs" / f"{name}.json"
    cfg = spec.load_json(path)
    tiny = cfg.pop("tiny", None)
    if not isinstance(tiny, dict) or not {"config", "overrides"} <= set(tiny):
        raise spec.SpecError(f"{path}: no 'tiny' object with 'config' and "
                             "'overrides', the sizes its CPU tests run")
    cfg.update(tiny["config"])
    cfg["name"] = f"{name}-tiny"
    over = dict(cfg["program"].get("overrides", {}))
    for k, v in tiny["overrides"].items():
        nested = isinstance(v, dict) and isinstance(over.get(k), dict)
        over[k] = {**over[k], **v} if nested else v
    cfg["program"]["overrides"] = over
    return cfg


def add_tiny_cell(root: pathlib.Path, bench: Dict, w: Dict,
                  seq_len: int = 32) -> str:
    """Add to the checkout ``root`` and to ``bench`` (its BENCHMARK.json,
    as a dict) the cell ``<config>-tiny.<traffic>``: cell ``w`` at its
    configuration's tiny size and batch 8 x ``seq_len``, under its
    limits and per-layer metrics.  Returns the new cell's name."""
    b = root / "bench"
    c, traffic = w["config"], w["traffic"]
    (b / "configs" / f"{c}-tiny.json").write_text(
        json.dumps(tiny_config(c, root)))
    if f"{c}-tiny" not in {x["name"] for x in bench["configs"]}:
        bench["configs"].append({"name": f"{c}-tiny", "source": "test",
                                 "file": f"bench/configs/{c}-tiny.json",
                                 "reduced": [], "why": "test"})
    tr = spec.load_json(b / "traffic" / f"{traffic}.json")
    tr["seq_len"] = seq_len
    (b / "traffic" / f"{traffic}-tiny.json").write_text(json.dumps(tr))
    name = f"{c}-tiny.{traffic}"
    shutil.copy(b / "limits" / f"{w['name']}.json",
                b / "limits" / f"{name}.json")
    bench["workloads"].append(dict(w, name=name, config=f"{c}-tiny",
                                   traffic=f"{traffic}-tiny"))
    for m in bench["per_layer"]:
        if w["name"] in m.get("workloads", []):
            m["workloads"].append(name)
    return name


def make_root(tmp: pathlib.Path, seq_len: int = 32) -> pathlib.Path:
    """A checkout with a ``<config>-tiny.<traffic>`` cell
    (:func:`add_tiny_cell`) beside each cell of BENCHMARK.json."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    os.symlink(REPO / "src", root / "src")
    bench = spec.load_json(REPO / "BENCHMARK.json")
    for w in list(bench["workloads"]):
        add_tiny_cell(root, bench, w, seq_len)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


CHILD = r"""
import json, pathlib, sys
root = pathlib.Path(sys.argv[1])
sys.path[:0] = [str(root), str(root / "src")]
from bench import run
fault = sys.argv[2]
def on_cpu(chips):
    import jax
    return jax.devices()[:chips], {"flop_per_s": 197e12,
                                   "hbm_bytes_per_s": 819e9}
run.check_device = on_cpu
if fault != "none":
    from bench.tests import faults
    faults.plant(fault)
sys.exit(run.main(sys.argv[3:]))
"""


def run_cell(root: pathlib.Path, workload: str, seed: int = 7,
             seconds: float = 0.5, trace: int = 0, chips: int = 1,
             fault: Optional[str] = None, timeout: float = 600):
    """(returncode, stdout, stderr, last-line JSON or None)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=f"{root}{os.pathsep}{root / 'src'}")
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    cmd = [sys.executable, "-c", CHILD, str(root), fault or "none",
           "--workload", workload, "--seed", str(seed),
           "--seconds", str(seconds), "--trace", str(trace)]
    p = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=timeout, cwd=root)
    last = None
    lines = p.stdout.strip().splitlines()
    if p.returncode == 0 and lines:
        last = json.loads(lines[-1])
    return p.returncode, p.stdout, p.stderr, last

"""Helpers that drive ``bench/run.py`` on the CPU at a tiny size.

``make_root`` copies the benchmark into a temporary checkout (the
program's ``src`` linked in) and adds tiny cells beside the real ones;
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

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY = {
    "stablelm-1.6b": {"hidden_size": 64, "num_hidden_layers": 2,
                      "num_attention_heads": 4, "num_key_value_heads": 4,
                      "head_dim": 16, "intermediate_size": 160,
                      "vocab_size": 512},
    "pixtral-12b": {"hidden_size": 64, "num_hidden_layers": 2,
                    "num_attention_heads": 4, "num_key_value_heads": 2,
                    "head_dim": 16, "intermediate_size": 128,
                    "vocab_size": 512},
}
PROGRAM_KEYS = {"hidden_size": "d_model", "num_hidden_layers": "n_layers",
                "num_attention_heads": "n_heads",
                "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
                "intermediate_size": "d_ff", "vocab_size": "vocab"}


def tiny_config(name: str) -> Dict:
    cfg = json.loads((REPO / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg.update(TINY[name])
    cfg["name"] = f"{name}-tiny"
    cfg["program"]["overrides"] = dict(
        cfg["program"].get("overrides", {}),
        **{PROGRAM_KEYS[k]: v for k, v in TINY[name].items()})
    return cfg


#: a cell whose files are here but which is not yet in BENCHMARK.json
#: (not measured on the chip); its tiny copy takes the limits of
#: ``LIMITS_OF``
PREPARED = [{"name": "pixtral-12b.muon-tp4", "config": "pixtral-12b",
             "traffic": "muon-tp4", "chips": 4, "why": "prepared"}]
LIMITS_OF = "stablelm-1.6b.muon"


def make_root(tmp: pathlib.Path, seq_len: int = 32) -> pathlib.Path:
    """A checkout with a ``<config>-tiny.<traffic>`` cell for each cell
    of BENCHMARK.json and each prepared cell, at a tiny size and batch
    8 x ``seq_len``."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    os.symlink(REPO / "src", root / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    names = {w["name"] for w in bench["workloads"]}
    for w in list(bench["workloads"]) + [p for p in PREPARED
                                         if p["name"] not in names]:
        c = w["config"]
        cfg = tiny_config(c)
        (root / "bench" / "configs" / f"{c}-tiny.json").write_text(
            json.dumps(cfg))
        bench["configs"].append({"name": f"{c}-tiny", "source": "test",
                                 "file": f"bench/configs/{c}-tiny.json",
                                 "reduced": [], "why": "test"})
        tr = json.loads((root / "bench" / "traffic" /
                         f"{w['traffic']}.json").read_text())
        tr["seq_len"] = seq_len
        (root / "bench" / "traffic" / f"{w['traffic']}-tiny.json") \
            .write_text(json.dumps(tr))
        name = f"{c}-tiny.{w['traffic']}"
        limits = root / "bench" / "limits" / f"{w['name']}.json"
        if not limits.is_file():
            limits = root / "bench" / "limits" / f"{LIMITS_OF}.json"
        shutil.copy(limits, root / "bench" / "limits" / f"{name}.json")
        bench["workloads"].append(dict(w, name=name, config=f"{c}-tiny",
                                       traffic=f"{w['traffic']}-tiny"))
        for m in bench["per_layer"]:
            if w["name"] in m.get("workloads", []):
                m["workloads"].append(name)
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

"""Compile a cell's train step for a described TPU v5e, without a chip,
and print what the compiler says of its memory.

    JAX_PLATFORMS=cpu python3 bench/fit.py --workload pixtral-12b.muon-tp4
    JAX_PLATFORMS=cpu python3 bench/fit.py --workload pixtral-12b.muon-tp4 --layers 8

The step is built from the cell's files as ``bench/run.py`` builds it,
on the first ``chips`` devices of a described ``v5e:2x2`` (data x model
as the traffic plans it), and compiled from shapes alone.  The v5e
compiler refuses a program that does not fit the chip's memory; that
refusal is the fit test to trust.  ``--layers`` overrides the depth, to
find the deepest that fits.
"""
from __future__ import annotations

import argparse
import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    cell = spec.load_cell(ROOT, args.workload)
    if args.layers:
        cell.config["num_hidden_layers"] = args.layers
        cell.config["program"]["overrides"]["n_layers"] = args.layers
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devs = topo.devices[:cell.chips]
    from repro.distributed.elastic import plan_shape
    data, model = plan_shape(len(devs), max_model=cell.traffic["max_model"])
    mesh = Mesh(np.array(devs).reshape(data, model), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    # the program picks its kernels by backend: plan them for the TPU
    jax.default_backend = lambda: "tpu"

    step = cell.driver().Step(cell, mesh=mesh)

    def sds(shape_tree, shardings):
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shape_tree, shardings)

    job = cell.traffic
    b = (job["global_batch"], job["seq_len"])
    batch = {k: jax.ShapeDtypeStruct(b, np.int32, sharding=step.b_sh[k])
             for k in ("tokens", "labels")}
    t = time.perf_counter()
    with jax.set_mesh(mesh), step.routing.capture_routes() as log:
        lowered = step.jit_step.lower(sds(step.shape, step.p_sh),
                                      sds(step.state_shape, step.o_sh),
                                      batch)
    print(f"[fit] traced in {time.perf_counter() - t:.1f}s; routes "
          f"{sorted({(r.op, r.n1, r.n2, r.path) for r in log})}")
    t = time.perf_counter()
    compiled = lowered.compile()
    print(f"[fit] compiled in {time.perf_counter() - t:.1f}s for "
          f"{len(devs)} x {devs[0].device_kind}, mesh {dict(mesh.shape)}, "
          f"{cell.config['num_hidden_layers']} layers")
    ma = compiled.memory_analysis()
    print(f"[fit] memory per device: {ma}")
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "tpu_custom_call"):
        print(f"[fit] {op}: {text.count(op)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

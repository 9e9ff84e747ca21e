import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the
# device count on first initialization) — do not move them.

import argparse      # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.analysis.roofline import build_roofline  # noqa: E402
from repro.configs import ARCHS, get_config  # noqa: E402
from repro.configs.shapes import (SHAPES, cell_applicable,  # noqa: E402
                                  input_specs)
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import (make_decode_step,  # noqa: E402
                                make_optimizer, make_prefill_step,
                                make_train_step)
from repro.models.model import init_params  # noqa: E402
from repro.models.sharding import (batch_specs, cache_specs,  # noqa: E402
                                   param_specs)
from repro.optim import AdamWState, MuonState  # noqa: E402


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _opt_shardings(opt_state, p_shardings, mesh):
    """Optimizer-state shardings: moments inherit the parameter sharding
    (ZeRO-style: states live wherever the param shard lives)."""
    rep = NamedSharding(mesh, P())

    def match(state_leaf_path_tree):
        return state_leaf_path_tree

    if isinstance(opt_state, AdamWState):
        def like_params(x):
            return jax.tree.map(lambda _, s: s, x, p_shardings) \
                if x is not None else None
        return AdamWState(
            step=rep,
            m=like_params(opt_state.m), v=like_params(opt_state.v),
            m_scale=(jax.tree.map(lambda _: rep, opt_state.m_scale)
                     if opt_state.m_scale is not None else None),
            v_scale=(jax.tree.map(lambda _: rep, opt_state.v_scale)
                     if opt_state.v_scale is not None else None))
    if isinstance(opt_state, MuonState):
        return MuonState(step=rep,
                         momentum=jax.tree.map(lambda _, s: s,
                                               opt_state.momentum,
                                               p_shardings))
    return jax.tree.map(lambda _: rep, opt_state)


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                optimizer: str = "adamw", microbatches: int = 1,
                loss_chunk: int = 512, verbose: bool = True) -> dict:
    """Lower + compile one (arch × shape × mesh) cell; return the record
    for EXPERIMENTS.md (§Dry-run / §Roofline)."""
    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    if not cell_applicable(cfg, shape_name):
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped (full attention at 500k — DESIGN §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()

    params_shape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0)))
    p_specs = param_specs(cfg, params_shape, mesh)
    p_shardings = _ns(mesh, p_specs)
    specs = input_specs(cfg, shape_name)
    has_pod = "pod" in mesh.shape

    if cell.kind == "train":
        opt = make_optimizer(cfg, optimizer, mesh=mesh)
        opt_state_shape = jax.eval_shape(opt.init, params_shape)
        o_shardings = _opt_shardings(opt_state_shape, p_shardings, mesh)
        step = make_train_step(cfg, opt, microbatches=microbatches,
                               loss_chunk=loss_chunk)
        bs = batch_specs(cfg, mesh, cell.global_batch, has_pod)
        b_shardings = {k: NamedSharding(mesh, bs[k]) for k in specs}
        fn = jax.jit(step, in_shardings=(p_shardings, o_shardings,
                                         b_shardings))
        args = (params_shape, opt_state_shape, specs)
    elif cell.kind == "prefill":
        step = make_prefill_step(cfg, s_max=cell.seq_len)
        bs = batch_specs(cfg, mesh, cell.global_batch, has_pod)
        b_shardings = {k: NamedSharding(mesh, bs[k]) for k in specs}
        fn = jax.jit(step, in_shardings=(p_shardings, b_shardings))
        args = (params_shape, specs)
    else:  # decode
        step = make_decode_step(cfg)
        cache_shape = specs["cache"]
        c_shardings = _ns(mesh, cache_specs(cfg, cache_shape, mesh,
                                            cell.global_batch))
        bs = batch_specs(cfg, mesh, cell.global_batch, has_pod)
        tok_spec = bs["embeds"] if cfg.frontend == "embeddings" \
            else bs["tokens"]
        fn = jax.jit(step, in_shardings=(
            p_shardings, NamedSharding(mesh, tok_spec),
            NamedSharding(mesh, bs["positions"]), c_shardings))
        args = (params_shape, specs["token"], specs["pos"], cache_shape)

    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    roof, coll = build_roofline(cost, hlo, chips)

    # MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (inference)
    n_active = cfg.active_param_count()
    if cell.kind == "train":
        tokens = cell.global_batch * cell.seq_len
        model_flops = 6.0 * n_active * tokens
    elif cell.kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        model_flops = 2.0 * n_active * tokens
    else:  # decode: one new token per sequence
        model_flops = 2.0 * n_active * cell.global_batch

    rec = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "chips": chips, "optimizer": optimizer if cell.kind == "train"
        else None,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": roof.flops,
        "raw_cost_analysis_flops": roof.raw_flops,
        "raw_cost_analysis_bytes": roof.raw_bytes,
        "model_flops_total": model_flops,
        "model_vs_hlo_flops": model_flops / max(roof.flops * chips, 1e-30),
        "unknown_trip_whiles": roof.unknown_trip_whiles,
        "hbm_bytes_per_device": roof.hbm_bytes,
        "collective_operand_bytes": roof.collective_bytes,
        "collective_wire_bytes": roof.wire_bytes,
        "collective_counts": coll.counts,
        "collective_by_kind": coll.op_bytes,
        "compute_s": roof.compute_s,
        "memory_s": roof.memory_s,
        "collective_s": roof.collective_s,
        "dominant": roof.dominant,
        "roofline_fraction": roof.roofline_fraction(),
        "memory_analysis": {
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "generated_code_bytes":
                getattr(mem, "generated_code_size_in_bytes", None),
        },
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × "
              f"{'2x16x16' if multi_pod else '16x16'}: OK "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops/device={roof.flops:.3e} "
              f"bytes/device={roof.hbm_bytes:.3e}")
        print(f"  collectives: {coll.counts} operand_bytes="
              f"{roof.collective_bytes:.3e}")
        print(f"  roofline: compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms "
              f"dominant={roof.dominant} "
              f"fraction={roof.roofline_fraction():.3f}")
        print(f"  model_flops={model_flops:.3e} "
              f"useful-ratio={rec['model_vs_hlo_flops']:.3f}")
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape) cell")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--loss-chunk", type=int, default=512)
    ap.add_argument("--out", default=None, help="append JSONL records here")
    args = ap.parse_args()

    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    rec = dryrun_cell(arch, shape, multi_pod=mp,
                                      optimizer=args.optimizer,
                                      microbatches=args.microbatches,
                                      loss_chunk=args.loss_chunk)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "status": f"FAILED: {e}"}
                    failures += 1
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(rec) + "\n")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

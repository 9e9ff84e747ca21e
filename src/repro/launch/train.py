"""End-to-end fault-tolerant training driver.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --smoke --steps 300 --global-batch 8 --seq-len 256

Production behaviors demonstrated end-to-end (all on the CPU mesh here;
the same code paths shard on a pod via the production mesh):

  * mesh planned from the LIVE device count (elastic restarts resume on
    whatever world survives — distributed/elastic.py),
  * deterministic sharded data pipeline that seeks to the restart step,
  * atomic async checkpoints every ``--ckpt-every`` steps + resume,
  * straggler monitor with warn/checkpoint/evict escalation,
  * optional int8 gradient compression with error feedback,
  * optional Muon-SYRK optimizer — the paper's communication-optimal
    SYRK/SYMM driving Newton–Schulz orthogonalization.

``--fail-at N`` injects a crash at step N (exercised by the restart
integration test).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.data import DataConfig, make_train_iterator
from repro.distributed import (ErrorFeedbackInt8, StepTimer,
                               StragglerMonitor, checkpoint_bytes, faults,
                               latest_step, plan_mesh, restore_checkpoint,
                               save_checkpoint, verify_restored,
                               wait_for_saves)
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.steps import (describe_blas_routing, make_optimizer,
                                make_train_step)
from repro.models.model import init_params
from repro.models.sharding import batch_specs, param_specs


def _ns(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def build_config(args):
    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch)
    overrides: Dict[str, Any] = {}
    if args.layers:
        overrides["n_layers"] = args.layers
    if args.d_model:
        overrides["d_model"] = args.d_model
        overrides["d_ff"] = args.d_ff or args.d_model * 4
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def train(args) -> Dict[str, Any]:
    mesh = plan_mesh(max_model=args.max_model)
    dp = mesh.shape["data"]
    if args.global_batch % dp:
        raise SystemExit(f"--global-batch must divide data axis {dp}")
    cfg = build_config(args)

    opt = make_optimizer(cfg, args.optimizer, lr=args.lr, mesh=mesh,
                         track_gram=args.track_gram)
    compressor = ErrorFeedbackInt8() if args.compress_grads else None
    step_fn = make_train_step(cfg, opt, microbatches=args.microbatches,
                              loss_chunk=args.loss_chunk,
                              compressor=compressor)

    params_shape = jax.eval_shape(lambda: init_params(cfg,
                                                      jax.random.key(0)))
    n_params = sum(int(np.prod(x.shape))
                   for x in jax.tree.leaves(params_shape))
    p_specs = param_specs(cfg, params_shape, mesh)
    p_sh = _ns(mesh, p_specs)

    if args.optimizer.startswith("muon"):
        print("[train] symmetric-BLAS routing (repro.blas):")
        for line in describe_blas_routing(params_shape, mesh):
            print(line)

    def init_state(params):
        if compressor is None:
            return opt.init(params)
        return opt.init(params), compressor.init(params)

    # the step is fed and returns state at exactly these shardings, so
    # step 1 reuses step 0's executable
    state_shape = jax.eval_shape(init_state, params_shape)
    o_sh = _state_shardings(state_shape, params_shape, p_sh, mesh)

    # ---- init or resume -------------------------------------------------
    start_step = 0
    resumed = False
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None \
            and not args.fresh:
        like = {"params": params_shape}
        if compressor is not None:
            like["opt"], like["ef"] = state_shape
        else:
            like["opt"] = state_shape
        start_step, state = restore_checkpoint(args.ckpt_dir, like)
        vr = verify_restored(args.ckpt_dir, state, step=start_step)
        print(f"[train] restore verified: {vr['checked']} leaves, "
              f"{len(vr['mismatches'])} mismatches")
        params = jax.device_put(state["params"], p_sh)
        opt_state = state["opt"] if compressor is None \
            else (state["opt"], state["ef"])
        opt_state = jax.device_put(opt_state, o_sh)
        resumed = True
        print(f"[train] resumed from step {start_step} "
              f"({args.ckpt_dir})")
    else:
        with jax.set_mesh(mesh):
            params = jax.jit(
                lambda k: init_params(cfg, k),
                out_shardings=p_sh)(jax.random.key(args.seed))
        opt_state = jax.jit(init_state, out_shardings=o_sh)(params)

    # ---- data ------------------------------------------------------------
    dcfg = DataConfig(seq_len=args.seq_len, global_batch=args.global_batch,
                      vocab_size=cfg.vocab, seed=args.data_seed)
    bspecs = batch_specs(cfg, mesh, args.global_batch, False)
    b_sh = {k: NamedSharding(mesh, bspecs[k]) for k in ("tokens", "labels")}
    it = make_train_iterator(dcfg, start_step=start_step, sharding=b_sh,
                             frontend="tokens")

    jit_step = jax.jit(step_fn,
                       in_shardings=(p_sh, o_sh, b_sh),
                       out_shardings=(p_sh, o_sh, NamedSharding(mesh, P())),
                       donate_argnums=(0, 1))
    monitor = StragglerMonitor(threshold=args.straggler_threshold)
    timer = StepTimer(monitor)
    losses, step_s = [], []

    with jax.set_mesh(mesh):
        for step in range(start_step, args.steps):
            if args.fail_at is not None and step == args.fail_at \
                    and not resumed:
                it.close()
                wait_for_saves()
                raise RuntimeError(f"injected failure at step {step}")
            if not resumed:
                try:
                    faults.maybe_fail("train:step", step)
                except faults.DeviceLossError:
                    # a host dropped out: flush checkpoint writes so the
                    # surviving world resumes from the last commit, then
                    # surface the loss to the elastic-restart driver
                    it.close()
                    wait_for_saves()
                    raise
            batch = next(it)
            with timer:
                faults.maybe_fail("train:straggler", step)
                params, opt_state, metrics = jit_step(params, opt_state,
                                                      batch)
                loss = float(metrics["loss"])
            losses.append(loss)
            step_s.append(timer.last)
            if timer.event is not None:
                print(f"[straggler] step {step}: {timer.event.action} "
                      f"({timer.event.ratio:.1f}x median)")
                if timer.event.action == "checkpoint" and args.ckpt_dir:
                    _save(args, step + 1, params, opt_state, compressor)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step:5d} loss {loss:.4f} "
                      f"({timer.last*1e3:.0f} ms)")
            if args.ckpt_dir and args.ckpt_every \
                    and (step + 1) % args.ckpt_every == 0:
                _save(args, step + 1, params, opt_state, compressor)
    it.close()
    if args.ckpt_dir:
        _save(args, args.steps, params, opt_state, compressor,
              blocking=True)
    wait_for_saves()

    out = {"arch": cfg.name, "params": n_params,
           "steps": args.steps - start_step,
           "final_loss": losses[-1] if losses else None,
           "first_loss": losses[0] if losses else None,
           "step_s": step_s,
           # one entry per distinct input signature: a step fed state at
           # other shardings than it returned would add a second
           "step_compiles": jit_step._cache_size(),
           "straggler_events": len(monitor.events),
           "resumed": resumed, "mesh": dict(mesh.shape)}
    if args.ckpt_dir:
        out["ckpt_bytes"] = checkpoint_bytes(args.ckpt_dir)["total"]
    print("[train] done:", json.dumps(out))
    return out


def _state_shardings(state_shape, params_shape, p_sh, mesh):
    """Optimizer-state shardings: in every subtree that mirrors the params
    tree (moments, momentum, error feedback), a leaf of its param's shape
    takes the param's sharding; every other leaf (quantized blocks and
    their scales, counters, packed Grams) is replicated."""
    p_def = jax.tree.structure(params_shape)
    rep = NamedSharding(mesh, P())

    def mirrors_params(x):
        return jax.tree.structure(x) == p_def

    def pick(sub):
        if not mirrors_params(sub):
            return jax.tree.map(lambda _: rep, sub)
        return jax.tree.map(lambda x, p, s: s if x.shape == p.shape else rep,
                            sub, params_shape, p_sh)

    return jax.tree.map(pick, state_shape, is_leaf=mirrors_params)


def _save(args, step, params, opt_state, compressor, blocking=False):
    tree = {"params": params}
    if compressor is not None:
        tree["opt"], tree["ef"] = opt_state
    else:
        tree["opt"] = opt_state
    save_checkpoint(args.ckpt_dir, step, tree, keep=args.ckpt_keep,
                    blocking=blocking,
                    extra={"global_batch": args.global_batch,
                           "seq_len": args.seq_len})


def build_argparser():
    ap = argparse.ArgumentParser(description="fault-tolerant LM training")
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--d-ff", type=int, default=0)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adamw8bit", "muon", "muon-syrk"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--loss-chunk", type=int, default=256)
    ap.add_argument("--max-model", type=int, default=4)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--track-gram", action="store_true",
                    help="EMA packed momentum-Grams in the Muon state "
                         "(typed PackedTriangle leaves; the checkpoint "
                         "layer stores them packed bf16)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-keep", type=int, default=3)
    ap.add_argument("--fresh", action="store_true",
                    help="ignore existing checkpoints")
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=0)
    ap.add_argument("--straggler-threshold", type=float, default=3.0)
    return ap


def main(argv=None):
    args = build_argparser().parse_args(argv)
    enable_compilation_cache()
    train(args)


if __name__ == "__main__":
    main()

"""Driver for traffic of kind ``train_step``: the program's jitted train
step, built as ``repro.launch.train.train`` builds it.

Set-up builds one object, the compiled step with its state, from the
program's own functions (``plan_mesh``, ``make_optimizer``,
``make_train_step``, ``param_specs``, ``train._state_shardings``, the
shapes of ``init_params``), with weights made on the device from the
seed.  It drives that object through the first ``checked_steps`` steps
on batches from ``make_train_iterator`` (these compile, or load from the
cache, and warm up), reads what the check compares, and hands the same
object to the window.  The window runs whole steps until ``seconds``
have passed.  Once it has closed and the program's state is freed, the
configuration's plain reference follows the checked steps from the same
seed and batches, and the readings are compared with the cell's limits.

Traffic keys: ``optimizer``, ``max_model`` (as the ``train`` CLI takes
them), ``global_batch``, ``seq_len``, ``loss_chunk``, ``checked_steps``,
``traced_steps``, and the optimizer's stated hyper-parameters, which
set-up checks against the program's optimizer.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import pathlib
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from bench import weights, work


class CompileCounter:
    """Counts compile and cache-load events while ``active``."""

    def __init__(self):
        self.active = False
        self.events: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.active and "compil" in event:
            self.events.append(event)


def _program_config(cell):
    """The program's ``ArchConfig``: the configuration's ``program.arch``
    with its ``program.overrides`` applied.  A dict overrides fields of
    the dataclass that a field holds (``{"moe": {"n_experts": 8}}``); a
    dict for a field that holds none, or a field the config does not
    have, is an error that names it."""
    from repro.configs import get_config
    prog = cell.config["program"]
    return _override(get_config(prog["arch"]), prog.get("overrides", {}))


def _override(obj, overrides: Dict[str, Any], where: str = ""):
    fields = {f.name for f in dataclasses.fields(obj)}
    new = {}
    for key, value in overrides.items():
        name = where + key
        if key not in fields:
            raise ValueError(f"program override {name!r}: "
                             f"{type(obj).__name__} has no such field")
        if isinstance(value, dict):
            held = getattr(obj, key)
            if not dataclasses.is_dataclass(held):
                raise ValueError(f"program override {name!r}: a dict for "
                                 f"a field that holds {held!r}, not a "
                                 "dataclass")
            value = _override(held, value, name + ".")
        new[key] = value
    return dataclasses.replace(obj, **new)


def _check_optimizer(opt, job) -> None:
    """The program runs the optimizer the traffic file states."""
    from repro.optim import muon
    if not job["optimizer"].startswith("muon"):
        raise NotImplementedError(f"optimizer {job['optimizer']!r}")
    stated = {"lr": job["lr"], "momentum": job["momentum"],
              "ns_steps": job["ns_steps"], "fallback_lr": job["fallback_lr"],
              "weight_decay": 0.0}
    got = {k: getattr(opt, k) for k in stated}
    if got != stated or tuple(muon.NS_COEFFS) != tuple(job["ns_coeffs"]):
        raise RuntimeError(f"program optimizer {got} {muon.NS_COEFFS} "
                           f"departs from the traffic file {stated} "
                           f"{job['ns_coeffs']}")


def first_gradient(opt_state):
    """The gradient as the optimizer got it, from its state after one
    step: Muon's momentum starts at zero, so it is that gradient."""
    if hasattr(opt_state, "momentum"):
        return opt_state.momentum
    raise NotImplementedError(type(opt_state).__name__)


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))),
        tree)


def _compare(prog: Dict, ref: Dict, limits: Dict[str, float]) -> List:
    """(name, reading, limit) of each number that decides ``correct``.

    loss_gap:   worst step's |loss - reference| / |reference|;
    grad_gap:   worst leaf's |norm - reference norm| of the first
                gradient, over max(that leaf's reference norm, the
                median leaf's);
    update_gap: the same for the parameters' change over the checked
                steps, leaving out leaves whose reference gradient is
                under a thousandth of the median leaf's."""
    def gap(a, b, keep):
        med = float(np.median([b[k] for k in keep]))
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in keep)

    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    g_ref = ref["grad_norms"]
    g_med = float(np.median(list(g_ref.values())))
    moving = [k for k in g_ref if g_ref[k] >= 1e-3 * g_med]
    readings = {"loss_gap": loss_gap,
                "grad_gap": gap(prog["grad_norms"], g_ref, list(g_ref)),
                "update_gap": gap(prog["update_norms"],
                                  ref["update_norms"], moving)}
    for k, v in readings.items():
        if not math.isfinite(v):
            readings[k] = float("inf")
    return [(k, readings[k], limits[k]) for k in ("loss_gap", "grad_gap",
                                                   "update_gap")]


class Step:
    """The program's train step, built from the cell; :meth:`start`
    gives it weights, optimizer state and a batch feed from a seed."""

    def __init__(self, cell, mesh=None):
        from repro.blas import routing
        from repro.distributed import plan_mesh
        from repro.launch import train as train_mod
        from repro.launch.steps import make_optimizer, make_train_step
        from repro.models.model import init_params
        from repro.models.sharding import batch_specs, param_specs

        job = cell.traffic
        self.job = job
        self.cfg = cfg = _program_config(cell)
        self.layout = cell.reference().layout(cell.config)
        self.mesh = mesh = mesh or plan_mesh(max_model=job["max_model"])
        opt = make_optimizer(cfg, job["optimizer"], mesh=mesh)
        _check_optimizer(opt, job)
        step_fn = make_train_step(cfg, opt, loss_chunk=job["loss_chunk"],
                                  clip_norm=job["clip_norm"])

        shape = jax.eval_shape(lambda: init_params(cfg, jax.random.key(0)))
        want = {p: (tuple(s), jnp.dtype(d).name)
                for p, s, d, _ in self.layout}
        got = {p: (tuple(x.shape), x.dtype.name)
               for p, x in weights.flatten(shape).items()}
        if want != got:
            raise RuntimeError(
                "program parameters differ from the configuration: "
                f"{sorted(set(want.items()) ^ set(got.items()))}")
        self.p_sh = p_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, s), param_specs(cfg, shape, mesh),
            is_leaf=lambda x: isinstance(x, P))
        self.shape = shape
        self.state_shape = state_shape = jax.eval_shape(opt.init, shape)
        self.o_sh = o_sh = train_mod._state_shardings(state_shape, shape,
                                                      p_sh, mesh)
        self.opt_init = jax.jit(opt.init, out_shardings=o_sh)
        bspecs = batch_specs(cfg, mesh, job["global_batch"], False)
        self.b_sh = b_sh = {k: NamedSharding(mesh, bspecs[k])
                            for k in ("tokens", "labels")}
        self.jit_step = jax.jit(
            step_fn, in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, NamedSharding(mesh, P())),
            donate_argnums=(0, 1))
        self.routing = routing
        self.routes = []
        self.params = self.opt_state = self.batches = None

    def start(self, seed: int) -> None:
        """Weights made on the device from ``seed``, fresh optimizer
        state, and the program's batch feed seeded with it."""
        from repro.data import DataConfig, make_train_iterator
        job = self.job
        self.seed = seed
        self.params = weights.generate(seed, self.layout, self.p_sh)
        self.opt_state = self.opt_init(self.params)
        dcfg = DataConfig(seq_len=job["seq_len"],
                          global_batch=job["global_batch"],
                          vocab_size=self.cfg.vocab, seed=seed % 2 ** 63)
        self.batches = make_train_iterator(dcfg, sharding=self.b_sh,
                                           frontend="tokens")

    def step(self, batch) -> jax.Array:
        self.params, self.opt_state, m = self.jit_step(
            self.params, self.opt_state, batch)
        return m["loss"]

    def checked_steps(self) -> Dict[str, Any]:
        """The first steps, through the window's own call and feed, and
        what the check reads from them."""
        losses, host_batches = [], []
        grad_norms = None
        for k in range(self.job["checked_steps"]):
            batch = next(self.batches)
            host_batches.append({n: np.asarray(v) for n, v in batch.items()})
            if k == 0:
                with self.routing.capture_routes() as log:
                    loss = self.step(batch)
                self.routes = sorted({(r.op, r.n1, r.n2, r.path, r.tiles)
                                      for r in log})
            else:
                loss = self.step(batch)
            losses.append(float(loss))
            if k == 0:
                norms = _leaf_norms(first_gradient(self.opt_state))
                grad_norms = {p: float(v) for p, v in
                              weights.flatten(jax.device_get(norms)).items()}
        flat = weights.flatten(self.params)
        update_norms = {leaf[0]: float(weights.change_norm(
            self.seed, leaf, flat[leaf[0]])) for leaf in self.layout}
        rows = np.concatenate([b["tokens"] for b in host_batches])
        if len({r.tobytes() for r in rows}) != len(rows):
            raise RuntimeError("checked steps repeat a row")
        return {"losses": losses, "grad_norms": grad_norms,
                "update_norms": update_norms, "batches": host_batches}

    def free(self) -> None:
        """Stop the feed and free the state on the device."""
        self.batches.close()
        for x in jax.tree.leaves((self.params, self.opt_state)):
            x.delete()
        self.params = self.opt_state = self.batches = None
        gc.collect()


def ns_calls(cell, layout) -> List[work.Call]:
    job = cell.traffic
    return work.ns_calls([s for _, s, _, _ in layout], job["ns_steps"],
                         job["ns_min_side"])


def step_flop(cell, layout) -> Dict[str, float]:
    """The flop one step requires: the model's, as the configuration's
    reference counts it from the published shapes, and the NS chains
    of ``layout``'s leaves (``bench/work.py``)."""
    job = cell.traffic
    model = cell.reference().model_flop(cell.config, job["global_batch"],
                                        job["seq_len"])
    return work.step_flop(model, [s for _, s, _, _ in layout],
                          job["ns_steps"], job["ns_min_side"])


def run(cell, args, t0: float, profile_dir: pathlib.Path) -> Dict[str, Any]:
    """One run of the cell; ``t0`` is the process start on the host
    clock.  Returns what ``bench/run.py`` prints."""
    from jax.profiler import TraceAnnotation
    job = cell.traffic
    counter = CompileCounter()
    step = Step(cell)
    step.start(args.seed)
    with jax.set_mesh(step.mesh):
        prog = step.checked_steps()
        traced = bool(args.trace)
        n_traced = job["traced_steps"] if traced else 0
        losses, done = [], 0
        tr0 = tr1 = None
        counter.active = True
        t_start = time.perf_counter()
        while True:
            if traced and done == 1:
                jax.profiler.start_trace(str(profile_dir))
                tr0 = time.perf_counter()
                span = TraceAnnotation("bench.window")
                span.__enter__()
            with TraceAnnotation("bench.next_batch"):
                batch = next(step.batches)
            with TraceAnnotation("bench.dispatch"):
                loss = step.step(batch)
            with TraceAnnotation("bench.loss_read"):
                losses.append(float(loss))
            done += 1
            if traced and done == 1 + n_traced:
                span.__exit__(None, None, None)
                tr1 = time.perf_counter()
                jax.profiler.stop_trace()
            elapsed = time.perf_counter() - t_start
            if elapsed >= args.seconds and (not traced
                                            or done >= 1 + n_traced):
                break
        counter.active = False

    devices = jax.devices()[:cell.chips]
    stats = [d.memory_stats() or {} for d in devices]
    memory = [{"device": d.id,
               "peak_bytes_in_use": int(s.get("peak_bytes_in_use", 0)),
               "bytes_limit": int(s.get("bytes_limit", 0))}
              for d, s in zip(devices, stats)]
    compiles = step.jit_step._cache_size()
    routes, layout = step.routes, step.layout
    step.free()

    ref = cell.reference().follow(cell.config, job, args.seed,
                                  prog["batches"],
                                  exchange_shards=step.mesh.shape["model"])
    checks = _compare(prog, ref, cell.limits)
    failed = sum(1 for x in losses + prog["losses"] if not math.isfinite(x))

    calls = ns_calls(cell, layout)
    pallas = {(op, n1, n2) for op, n1, n2, path, _ in routes
              if path == "pallas"}
    flop = step_flop(cell, layout)
    return {
        "attempted": done, "failed": failed,
        "correct": failed == 0 and all(v <= lim for _, v, lim in checks),
        "checks": checks,
        "e2e": {"step_s": elapsed / done,
                "setup_s": t_start - t0},
        "memory": memory,
        "log": {"routes": [list(r) for r in routes],
                "compile_events_in_window": counter.events,
                "step_executables": compiles,
                "mesh": dict(step.mesh.shape),
                "window_steps": done, "window_s": elapsed,
                "checked_losses": prog["losses"],
                "reference_losses": ref["losses"],
                "window_losses": losses,
                "step_flop": flop},
        "trace": None if not traced else {
            "dir": str(profile_dir), "span": "bench.window",
            "steps": n_traced, "window_s": tr1 - tr0},
        "work": {"step_flop": flop["total"],
                 "pallas_calls": [c for c in calls
                                  if (c.op, c.n1, c.n2) in pallas]},
    }

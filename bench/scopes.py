"""Device time of a traced window by the program's named scopes.

The program names its layers with ``jax.named_scope``; XLA keeps each
instruction's scope path in the ``op_name`` of its HLO metadata, and the
trace names each device op by its HLO instruction (``bench/xplane.py``).
The join looks every op of the window up in the compiled step's HLO and
puts its self time in exactly one bucket:

  forward     under ``train.loss``, not transposed;
  backward    under ``train.loss`` and inside ``transpose(...)``;
  recompute   under ``train.loss`` in a ``rematted_computation``;
  clip        under ``train.clip``;
  optimizer   under ``optim.*``, split by matrix leaf
              (``optim.muon.ns.<leaf>``) and by NS product
              (``ns.gram``, ``ns.square``, ``ns.apply``);
  other       under none of these;
  unattributed  no metadata, or a name the HLO does not have.

Each op's self time is also kept under its :func:`label`, the short
scope path of its ``op_name``, so every attributed op falls in exactly
one label and the labels add up to the attributed time; a per-layer
metric reads the labels that match a pattern with :func:`scope_s`.
Under ``blas.<op>.pallas`` the ``tpu_custom_call`` time of each op is
kept apart from the glue around it (pad, tile <-> fill).  An
instruction XLA made without metadata takes the scope of its fused
computation, its operands, its users or its caller (:func:`hlo_ops`).
When an op's opcode differs from the HLO's, more than
``MAX_UNATTRIBUTED`` of the time is unattributed, or no op carries a
scope (a program without them), the join gives nothing, and says why.

The scope names a program may use (``jax.named_scope``), each one
name in the ``op_name`` path.  Where an inner name extends an outer one
by ``.<name>`` (``model.moe.route`` inside ``model.moe``), the label
keeps the inner one alone:

  train.loss, train.clip          the step's loss and gradient, its clip;
  model.<name>[.<name>...]        the model's layers, nested as deep as
                                  the program likes;
  optim.<name>                    an optimizer (``optim.muon``,
                                  ``optim.adamw``) or its wire
                                  (``optim.muon_1d``);
  optim.muon.ns.<leaf>            one matrix leaf's NS chain;
  ns.<name>                       an NS product;
  blas.<op>.<path>                a symmetric-BLAS route.

Any other name is not a scope here: its time falls under the scopes
around it.

Host spans (``bench.*`` and the program's ``repro.*``) are on the host's
clock.  ``clock_offset`` bounds the offset from each step's device
``XLA Modules`` event: the host ``CompleteCallbacks`` event with the
same ``_c`` correlation ends it from above, the host
``PJRT_LoadedExecutable_Execute`` that comes before that from below.
Idle gaps are labelled after the spans are moved onto the device's
clock, with the innermost span that overlaps most of the gap, or
``unresolved`` when the gap is shorter than the offset's uncertainty.

The per-layer readers call :func:`read` with their context; the
compiled step's text comes from :func:`compiled_step_text`, which
builds the cell's step again, as ``bench/drivers/train_step.py``
did, and compiles it (a hit in the compilation cache the run filled).
"""
from __future__ import annotations

import json
import pathlib
import re
import sys
import time
import traceback
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from bench import xplane

MAX_UNATTRIBUTED = 0.01
BUCKETS = ("forward", "backward", "recompute", "clip", "optimizer",
           "other", "unattributed")
MODEL = ("forward", "backward", "recompute")
PRODUCTS = ("ns.gram", "ns.square", "ns.apply")
SPAN_PREFIXES = ("bench.", "repro.")
MODULES_LINE = "XLA Modules"
DONE_EVENT = "CompleteCallbacks"
LAUNCH_EVENT = "PJRT_LoadedExecutable_Execute"

_SCOPE = re.compile(r"(?<![\w.])(train\.loss|train\.clip|model(?:\.\w+)+|"
                    r"optim\.muon\.ns\.[^/()]+|optim\.\w+|ns\.\w+|"
                    r"blas\.\w+\.[\w-]+)(?=[/)]|$)")
_LEAF = re.compile(r"optim\.muon\.ns\.([^/()]+)")
_BLAS = re.compile(r"blas\.(\w+)\.([\w-]+)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')
_FUSED = re.compile(r"\bcalls=%?([\w.\-]+)")
_CONTROL = re.compile(r"\b(?:body|condition|true_computation|"
                      r"false_computation)=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
# instructions whose op_name names an argument, not a scope
_NOT_SOURCES = ("parameter", "constant")


# ------------------------------------------------------------------ HLO
@dataclass
class Hlo:
    """A compiled HLO module as the join reads it: each instruction's
    (opcode, op_name), and ``top``, the instructions that run as ops of
    their own (those of the entry computation and of the loops,
    branches and calls it runs)."""
    module: str
    ops: Dict[str, Tuple[str, str]]
    top: List[str]


def hlo_ops(text: str) -> Hlo:
    """The instructions of a compiled HLO module's text.  One that XLA
    made without metadata takes the ``op_name`` of the first instruction
    with one in its fused computation, else of its operands, else of its
    users, else of the instruction that calls its computation; else it
    keeps ``""``."""
    first = text.split("\n", 1)[0]
    module = first.split()[1].rstrip(",") if first.startswith("HloModule") \
        else ""
    own: Dict[str, Tuple[str, str]] = {}
    uses: Dict[str, List[str]] = {}
    fused: Dict[str, List[str]] = {}
    runs: Dict[str, List[str]] = {}
    comp_of: Dict[str, str] = {}
    members: Dict[str, List[str]] = {}
    caller: Dict[str, str] = {}
    comp = entry = ""
    for line in text.splitlines():
        if not line or line.startswith("HloModule"):
            continue
        if not line[0].isspace():
            m = _HEADER.match(line)
            if m and line.rstrip().endswith("{"):
                comp = m.group(1)
                entry = comp if line.startswith("ENTRY") else entry
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        _, opcode = xplane.parse_op(line.strip().removeprefix("ROOT "))
        om = _OP_NAME.search(line)
        own[name] = (opcode, om.group(1) if om else "")
        comp_of[name] = comp
        members.setdefault(comp, []).append(name)
        fused[name] = _FUSED.findall(line)
        runs[name] = _CONTROL.findall(line)
        for lst in _BRANCHES.findall(line):
            runs[name] += [c.strip().lstrip("%") for c in lst.split(",")]
        if opcode == "call":
            runs[name] += _TO_APPLY.findall(line)
        for c in fused[name] + runs[name] + _TO_APPLY.findall(line):
            caller.setdefault(c, name)
        body = line[m.end():].split(", metadata=")[0]
        uses[name] = [u for u in _OPERAND.findall(body) if u != name]

    users: Dict[str, List[str]] = {}
    for n, us in uses.items():
        for u in us:
            users.setdefault(u, []).append(n)
    found: Dict[str, str] = {}

    def scope(name: str, depth: int = 0) -> str:
        if name in found:
            return found[name]
        opcode, op_name = own[name]
        if opcode in _NOT_SOURCES:
            op_name = ""
        found[name] = op_name           # stops a cycle
        if not op_name and depth < 32:
            inner = [n for c in fused[name] for n in members.get(c, [])]
            op_name = next((own[n][1] for n in inner
                            if own[n][1] and own[n][0] not in _NOT_SOURCES),
                           "")
            for u in uses[name] + users.get(name, []):
                if op_name:
                    break
                if u in own:
                    op_name = scope(u, depth + 1)
            up = caller.get(comp_of[name])
            if not op_name and up in own:
                op_name = scope(up, depth + 1)
        found[name] = op_name
        return op_name

    top, todo, seen = [], [entry], set()
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for n in members.get(c, []):
            top.append(n)
            todo += runs[n]
    return Hlo(module, {n: (own[n][0], scope(n)) for n in own}, top)


def scopes_of(op_name: str) -> List[str]:
    """The program's scope names in an ``op_name``, outermost first."""
    return _SCOPE.findall(op_name)


def bucket(op_name: str) -> str:
    """Which of :data:`BUCKETS` an instruction's ``op_name`` falls in."""
    if not op_name:
        return "unattributed"
    names = scopes_of(op_name)
    if "train.loss" in names:
        if "rematted_computation" in op_name:
            return "recompute"
        return "backward" if "transpose(" in op_name else "forward"
    if "train.clip" in names:
        return "clip"
    if any(n.startswith("optim.") for n in names):
        return "optimizer"
    return "other"


def label(op_name: str) -> str:
    """A short scope path for an op: the program's scopes, a scope that
    a later one extends left out, the phase after ``train.loss``."""
    names = scopes_of(op_name)
    keep = [n for i, n in enumerate(names)
            if not any(m.startswith(n + ".") for m in names[i + 1:])]
    b = bucket(op_name)
    return "/".join(f"{n}[{b}]" if n == "train.loss" else n for n in keep)


# ---------------------------------------------------------------- trace
@dataclass
class Timeline(xplane.Trace):
    """A trace as the join reads it: :class:`xplane.Trace` (device ops on
    the device's clock, ``bench.*`` spans) plus, per device, the
    ``XLA Modules`` events (name, start, end, correlation), the host's
    ``CompleteCallbacks`` (start, correlation) and launch starts, and
    every ``bench.*`` / ``repro.*`` span."""
    modules: Dict[str, List[Tuple[str, float, float, str]]] = \
        field(default_factory=dict)
    done: List[Tuple[float, str]] = field(default_factory=list)
    launches: List[float] = field(default_factory=list)
    host_spans: List[Tuple[str, float, float]] = field(default_factory=list)


def _stat(event, key: str) -> str:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return ""


def load(path: pathlib.Path) -> Timeline:
    from jax.profiler import ProfileData
    base = xplane.load(path)
    tl = Timeline(devices=base.devices, spans=base.spans)
    for plane in ProfileData.from_file(str(path)).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            tl.modules[plane.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 _stat(e, "_c"))
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == DONE_EVENT:
                        tl.done.append((e.start_ns, _stat(e, "_c")))
                    elif e.name == LAUNCH_EVENT:
                        tl.launches.append(e.start_ns)
                    elif e.name.startswith(SPAN_PREFIXES):
                        tl.host_spans.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    tl.launches.sort()
    return tl


def clock_offset(tl: Timeline) -> Optional[Tuple[float, float]]:
    """(lower, upper) bounds in ns on host time - device time, from
    every device module whose completion the host recorded; None when
    no module pairs up or the bounds cross."""
    done = {c: t for t, c in tl.done if c}
    lo, hi = -float("inf"), float("inf")
    for mods in tl.modules.values():
        for _, start, end, c in mods:
            if c not in done:
                continue
            hi = min(hi, done[c] - end)
            before = [t for t in tl.launches if t <= done[c]]
            if before:
                lo = max(lo, before[-1] - start)
    if hi == float("inf") or lo == -float("inf") or lo > hi:
        return None
    return lo, hi


def label_gaps(tl: Timeline, window: Tuple[float, float],
               bounds: Tuple[float, float], top: int = 10) -> List:
    """The window's idle gaps on each chip, longest first, each with
    the innermost host span that overlaps most of it once the spans are
    on the device's clock; ``unresolved`` below the offset's
    uncertainty."""
    off = (bounds[0] + bounds[1]) / 2
    lo, hi = window[0] - off, window[1] - off
    spans = [(n, a - off, b - off) for n, a, b in tl.host_spans
             if n != "bench.window"]
    out = []
    for ops in tl.devices.values():
        busy = xplane.union(xplane.clip(
            [(o.start, o.end) for o in ops if not o.spans_async], lo, hi))
        for a, b in xplane.gaps(busy, lo, hi):
            name = "unresolved"
            if b - a >= bounds[1] - bounds[0]:
                over = [(min(b, e) - max(a, s), -(e - s), n)
                        for n, s, e in spans if min(b, e) > max(a, s)]
                name = max(over)[2] if over else "no span"
            out.append([name, (b - a) * 1e-9])
    return sorted(out, key=lambda g: -g[1])[:top]


# ----------------------------------------------------------------- join
@dataclass
class Join:
    """Device seconds, summed over the window and averaged over chips."""
    buckets: Dict[str, float]
    leaves: Dict[str, float]
    products: Dict[str, float]
    kernel: Dict[str, float]      # blas op -> tpu_custom_call time
    glue: Dict[str, float]        # blas op -> other time under .pallas
    labels: Dict[str, float]      # scope label -> self time
    ops: Dict[str, str]           # short op name -> scope label
    mismatches: List[Tuple[str, str, str]]
    busy_s: float

    def unattributed_share(self) -> float:
        total = sum(self.buckets.values())
        return self.buckets["unattributed"] / total if total else 1.0

    def refusal(self) -> Optional[str]:
        """Why the join gives nothing, or None."""
        if self.mismatches:
            name, want, got = self.mismatches[0]
            return (f"{len(self.mismatches)} ops whose opcode differs from "
                    f"the HLO's, e.g. {name}: {got} in the trace, {want} "
                    "in the HLO")
        if not any(self.buckets[b] for b in MODEL + ("clip", "optimizer")):
            return "no op carries the train step's scopes"
        share = self.unattributed_share()
        if share > MAX_UNATTRIBUTED:
            return (f"unattributed {share:.4f} of the step's device time "
                    f"(more than {MAX_UNATTRIBUTED})")
        return None


def attribute(tr: xplane.Trace, hlo: Dict[str, Tuple[str, str]],
              steps: Dict[str, List[Tuple[float, float]]],
              window: Tuple[float, float]) -> Join:
    """Each op that runs inside ``window`` and inside one of its chip's
    ``steps`` intervals (all on the device's clock), by the HLO's
    ``op_name``."""
    lo, hi = window
    acc = {k: {} for k in ("buckets", "leaves", "products", "kernel",
                           "glue", "labels")}
    acc["buckets"] = dict.fromkeys(BUCKETS, 0.0)
    names: Dict[str, str] = {}
    mismatches = []
    busy = 0.0

    def add(kind: str, key: str, t: float) -> None:
        acc[kind][key] = acc[kind].get(key, 0.0) + t

    for dev, ops in tr.devices.items():
        ivs = sorted(steps.get(dev, []))
        inside = [o for o in ops if not o.spans_async
                  and min(o.end, hi) > max(o.start, lo)
                  and any(a <= (o.start + o.end) / 2 <= b for a, b in ivs)]
        busy += xplane.total(xplane.union(xplane.clip(
            [(o.start, o.end) for o in inside], lo, hi)))
        for o in inside:
            share = (min(o.end, hi) - max(o.start, lo)) / (o.end - o.start)
            t = o.self_ns * share
            parts = o.name.split(" ")
            entry = hlo.get(parts[0])
            if entry is None:
                add("buckets", "unattributed", t)
                continue
            opcode, op_name = entry
            if len(parts) > 1 and parts[1] != opcode:
                mismatches.append((parts[0], opcode, parts[1]))
            b = bucket(op_name)
            add("buckets", b, t)
            names[o.name] = label(op_name)
            add("labels", names[o.name], t)
            if b == "optimizer":
                leaf = _LEAF.search(op_name)
                add("leaves", leaf.group(1) if leaf else "rest", t)
                prod = [p for p in scopes_of(op_name) if p in PRODUCTS]
                add("products", prod[-1] if prod else "rest", t)
            blas = _BLAS.findall(op_name)
            if blas and blas[-1][1] == "pallas":
                add("kernel" if o.kind == "pallas" else "glue",
                    blas[-1][0], t)
    n = max(len(tr.devices), 1)
    scale = 1e-9 / n
    return Join(**{k: {kk: vv * scale for kk, vv in v.items()}
                   for k, v in acc.items()},
                ops=names, mismatches=mismatches, busy_s=busy * scale)


# ------------------------------------------------------ the run's side
def compiled_step_text(cell) -> str:
    """The compiled HLO text of the cell's step, built again from the
    cell by its traffic kind's ``Step`` and compiled for the same
    arguments."""
    import jax
    step = cell.driver().Step(cell)
    job = cell.traffic
    batch = {k: jax.ShapeDtypeStruct((job["global_batch"], job["seq_len"]),
                                     "int32") for k in ("tokens", "labels")}
    with jax.set_mesh(step.mesh):
        return step.jit_step.lower(step.shape, step.state_shape,
                                   batch).compile().as_text()


def _cell(ctx):
    """The run's cell: ``bench/run.py`` traces into
    ``artifacts/bench/<cell>/trace``."""
    from bench import spec
    name = pathlib.Path(ctx["run"]["trace"]["dir"]).parent.name
    return spec.load_cell(pathlib.Path(__file__).resolve().parents[1], name)


def _compute(ctx) -> Optional[Dict]:
    reduced, run = ctx.get("trace"), ctx["run"]
    if not reduced or not run.get("trace"):
        return None
    path = xplane.find_xplane(pathlib.Path(run["trace"]["dir"]))
    if path is None:
        return None
    tl = load(path)
    windows = [(a, b) for n, a, b in tl.spans if n == "bench.window"]
    if not windows:
        return None
    bounds = clock_offset(tl)
    clock = {"offset_ms": None if bounds is None else
             [bounds[0] * 1e-6, bounds[1] * 1e-6]}
    if bounds is not None:
        # the breakdown's gap labels, with the host spans on the
        # device's clock
        reduced["idle_gaps"] = label_gaps(tl, windows[0], bounds)
    print(f"[bench] clock {json.dumps(clock)}", flush=True)

    t0 = time.perf_counter()
    hlo = hlo_ops(compiled_step_text(_cell(ctx)))
    compile_s = time.perf_counter() - t0
    off = 0.0 if bounds is None else (bounds[0] + bounds[1]) / 2
    window = (windows[0][0] - off, windows[0][1] - off)
    steps = {dev: [(a, b) for name, a, b, _ in mods
                   if name.split("(")[0] == hlo.module]
             for dev, mods in tl.modules.items()}
    join = attribute(tl, hlo.ops, steps, window)
    why = join.refusal()
    n = run["trace"]["steps"]
    per_step = {k: v / n for k, v in join.buckets.items()}
    line = {"module": hlo.module, "compile_s": compile_s,
            "unattributed_share": join.unattributed_share(),
            "per_step_s": per_step,
            "busy_per_step_s": join.busy_s / n,
            "leaves_per_step_s": {k: v / n for k, v in sorted(
                join.leaves.items(), key=lambda kv: -kv[1])},
            "products_per_step_s": {k: v / n
                                    for k, v in join.products.items()},
            "pallas_kernel_per_step_s": {k: v / n
                                         for k, v in join.kernel.items()},
            "pallas_glue_per_step_s": {k: v / n
                                       for k, v in join.glue.items()},
            "labels_per_step_s": {k: v / n for k, v in sorted(
                join.labels.items(), key=lambda kv: -kv[1])},
            "host_spans_s": _span_totals(tl, windows[0])}
    if why:
        line = {"none": why, **line}
    print(f"[bench] scopes {json.dumps(line)}", flush=True)
    if why:
        return None
    # the breakdown's top ops, named by their scope
    reduced["device_ops"] = [
        [f"{join.ops[k]} {k}" if join.ops.get(k) else k, v]
        for k, v in reduced["device_ops"]]
    return {"join": join, "steps": n}


def _span_totals(tl: Timeline, window) -> Dict[str, List[float]]:
    """Count and host seconds of each span inside the window."""
    out: Dict[str, List[float]] = {}
    for name, a, b in tl.host_spans:
        if name != "bench.window" and a >= window[0] and b <= window[1]:
            c = out.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (b - a) * 1e-9
    return out


def read(ctx) -> Optional[Dict]:
    """The join of this run's trace, once per run (kept in ``ctx``);
    None when there is no trace or the join refuses."""
    if "scopes" not in ctx:
        try:
            ctx["scopes"] = _compute(ctx)
        except Exception as e:     # a reader reports nothing, never fails
            traceback.print_exc(file=sys.stderr)
            print(f"[bench] scopes none: {type(e).__name__}: {e}",
                  flush=True)
            ctx["scopes"] = None
    return ctx["scopes"]


def scope_s(ctx, pattern: str) -> Optional[float]:
    """Device self time per traced step, mean over chips, of the ops
    whose :func:`label` matches the regex ``pattern`` (``re.search``);
    None when the join refuses or no label matches."""
    got = read(ctx)
    if not got:
        return None
    rx = re.compile(pattern)
    hit = [t for name, t in got["join"].labels.items() if rx.search(name)]
    return sum(hit) / got["steps"] if hit else None


def pallas_roofline(ctx, op: str) -> Optional[float]:
    """Roofline time of the step's ``op`` calls routed to Pallas, over
    the ``tpu_custom_call`` time under ``blas.<op>.pallas``, in %."""
    from bench import work
    got = read(ctx)
    calls = [c for c in ctx["run"]["work"]["pallas_calls"] if c.op == op]
    if not got or not calls or got["join"].kernel.get(op, 0.0) <= 0:
        return None
    need = work.calls_roofline_s(calls, ctx["peak"]) * got["steps"]
    return 100.0 * need / got["join"].kernel[op]

"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Device planes are ``/device:TPU:<n>``; their op line (``XLA Ops``)
holds one event per device operation, named by its HLO text
(``%name = <shape> <opcode>(...)``).  A control-flow op (``while``)
spans the ops of its body, which appear nested inside it on the same
line.  A Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"``; a collective is an op whose
opcode or name is one of XLA's collectives.

The reduction, per chip, over the window that the host span
``bench.window`` marks:

  busy        union of all op intervals;
  pallas      union of the Pallas kernels' intervals;
  collective  union of the collectives' intervals, an async one
              (``Async XLA Ops``) from its start to its done;
  exposed     the part of ``collective`` in which no innermost
              non-collective op runs;
  gaps        the idle intervals, each labelled with the ``bench.*``
              host span that overlaps most of it.

It also totals each op's self time (its duration less that of the ops
nested in it), averaged over the chips.  The host and device clocks of
a trace agree to about a millisecond, so a gap shorter than that may
carry the wrong label.
"""
from __future__ import annotations

import pathlib
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"     # an async op from its start to its done
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|^send|^recv|"
                        r"collective", re.I)
PALLAS = 'custom_call_target="tpu_custom_call"'
HLO = re.compile(r"^%?(?P<name>[^ ]+) = ")
SPAN_PREFIX = "bench."


@dataclass
class Op:
    name: str             # short: "<hlo name> <opcode>"
    start: float          # ns on the trace's clock
    end: float
    kind: str             # "pallas" | "collective" | "compute"
    leaf: bool = True     # no op nested inside it
    self_ns: float = 0.0
    spans_async: bool = False


@dataclass
class Trace:
    devices: Dict[str, List[Op]] = field(default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def find_xplane(directory: pathlib.Path) -> Optional[pathlib.Path]:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def parse_op(text: str) -> Tuple[str, str]:
    """(HLO name, opcode) of an op event's HLO text."""
    m = HLO.match(text)
    if not m:
        return text.split("(")[0].strip(), ""
    rest = text[m.end():]
    if rest.startswith("("):            # tuple-shaped result
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return m.group("name"), rest.strip().split("(")[0]


def classify(text: str) -> Tuple[str, str]:
    """(short name, kind) of an op event."""
    name, opcode = parse_op(text)
    if PALLAS in text:
        return f"{name} {opcode} tpu_custom_call", "pallas"
    if COLLECTIVE.search(opcode) or COLLECTIVE.search(name):
        return f"{name} {opcode}", "collective"
    return f"{name} {opcode}", "compute"


def nest(ops: List[Op]) -> List[Op]:
    """Mark ops that hold others and set every op's self time."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack: List[Op] = []
    for o in ops:
        o.self_ns = o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].leaf = False
            stack[-1].self_ns -= o.end - o.start
        stack.append(o)
    return ops


def load(path: pathlib.Path) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, pending = [], []
            for line in plane.lines:
                for e in line.events:
                    if line.name not in (OP_LINE, ASYNC_LINE):
                        break
                    name, kind = classify(e.name)
                    op = Op(name, e.start_ns, e.start_ns + e.duration_ns,
                            kind, spans_async=line.name == ASYNC_LINE)
                    if not op.spans_async:
                        ops.append(op)
                    elif kind == "collective":
                        pending.append(op)
            tr.devices[plane.name] = nest(ops) + pending
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        tr.spans.append((e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
    return tr


# ------------------------------------------------------------ intervals
def union(ivs: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(ivs):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(ivs: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in ivs
            if min(b, hi) > max(a, lo)]


def total(ivs: Iterable[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def subtract(ivs: Sequence[Interval], cut: Sequence[Interval]
             ) -> List[Interval]:
    """Parts of the (disjoint, sorted) ``ivs`` not in the (disjoint,
    sorted) ``cut``."""
    out, j = [], 0
    for a, b in ivs:
        while j < len(cut) and cut[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cut) and cut[k][0] < b:
            if cut[k][0] > cur:
                out.append((cur, cut[k][0]))
            cur = max(cur, cut[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


# ------------------------------------------------------------- reduction
def reduce(tr: Trace, span: str = "bench.window", top: int = 10) -> Dict:
    windows = [(a, b) for n, a, b in tr.spans if n == span]
    if not windows or not tr.devices:
        return {}
    lo, hi = windows[0]
    others = [(n, a, b) for n, a, b in tr.spans if n != span]
    per_dev, op_time, all_gaps = [], {}, []
    for dev, ops in sorted(tr.devices.items()):
        inside = [o for o in ops if min(o.end, hi) > max(o.start, lo)]
        spans_async = [o for o in inside if o.spans_async]
        inside = [o for o in inside if not o.spans_async]
        busy = union(clip([(o.start, o.end) for o in inside], lo, hi))
        # a container (while) counts as busy, not as compute
        kinds = {k: union(clip([(o.start, o.end) for o in inside
                                if o.kind == k and (o.leaf or k != "compute")],
                               lo, hi))
                 for k in ("pallas", "collective", "compute")}
        kinds["collective"] = union(kinds["collective"] + clip(
            [(o.start, o.end) for o in spans_async], lo, hi))
        not_coll = union(kinds["pallas"] + kinds["compute"])
        exposed = subtract(kinds["collective"], not_coll)
        per_dev.append({"device": dev, "busy_s": total(busy) * 1e-9,
                        "pallas_s": total(kinds["pallas"]) * 1e-9,
                        "collective_s": total(kinds["collective"]) * 1e-9,
                        "exposed_collective_s": total(exposed) * 1e-9})
        for o in inside:
            share = (min(o.end, hi) - max(o.start, lo)) / (o.end - o.start) \
                if o.end > o.start else 0.0
            op_time[o.name] = op_time.get(o.name, 0.0) + \
                o.self_ns * share * 1e-9
        for a, b in gaps(busy, lo, hi):
            all_gaps.append((_label(a, b, others), (b - a) * 1e-9))
    n = len(per_dev)
    ops_sorted = sorted(((k, v / n) for k, v in op_time.items()),
                        key=lambda kv: -kv[1])
    return {"window_s": (hi - lo) * 1e-9, "devices": per_dev,
            "device_ops": [list(kv) for kv in ops_sorted[:top]],
            "idle_gaps": [list(g) for g in
                          sorted(all_gaps, key=lambda g: -g[1])[:top]]}


def _label(a: float, b: float, spans) -> str:
    best, name = 0.0, "no bench span"
    for n, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, name = ov, n
    return name


def reduce_file(path: pathlib.Path, span: str = "bench.window") -> Dict:
    return reduce(load(path), span)

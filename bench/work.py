"""The work a train step requires, counted from shapes alone.

These counts do not change when the implementation changes: they are
what the step has to do, not what one implementation compiled to.

* Model: 6 flop per matmul parameter per token (forward and backward,
  recomputation not counted) plus causal attention.
* Newton–Schulz (NS): each iteration on X (m x n, m <= n) is one SYRK
  S = X Xᵀ, one SYMM S·S and one SYMM Y·X, counted with the paper's
  counts: SYRK m²n, SYR2K 2m²n, SYMM 2m²n.
* Bytes of a symmetric call: every operand read once (a symmetric
  operand as its triangle), the output written once at its fill.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Iterable, List, Sequence, Tuple


@dataclass(frozen=True)
class Call:
    """One symmetric-BLAS call: ``op`` on (n1, n2), ``batch`` stacked
    slices, ``per_step`` times per train step."""
    op: str
    n1: int
    n2: int
    batch: int = 1
    per_step: int = 1


def tri(n: int) -> int:
    return n * (n + 1) // 2


def call_flop(op: str, n1: int, n2: int) -> float:
    if op == "syrk":
        return float(n1) * n1 * n2
    if op in ("syr2k", "symm"):
        return 2.0 * n1 * n1 * n2
    raise ValueError(op)


def call_bytes(op: str, n1: int, n2: int, itemsize: int = 4,
               fill: str = "full") -> float:
    out_sym = n1 * n1 if fill == "full" else tri(n1)
    if op == "syrk":
        words = n1 * n2 + out_sym
    elif op == "syr2k":
        words = 2 * n1 * n2 + out_sym
    elif op == "symm":
        words = tri(n1) + 2 * n1 * n2
    else:
        raise ValueError(op)
    return float(words) * itemsize


def roofline_s(flop: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flop / peak["flop_per_s"], nbytes / peak["hbm_bytes_per_s"])


# ---------------------------------------------------------------- model
def matmul_params(cfg: Dict) -> int:
    """Parameters that take part in a matmul per token (the embedding
    lookup is a gather; the head is a matmul, tied or not)."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return per_layer * cfg["num_hidden_layers"] + d * v


def model_flop(cfg: Dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: 6·N·tokens plus causal
    attention (QKᵀ and PV, half the square, three passes)."""
    attn = 6.0 * batch * seq * seq * cfg["num_attention_heads"] \
        * cfg["head_dim"] * cfg["num_hidden_layers"]
    return 6.0 * matmul_params(cfg) * batch * seq + attn


# ----------------------------------------------------------- optimizer
def ns_matrices(shapes: Iterable[Sequence[int]], min_side: int = 8
                ) -> List[Tuple[int, int, int]]:
    """(m, n, count) of every matrix Muon orthogonalizes: a leaf of rank
    ≥ 2 whose trailing sides are both ≥ ``min_side``, per trailing 2-D
    slice, on its short side (m ≤ n)."""
    out = []
    for s in shapes:
        if len(s) < 2 or min(s[-2:]) < min_side:
            continue
        m, n = sorted(int(x) for x in s[-2:])
        out.append((m, n, int(math.prod(s[:-2]))))
    return out


def ns_calls(shapes: Iterable[Sequence[int]], ns_steps: int,
             min_side: int = 8) -> List[Call]:
    """The symmetric calls of one step's NS chains."""
    calls = []
    for m, n, count in ns_matrices(shapes, min_side):
        calls += [Call("syrk", m, n, count, ns_steps),
                  Call("symm", m, m, count, ns_steps),
                  Call("symm", m, n, count, ns_steps)]
    return calls


def calls_flop(calls: Iterable[Call]) -> float:
    return sum(call_flop(c.op, c.n1, c.n2) * c.batch * c.per_step
               for c in calls)


def calls_roofline_s(calls: Iterable[Call], peak: Dict[str, float],
                     itemsize: int = 4) -> float:
    return sum(roofline_s(call_flop(c.op, c.n1, c.n2),
                          call_bytes(c.op, c.n1, c.n2, itemsize), peak)
               * c.batch * c.per_step for c in calls)


def step_flop(cfg: Dict, shapes: Iterable[Sequence[int]], batch: int,
              seq: int, ns_steps: int, min_side: int = 8) -> Dict[str, float]:
    ns = calls_flop(ns_calls(list(shapes), ns_steps, min_side))
    model = model_flop(cfg, batch, seq)
    return {"model": model, "ns": ns, "total": model + ns}

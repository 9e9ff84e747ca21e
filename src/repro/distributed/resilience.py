"""ABFT checksums + retry policies for the packed mesh wire.

Algorithm-based fault tolerance for the paper's symmetric kernels: the
SYRK output C = A·Aᵀ satisfies the row-sum identity

    sym(C) · 1  =  A · (Aᵀ · 1)

so an O(n) checksum vector guards the O(n²/2) packed triangle payload
of every mesh route (Huang–Abraham encoding specialized to the packed
wire).  The verified identity is the *prefix* form of the row sums —
the packed row-major row i holds exactly C[i, :i+1], so

    Σ_{j≤i} C[i, j]  =  a_i · (Σ_{j≤i} a_j)

which maps every packed word into exactly one checksum row (clean
localization) and makes the observed side a single
``np.add.reduceat`` pass over the payload on the host — the payload
already lives in host memory on the packed wire, so the check rides
for O(L) reads with no device round-trip and, crucially, no
re-replicated SPMD program over the mesh.  The expected side needs
the row prefixes of A, computed blocked (:func:`_prefix_dots`):
block-level exclusive prefixes plus batched r×r triangle matmuls,
all BLAS-shaped.  SYR2K uses Σ_{j≤i} C[i,j] = a_i·cumB[i] +
b_i·cumA[i]; SYMM (C = sym(S)·B, dense output) keeps the full
row-sum form C·1 = sym(S)·(B·1), a packed matvec on the cached
triangle view.

Verification is accumulation-aware: the tolerance scales with the
per-row magnitude bound |A|·(|Aᵀ|·1) (what f32 rounding of the same
accumulation could legitimately produce) rather than a global eps, so
a bitflip in one payload word is distinguishable from honest rounding
even when row norms differ by orders of magnitude — the calibrated
margin (:func:`_default_rtol`) sits ~100× above the worst honest
residual of any mesh route and ~30× below the smallest single-word
corruption (an exponent down-flip of a typical slot).

On mismatch, :func:`checked_syrk` / :func:`checked_syr2k` /
:func:`checked_symm` localize the bad checksum rows to the owning
device's row band, then repair: patch the corrupted device's shard
from a trusted packed reference via
:func:`~repro.distributed.straggler.rebuild_replacement_shard` when
one is available (checkpointed state), else recompute the collective
with exponential backoff — injected transient faults
(distributed/faults.py) don't re-fire, mirroring real single-event
upsets.  :func:`with_retries` is the generic transient-failure policy
shared with checkpoint I/O and the serving refresh executor.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.packing import ShardedTriTiles, tril_size
from . import faults

#: default relative scale for the accumulation-aware tolerance; the
#: per-row bound already carries the magnitude, this carries the
#: accumulation-length growth (n2-term dots summed over n rows)
DEFAULT_ATOL = 1e-5


class AbftError(RuntimeError):
    """Checksum mismatch that survived every repair attempt."""

    def __init__(self, msg: str, report: "AbftReport"):
        super().__init__(msg)
        self.report = report


@dataclass
class AbftReport:
    op: str
    route: str
    n: int
    attempts: int = 0
    detected: bool = False
    bad_rows: List[int] = field(default_factory=list)
    devices: List[int] = field(default_factory=list)
    #: owner of the highest flagged checksum row — the prefix checksum
    #: maps packed slot (i, j) to exactly row i, so every flagged row
    #: lies inside a corrupted device's own band (SYMM's dense row
    #: sums share the property); max picks the deepest band when the
    #: corruption straddles a boundary
    primary: Optional[int] = None
    action: str = "none"           # none | retry | rebuild


# -- generic retry policy ---------------------------------------------------
def with_retries(fn: Callable, *args, retries: int = 4,
                 backoff: float = 0.05, jitter: float = 0.25,
                 timeout: Optional[float] = None,
                 retry_on=(OSError,), on_retry: Optional[Callable] = None,
                 **kwargs) -> Any:
    """Call ``fn(*args, **kwargs)``, retrying transient failures with
    exponential backoff.

    ``retries`` extra attempts after the first; ``backoff`` doubles per
    retry with a deterministic ``jitter`` fraction added (reproducible
    chaos runs must not depend on a wall-clock rng); ``timeout`` caps
    the total budget — the last error re-raises once sleeping again
    would exceed it.  ``on_retry(attempt, exc)`` observes each failure
    (logging / counters).  Non-matching exceptions propagate
    immediately.
    """
    t0 = time.monotonic()
    delay = backoff
    for attempt in range(retries + 1):
        try:
            return fn(*args, **kwargs)
        except retry_on as e:                       # noqa: PERF203
            if attempt >= retries:
                raise
            pause = delay * (1.0 + jitter
                             * ((attempt * 2654435761) % 997) / 997.0)
            if timeout is not None and \
                    time.monotonic() - t0 + pause > timeout:
                raise
            if on_retry is not None:
                on_retry(attempt, e)
            time.sleep(pause)
            delay *= 2.0
    raise RuntimeError("unreachable")               # pragma: no cover


# -- packed checksum algebra ------------------------------------------------
@functools.lru_cache(maxsize=None)
def _tril_ids(n: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(row-id, col-id, diag-slot) tables over the n(n+1)/2 packed
    row-major slots — cached per n, shared by every checksum."""
    rows = np.repeat(np.arange(n, dtype=np.int32),
                     np.arange(1, n + 1, dtype=np.int32))
    idx = np.arange(tril_size(n), dtype=np.int64)
    cols = (idx - rows.astype(np.int64) * (rows.astype(np.int64) + 1)
            // 2).astype(np.int32)
    i = np.arange(n, dtype=np.int64)
    diag = (i * (i + 3) // 2).astype(np.int32)
    return rows, cols, diag


@functools.lru_cache(maxsize=None)
def _row_starts(n: int) -> np.ndarray:
    """``np.add.reduceat`` segment starts of the n packed row-major
    rows (row i starts one past the previous diagonal slot)."""
    _, _, diag = _tril_ids(n)
    return np.concatenate([[0], diag[:-1].astype(np.int64) + 1])


@functools.lru_cache(maxsize=None)
def _tri_tables(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Dense gather tables for the packed triangle: ``slot[i, j]`` is
    the packed index of (i, j) for i ≥ j (0 above the diagonal) and
    ``mask`` the lower-triangle indicator.  Host-side (numpy) — the
    dense view is a *local* O(n²) temp in the same footprint class as
    the payload it checks, nothing extra on the wire."""
    i, j = np.tril_indices(n)
    slot = np.zeros((n, n), np.int32)
    slot[i, j] = np.arange(i.size, dtype=np.int32)
    mask = np.zeros((n, n), np.float32)
    mask[i, j] = 1.0
    return slot, mask


def _as_f32(x) -> np.ndarray:
    return np.asarray(x).astype(np.float32, copy=False)


def _tril_view(p, n: int) -> np.ndarray:
    slot, mask = _tri_tables(n)
    return _as_f32(p)[slot] * mask


def packed_row_sums(p, n: int) -> np.ndarray:
    """Row sums of sym(C) from the packed triangle (host-side): row
    segment sums + column sums − diag (the diagonal slot is counted by
    both sides)."""
    _, cols, diag = _tril_ids(n)
    pf = _as_f32(p)
    rs = np.add.reduceat(pf, _row_starts(n))
    cs = np.bincount(cols, weights=pf, minlength=n).astype(np.float32)
    return rs + cs - pf[diag]


def packed_sym_matvec(p, n: int, v) -> np.ndarray:
    """sym(S) · v from the packed triangle (the SYMM checksum's
    expected side): two triangular matvecs on the dense host view,
    minus the double-counted diagonal."""
    _, _, diag = _tril_ids(n)
    m = _tril_view(p, n)
    pf, vf = _as_f32(p), _as_f32(v)
    return m @ vf + m.T @ vf - pf[diag] * vf


#: within-block size of the blocked prefix — small enough that the
#: batched r×r cross-dot stays ~n·r·k flops, large enough that the
#: block-level cumsum is negligible
_PREFIX_BLOCK = 64


def _prefix_dots(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """``s[i] = x_i · Σ_{j≤i} y_j`` without a length n·k scalar scan
    (numpy's cumsum walks element-at-a-time — ~10× the cost of the
    collective being checked at n=2k).  Blocked instead: an exclusive
    block-level prefix (one tiny cumsum over n/r block column sums)
    plus batched r×r cross-dot matmuls masked to the within-block
    triangle — all BLAS-shaped, ~n·r·k flops."""
    n, k = x.shape
    r = min(_PREFIX_BLOCK, n)
    b = -(-n // r)
    if b * r != n:
        pad = np.zeros((b * r - n, k), np.float32)
        x = np.concatenate([x, pad])
        y = np.concatenate([y, pad])
    x3 = x.reshape(b, r, k)
    y3 = y.reshape(b, r, k)
    blk = y3.sum(axis=1)                            # (b, k) block sums
    pre = np.cumsum(blk, axis=0, dtype=np.float32) - blk   # exclusive
    g = np.matmul(x3, y3.transpose(0, 2, 1))        # (b, r, r)
    t = (g * np.tril(np.ones((r, r), np.float32))).sum(axis=2)
    s = np.matmul(x3, pre[:, :, None])[:, :, 0] + t
    return s.reshape(-1)[:n]


def _default_rtol(n1: int, n2: int, dtype=None) -> float:
    """Calibrated detection margin.  Across every mesh route (1d /
    ring / 2d / 3d / 3d-limited / local, n up to 4k) the worst honest
    f32 rounding keeps |rs − s| below ~1e-8·(m+1), while a single
    corrupted payload word moves its checksum row by at least the
    slot magnitude ≈ 3e-5·(m+1) even in the worst (exponent
    down-flip) direction — 1e-6 splits the two decades with ~100×
    margin against false positives and ~30× against misses.  Scales
    with machine eps for wider-eps payloads (bf16)."""
    del n1, n2                                      # magnitude lives in m
    try:
        eps = float(jnp.finfo(dtype).eps) if dtype is not None \
            else float(np.finfo(np.float32).eps)
    except ValueError:                              # non-float payload
        eps = float(np.finfo(np.float32).eps)
    return max(1e-6, 8.0 * eps)


@functools.lru_cache(maxsize=None)
def _check_syrk(n: int, rtol: float, atol: float):
    starts = _row_starts(n)
    ones = np.ones((n,), np.float32)

    def chk(a, out):
        af = np.ascontiguousarray(np.asarray(a), dtype=np.float32)
        with np.errstate(invalid="ignore"):     # NaN payloads are *caught*
            rs = np.add.reduceat(_as_f32(out), starts)
        s = _prefix_dots(af, af)
        ab = np.abs(af)
        m = ab @ (ab.T @ ones)
        resid = np.abs(rs - s)
        return np.where(np.isnan(resid), True,
                        resid > atol + rtol * (m + 1.0))
    return chk


@functools.lru_cache(maxsize=None)
def _check_syr2k(n: int, rtol: float, atol: float):
    starts = _row_starts(n)
    ones = np.ones((n,), np.float32)

    def chk(a, b, out):
        af = np.ascontiguousarray(np.asarray(a), dtype=np.float32)
        bf = np.ascontiguousarray(np.asarray(b), dtype=np.float32)
        with np.errstate(invalid="ignore"):     # NaN payloads are *caught*
            rs = np.add.reduceat(_as_f32(out), starts)
        s = _prefix_dots(af, bf) + _prefix_dots(bf, af)
        ab, bb = np.abs(af), np.abs(bf)
        m = ab @ (bb.T @ ones) + bb @ (ab.T @ ones)
        resid = np.abs(rs - s)
        return np.where(np.isnan(resid), True,
                        resid > atol + rtol * (m + 1.0))
    return chk


@functools.lru_cache(maxsize=None)
def _check_symm(n: int, rtol: float, atol: float):
    def chk(a_packed, b, out):
        bf = _as_f32(b)
        ones = np.ones((bf.shape[1],), np.float32)
        s = packed_sym_matvec(a_packed, n, bf @ ones)
        m = packed_sym_matvec(np.abs(_as_f32(a_packed)), n,
                              np.abs(bf) @ ones)
        resid = np.abs(_as_f32(out).sum(axis=1) - s)
        return np.where(np.isnan(resid), True,
                        resid > atol + rtol * (m + 1.0))
    return chk


# -- row-band device ownership ----------------------------------------------
def device_rows(n: int, world: int, k: int) -> Tuple[int, int]:
    """Row band [r0, r1) of the packed payload attributed to device
    ``k`` of ``world`` (the corruption/localization model: a device's
    contribution to the assembled triangle is a contiguous row band,
    and its packed slots ``[tril_size(r0), tril_size(r1))`` are
    contiguous by row-major packing)."""
    return (k * n) // world, ((k + 1) * n) // world


def owner_of_rows(rows: np.ndarray, n: int, world: int) -> List[int]:
    bounds = np.array([(k * n) // world for k in range(1, world + 1)])
    return sorted(set(int(np.searchsorted(bounds, r, side="right"))
                      for r in np.asarray(rows).ravel()))


# -- route runners (jit-cached per route signature) -------------------------
_ROUTE_JIT: dict = {}


def _route_world(route: str, mesh, axis: str, c) -> int:
    """Devices whose row bands make up a route's payload: the c(c+1)
    triangle-block grid on the grid wires, the mesh axis on the others."""
    if route == "local":
        return 1
    from ..blas import meshpath
    if meshpath.WIRES[route].sharded:
        return c * (c + 1)
    return int(mesh.shape[axis])


def route_runner(op: str, route: str, mesh=None, axis: str = "x",
                 c: Optional[int] = None, p2: Optional[int] = None,
                 chunk: Optional[int] = None) -> Callable:
    """Jitted packed-output runner for (op, route) — the same
    :data:`~repro.blas.meshpath.WIRES` schedules the blas router
    dispatches to, on the caller's grid (``c``, ``p2``, ``chunk``), with
    ShardedTriTiles exits lowered to the element-packed triangle
    in-jit.  Cached so repeated checked calls reuse the compiled
    executable."""
    key = (op, route, mesh, axis, c, p2, chunk)
    fn = _ROUTE_JIT.get(key)
    if fn is not None:
        return fn
    from ..core.packing import pack_tril, unpack_tril
    if route == "local":
        mk = {
            "syrk": lambda a: pack_tril(a @ a.T),
            "syr2k": lambda a, b: pack_tril(a @ b.T + b @ a.T),
            "symm": lambda p, b: unpack_tril(
                p.astype(jnp.float32), b.shape[0], symmetric=True) @ b,
        }[op]
    else:
        from ..blas import meshpath
        from ..blas.routing import M_OF, Route
        from ..core.dispatch import AlgoChoice
        schedule = getattr(meshpath.WIRES[route], op)
        P = int(mesh.shape[axis])
        choice = AlgoChoice(route, 0, P, c=c or 0,
                            p1=(c or 0) * ((c or 0) + 1), p2=p2 or 1,
                            b=chunk or 0)

        def mk(*ops):
            n1, n2 = ops[-1].shape
            r = Route(op, route, "ABFT-checked call", n1, n2, M_OF[op],
                      P=P, axis=axis, choice=choice)
            return meshpath.as_packed(schedule(*ops, mesh, r))
    fn = jax.jit(mk)
    _ROUTE_JIT[key] = fn
    return fn


# -- shard repair from a trusted reference ----------------------------------
def repair_with_reference(out: jax.Array, reference: jax.Array, n: int,
                          c: int, *, rtol: float = 1e-6,
                          atol: float = 1e-6
                          ) -> Tuple[jax.Array, List[int]]:
    """Patch corrupted device shards of a packed triangle from a
    trusted reference (checkpointed words).

    Each of the P = c(c+1) wire devices' extended triangle blocks is
    rebuilt from the reference via
    :func:`~repro.distributed.straggler.rebuild_replacement_shard`
    (one slice-granular gather per device — never the dense n×n) and
    compared to the same shard of ``out``; differing shards are
    replaced.  Returns ``(repaired_packed, corrupted_devices)``.
    """
    from .straggler import rebuild_replacement_shard
    ref = jnp.asarray(reference)
    st = ShardedTriTiles.from_packed(jnp.asarray(out), n, c)
    off, diag = st.off, st.diag
    patched: List[int] = []
    for k in range(st.num_devices):
        off_r, diag_r = rebuild_replacement_shard(ref, n, c, k)
        bad = _differs(off[k], off_r, rtol, atol) \
            or _differs(diag[k], diag_r, rtol, atol)
        if bad:
            off = off.at[k].set(off_r.astype(off.dtype))
            diag = diag.at[k].set(diag_r.astype(diag.dtype))
            patched.append(k)
    if not patched:
        return out, patched
    return ShardedTriTiles(off, diag, n, c).to_packed(), patched


def _differs(x, y, rtol: float, atol: float) -> bool:
    d = jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32))
    tol = atol + rtol * jnp.abs(y.astype(jnp.float32))
    return bool(jnp.any(jnp.where(jnp.isnan(d), True, d > tol)))


# -- checked collectives ----------------------------------------------------
def _corrupt_packed(out: jax.Array, n: int, world: int, op: str,
                    step: Optional[int]) -> jax.Array:
    """Fault-injection hook: corrupt the armed device's row band of the
    packed payload (no-op without an active injector)."""
    sp = faults.payload_fault(f"collective:{op}", step)
    if sp is None:
        return out
    k = min(sp.device or 0, world - 1)
    r0, r1 = device_rows(n, world, k)
    return faults.corrupt_slots(out, tril_size(r0), tril_size(r1), sp,
                                f"collective:{op}", step)


def _corrupt_dense_rows(out: jax.Array, world: int, op: str,
                        step: Optional[int]) -> jax.Array:
    sp = faults.payload_fault(f"collective:{op}", step)
    if sp is None:
        return out
    n1, n2 = out.shape
    k = min(sp.device or 0, world - 1)
    r0, r1 = device_rows(n1, world, k)
    flat = faults.corrupt_slots(out.reshape(-1), r0 * n2, r1 * n2, sp,
                                f"collective:{op}", step)
    return flat.reshape(n1, n2)


def _checked(op: str, n: int, world: int, compute: Callable,
             corrupt: Callable, check: Callable, route: str,
             retries: int, backoff: float, reference, c,
             step: Optional[int]) -> Tuple[jax.Array, AbftReport]:
    report = AbftReport(op=op, route=route, n=n)
    delay = backoff
    for attempt in range(retries + 1):
        report.attempts = attempt + 1
        out = corrupt(compute(), step)
        bad_rows = np.nonzero(np.asarray(check(out)))[0]
        if bad_rows.size == 0:
            return out, report
        report.detected = True
        report.bad_rows = bad_rows[:16].tolist()
        report.devices = owner_of_rows(bad_rows, n, world)
        report.primary = owner_of_rows([int(bad_rows.max())], n,
                                       world)[0]
        if reference is not None and c is not None and op != "symm":
            repaired, patched = repair_with_reference(out, reference,
                                                      n, c)
            if patched and not np.asarray(check(repaired)).any():
                report.action = "rebuild"
                report.devices = patched
                return repaired, report
        report.action = "retry"
        if attempt >= retries:
            break
        time.sleep(delay)
        delay *= 2.0
    raise AbftError(
        f"ABFT checksum mismatch on {op}/{route} (n={n}) not repaired "
        f"after {report.attempts} attempts — rows {report.bad_rows} "
        f"(devices {report.devices})", report)


def checked_syrk(a: jax.Array, *, route: str = "local", mesh=None,
                 axis: str = "x", c: Optional[int] = None,
                 p2: Optional[int] = None, chunk: Optional[int] = None,
                 retries: int = 2, backoff: float = 0.02,
                 rtol: Optional[float] = None, atol: float = DEFAULT_ATOL,
                 reference: Optional[jax.Array] = None,
                 step: Optional[int] = None
                 ) -> Tuple[jax.Array, AbftReport]:
    """ABFT-checked packed SYRK over any mesh route.  Returns
    ``(packed, report)``; raises :class:`AbftError` when the checksum
    still fails after shard repair + ``retries`` recomputes."""
    n1, n2 = a.shape
    run = route_runner("syrk", route, mesh, axis, c, p2, chunk)
    chk = _check_syrk(n1, rtol if rtol is not None
                      else _default_rtol(n1, n2, a.dtype), atol)
    world = _route_world(route, mesh, axis, c)
    return _checked(
        "syrk", n1, world, lambda: run(a),
        lambda o, s: _corrupt_packed(o, n1, world, "syrk", s),
        lambda o: chk(a, o), route, retries, backoff, reference, c, step)


def checked_syr2k(a: jax.Array, b: jax.Array, *, route: str = "local",
                  mesh=None, axis: str = "x", c: Optional[int] = None,
                  p2: Optional[int] = None, chunk: Optional[int] = None,
                  retries: int = 2, backoff: float = 0.02,
                  rtol: Optional[float] = None,
                  atol: float = DEFAULT_ATOL,
                  reference: Optional[jax.Array] = None,
                  step: Optional[int] = None
                  ) -> Tuple[jax.Array, AbftReport]:
    """ABFT-checked packed SYR2K (C·1 = A·(Bᵀ1) + B·(Aᵀ1))."""
    n1, n2 = a.shape
    run = route_runner("syr2k", route, mesh, axis, c, p2, chunk)
    chk = _check_syr2k(n1, rtol if rtol is not None
                       else _default_rtol(n1, n2, a.dtype), atol)
    world = _route_world(route, mesh, axis, c)
    return _checked(
        "syr2k", n1, world, lambda: run(a, b),
        lambda o, s: _corrupt_packed(o, n1, world, "syr2k", s),
        lambda o: chk(a, b, o), route, retries, backoff, reference, c,
        step)


def checked_symm(a_packed: jax.Array, b: jax.Array, *,
                 route: str = "local", mesh=None, axis: str = "x",
                 c: Optional[int] = None, p2: Optional[int] = None,
                 chunk: Optional[int] = None, retries: int = 2,
                 backoff: float = 0.02, rtol: Optional[float] = None,
                 atol: float = DEFAULT_ATOL,
                 step: Optional[int] = None
                 ) -> Tuple[jax.Array, AbftReport]:
    """ABFT-checked SYMM (C = sym(S)·B, checksum C·1 = sym(S)·(B·1)).
    The symmetric operand is an input here, so repair is recompute."""
    n1, n2 = b.shape
    run = route_runner("symm", route, mesh, axis, c, p2, chunk)
    chk = _check_symm(n1, rtol if rtol is not None
                      else _default_rtol(n1, n2, b.dtype), atol)
    world = _route_world(route, mesh, axis, c)
    return _checked(
        "symm", n1, world, lambda: run(a_packed, b),
        lambda o, s: _corrupt_dense_rows(o, world, "symm", s),
        lambda o: chk(a_packed, b, o), route, retries, backoff, None,
        None, step)

"""(bm, bk) tile selection for the Pallas symmetric kernels.

Two modes:
  * heuristic (default) — MXU-aligned tiles derived from the problem
    shape, no measurement;
  * measured (``tile="auto"``)  — time a small candidate set once and
    remember the winner in an in-process dict AND an on-disk JSON cache,
    keyed by (op, shape, dtype, backend), so the search cost is paid at
    most once per problem class per machine.

The cache location is ``$REPRO_BLAS_CACHE_DIR`` (default
``~/.cache/repro_blas``).  Disk I/O failures are never fatal — the tuner
degrades to in-process caching.
"""
from __future__ import annotations

import itertools
import json
import os
import tempfile
import time
from typing import Callable, Dict, Optional, Tuple

from ..kernels import trigrid

Tiles = Tuple[int, int]

# measured-mode candidates: MXU-aligned; the lane-multiple ones are the
# v5e tile sweep's grid (PERF.md §6), so ``tile="auto"`` can find what
# the heuristic picks
_CANDIDATES: Tuple[Tiles, ...] = ((64, 64),) + tuple(
    itertools.product((128, 256, 512, 1024), repeat=2))

# extra non-square candidates tried only for the beta-accumulate
# epilogue: the streamed C0 tile is (bm, bm), so shrinking bm while
# keeping the contraction panel wide (or vice versa) trades accumulator
# VMEM against panel reuse — a trade square tiles cannot express.  The
# winner is cached per (fill, accumulate) via :func:`cache_key`.
_ACCUMULATE_EXTRA: Tuple[Tiles, ...] = ((64, 128), (64, 256), (128, 64),
                                        (256, 64), (64, 512))

_memory_cache: Dict[str, Tiles] = {}


def _dtype_token(dtype) -> str:
    """Canonical dtype spelling for cache keys.

    Callers hand us anything dtype-like — ``jnp.float32`` (a *type*,
    which stringifies as ``<class 'jax.numpy.float32'>``), ``np.dtype``
    instances, or plain strings — and naive f-string interpolation
    splits one problem class into several cache entries.  ``None``
    (dtype unknown at planning time) gets its own stable token.
    """
    if dtype is None:
        return "any"
    try:
        import jax.numpy as jnp
        return jnp.dtype(dtype).name
    except TypeError:
        return str(dtype)


def cache_key(op: str, n1: int, n2: int, dtype, backend: str,
              fill: str = "tril", accumulate: bool = False) -> str:
    """One cache slot per *epilogue*, not just per problem shape: the
    output layout (fill) and a beta-accumulate C0 input change a
    candidate's VMEM footprint and traffic, so tiles measured for one
    epilogue must not be reused for another."""
    acc = "acc" if accumulate else "noacc"
    return (f"{op}:{n1}x{n2}:{_dtype_token(dtype)}:{backend}"
            f":{fill}:{acc}")


def _cache_dir() -> str:
    return os.environ.get(
        "REPRO_BLAS_CACHE_DIR",
        os.path.join(os.path.expanduser("~"), ".cache", "repro_blas"))


def _cache_path() -> str:
    return os.path.join(_cache_dir(), "tiles.json")


def _load_disk() -> Dict[str, Tiles]:
    try:
        with open(_cache_path()) as f:
            raw = json.load(f)
        return {k: (int(v[0]), int(v[1])) for k, v in raw.items()
                if isinstance(v, (list, tuple)) and len(v) == 2}
    except (OSError, ValueError):
        return {}


def _store_disk(key: str, tiles: Tiles) -> None:
    """Read-modify-write with an atomic replace; best-effort only."""
    try:
        os.makedirs(_cache_dir(), exist_ok=True)
        data = {k: list(v) for k, v in _load_disk().items()}
        data[key] = list(tiles)
        fd, tmp = tempfile.mkstemp(dir=_cache_dir(), suffix=".json")
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=0, sort_keys=True)
        os.replace(tmp, _cache_path())
    except OSError:
        pass


def clear_cache(disk: bool = False) -> None:
    """Drop the in-process cache (and optionally the on-disk file)."""
    _memory_cache.clear()
    if disk:
        try:
            os.remove(_cache_path())
        except OSError:
            pass


def _round_up_tile(n: int, cap: int = 128, floor: int = 8) -> int:
    """Smallest power of two >= n (>= floor), capped at ``cap``."""
    t = floor
    while t < n and t < cap:
        t *= 2
    return min(t, cap)


#: lane width of the TPU; a side of at least this is padded to a multiple
LANE = 128

#: largest tile side: on a TPU v5e, over {128, ..., 1024}² at the
#: Newton–Schulz shapes, every op ran fastest at 1024 rows and the widest
#: column tile that fits (PERF.md §6)
TILE_CAP = 1024

#: VMEM a grid step may hold at the heuristic's tiles (f32 operands):
#: (1024, 1024) for SYRK and SYMM, (1024, 512) for SYR2K's four panels
VMEM_BUDGET = 48 << 20


def _side_tile(n: int) -> int:
    """Shrink-to-fit up to one lane; above it the largest power of two
    up to ``TILE_CAP`` that divides n rounded up to a lane, so a larger
    tile never pads more than the lane rounding does (640 stays at
    128)."""
    if n <= LANE:
        return _round_up_tile(n)
    padded = -(-n // LANE) * LANE
    t = LANE
    while 2 * t <= TILE_CAP and padded % (2 * t) == 0:
        t *= 2
    return t


def _vmem(op: str, t0: int, t1: int) -> int:
    if op == "symm":
        return trigrid.sym_stream_vmem(t0, t1)
    return trigrid.rank_update_vmem(4 if op == "syr2k" else 2, t0, t1)


def heuristic_tiles(op: str, n1: int, n2: int) -> Tiles:
    """Shape-derived MXU-aligned default, from the op and the shape
    alone: each side's tile as :func:`_side_tile` gives it, then the
    wider (the column tile on a tie) halved until a grid step fits
    ``VMEM_BUDGET``.  Small sides shrink to fit (padding a 20-row
    matrix to 128 would waste 6x the kernel work).  SYMM's ``bn``
    follows n2, at least one lane once n1 fills one, so a one-column
    SYMM pads to 128 columns and no further."""
    bm = _side_tile(n1)
    bk = _side_tile(max(n2, min(n1, LANE)) if op == "symm" else n2)
    while _vmem(op, bm, bk) > VMEM_BUDGET and max(bm, bk) > LANE:
        if bk >= bm:
            bk //= 2
        else:
            bm //= 2
    return bm, bk


def pick_tiles(op: str, n1: int, n2: int, dtype, backend: str, *,
               mode: str = "heuristic",
               runner: Optional[Callable[[int, int], float]] = None,
               repeats: int = 2, fill: str = "tril",
               accumulate: bool = False) -> Tiles:
    """Tiles for (op, n1, n2, dtype, backend, fill, accumulate).

    ``mode="heuristic"``: shape-derived, not cached on disk.
    ``mode="auto"``: consult the in-process then on-disk cache; on a
    miss, time ``runner(bm, bk)`` (seconds; the caller provides a
    blocking executor of the real kernel) over the candidate set and
    persist the winner — keyed per epilogue (fill/accumulate).
    """
    if mode != "auto":
        return heuristic_tiles(op, n1, n2)
    key = cache_key(op, n1, n2, dtype, backend, fill, accumulate)
    if key in _memory_cache:
        return _memory_cache[key]
    disk = _load_disk()
    if key in disk:
        _memory_cache[key] = disk[key]
        return disk[key]
    if runner is None:
        tiles = heuristic_tiles(op, n1, n2)
        _memory_cache[key] = tiles
        return tiles
    best, best_t = None, float("inf")
    for bm, bk in _candidates_for(n1, n2, accumulate=accumulate):
        try:
            runner(bm, bk)                    # compile + warm up
            t = min(_time_once(runner, bm, bk) for _ in range(repeats))
        except Exception:                     # candidate invalid: skip
            continue
        if t < best_t:
            best, best_t = (bm, bk), t
    tiles = best or heuristic_tiles(op, n1, n2)
    _memory_cache[key] = tiles
    _store_disk(key, tiles)
    return tiles


def _candidates_for(n1: int, n2: int, accumulate: bool = False
                    ) -> Tuple[Tiles, ...]:
    """Candidates no larger than ~2x the (padded) problem; the
    beta-accumulate epilogue widens the set with non-square (bm, bk)
    (its C0 stream changes the VMEM budget per bm)."""
    pool = _CANDIDATES + (_ACCUMULATE_EXTRA if accumulate else ())
    out = [t for t in pool if t[0] <= 2 * n1 and t[1] <= 2 * n2]
    return tuple(out) or (heuristic_tiles("syrk", n1, n2),)


def _time_once(runner: Callable[[int, int], float], bm: int, bk: int
               ) -> float:
    t0 = time.perf_counter()
    runner(bm, bk)
    return time.perf_counter() - t0

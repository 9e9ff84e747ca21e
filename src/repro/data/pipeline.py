"""Deterministic synthetic token pipeline with sharded host loading.

Design mirrors a production loader:

  * **Deterministic addressing** — sample ``i`` of epoch ``e`` is a pure
    function of ``(seed, e, i)``; restarts resume mid-epoch from the step
    counter alone (no loader state in checkpoints beyond one integer).
  * **Sharded host loading** — each host materializes only its slice of
    the global batch (``host_id``/``num_hosts``), then the arrays are
    placed with ``jax.make_array_from_process_local_data`` in multi-host
    runs or ``device_put`` here.
  * **Document packing** — variable-length synthetic "documents" are
    packed into fixed ``seq_len`` rows with EOS separators, the standard
    LM pretraining treatment (no padding waste).
  * **Async prefetch** — a background thread keeps ``prefetch`` batches
    ready so host data work overlaps device compute.  In a profiler
    trace its spans are ``repro.data.produce`` (synthesis and packing)
    and ``repro.data.put`` (``device_put``); the consumer's blocking
    wait is ``repro.data.wait``.

The synthetic distribution is a small LCG-mixed Markov stream — cheap,
seekable, and with enough temporal structure that a model's loss visibly
drops within a few hundred steps (used by examples/train_lm.py).
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro.models.common import ArchConfig


@dataclass(frozen=True)
class DataConfig:
    seq_len: int
    global_batch: int
    vocab_size: int
    seed: int = 0
    mean_doc_len: int = 512
    eos_id: int = 0
    prefetch: int = 2


# --------------------------------------------------------------------- #
# deterministic synthetic stream
# --------------------------------------------------------------------- #

def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 — uint64 -> uint64 bijective hash (vectorized)."""
    x = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
    x ^= x >> np.uint64(30)
    x = (x * np.uint64(0xBF58476D1CE4E5B9)).astype(np.uint64)
    x ^= x >> np.uint64(27)
    x = (x * np.uint64(0x94D049BB133111EB)).astype(np.uint64)
    x ^= x >> np.uint64(31)
    return x


class SyntheticLM:
    """Seekable synthetic corpus: document ``d`` is a Markov chain whose
    transition row is a deterministic function of (seed, d, prev_token).
    Documents have hash-derived lengths ~ mean_doc_len."""

    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        # per-seed mixing constant folded into every hash
        self._base = _mix(np.array([cfg.seed], dtype=np.uint64))[0]

    def doc_len(self, doc_id: np.ndarray) -> np.ndarray:
        h = _mix(doc_id.astype(np.uint64) ^ self._base)
        lo = max(self.cfg.mean_doc_len // 2, 8)
        hi = self.cfg.mean_doc_len * 3 // 2
        return (lo + (h % np.uint64(hi - lo))).astype(np.int64)

    def document(self, doc_id: int) -> np.ndarray:
        """Markov-ish chain: tok_{t+1} = h(doc, tok_t, t) with a skewed
        modulus so bigram statistics are learnable."""
        n = int(self.doc_len(np.array([doc_id]))[0])
        c = self.cfg
        toks = np.empty(n, dtype=np.int64)
        h0 = _mix(np.array([doc_id], dtype=np.uint64) ^ self._base)[0]
        tok = int(h0 % np.uint64(c.vocab_size))
        for t in range(n):
            toks[t] = tok
            h = _mix(np.array([(doc_id << 20) ^ (tok << 2) ^ t],
                              dtype=np.uint64) ^ self._base)[0]
            # 75% of steps follow a per-token deterministic successor
            # (learnable bigram); 25% jump randomly.
            if h % np.uint64(4) != 0:
                tok = int(_mix(np.array([tok], dtype=np.uint64)
                               ^ self._base)[0] % np.uint64(c.vocab_size))
            else:
                tok = int(h % np.uint64(c.vocab_size))
        if c.eos_id < c.vocab_size:
            toks[-1] = c.eos_id
        return toks


def pack_documents(docs: List[np.ndarray], seq_len: int,
                   eos_id: int) -> List[np.ndarray]:
    """Greedy-pack variable-length docs into fixed seq_len+1 rows (the
    +1 feeds the shift-by-one label split)."""
    rows, buf = [], np.empty(0, dtype=np.int64)
    for d in docs:
        buf = np.concatenate([buf, d])
        while buf.shape[0] >= seq_len + 1:
            rows.append(buf[:seq_len + 1].copy())
            buf = buf[seq_len + 1:]
    return rows


# --------------------------------------------------------------------- #
# batch iterator
# --------------------------------------------------------------------- #

class _HostShardIterator:
    """Yields this host's shard of each global batch, deterministically
    addressed by step."""

    def __init__(self, cfg: DataConfig, host_id: int, num_hosts: int):
        assert cfg.global_batch % num_hosts == 0
        self.cfg = cfg
        self.host_id = host_id
        self.num_hosts = num_hosts
        self.local_batch = cfg.global_batch // num_hosts
        self.corpus = SyntheticLM(cfg)
        self._rows: List[np.ndarray] = []
        self._next_doc = host_id          # round-robin doc ownership
        self._step = 0

    def seek(self, step: int) -> None:
        """Jump to an absolute step (restart support).

        Row production is a deterministic function of the doc-id
        sequence, so skipping ``step × local_batch`` rows replays the
        stream exactly.  Doc lengths are hash-derived (``doc_len``), so
        whole documents are skipped WITHOUT materializing tokens; only
        the final partially-consumed document is regenerated.  Host cost
        is O(step) int hashes — production systems amortize this with a
        row index, which slots in behind this same method.
        """
        self._rows = []
        self._next_doc = self.host_id
        self._step = step
        self._buf = np.empty(0, dtype=np.int64)
        L = self.cfg.seq_len + 1
        target_tokens = step * self.local_batch * L
        skipped = 0
        # skip whole documents while they fit strictly below the target
        while True:
            dl = int(self.corpus.doc_len(np.array([self._next_doc]))[0])
            if skipped + dl <= target_tokens:
                skipped += dl
                self._next_doc += self.num_hosts
            else:
                break
        # regenerate the boundary document; drop already-consumed tokens
        if skipped < target_tokens:
            doc = self.corpus.document(self._next_doc)
            self._next_doc += self.num_hosts
            self._buf = doc[target_tokens - skipped:].copy()
        # target_tokens is a multiple of L, so _buf now starts exactly
        # at a row boundary — replay from here is byte-exact.

    _buf = np.empty(0, dtype=np.int64)

    def _fill(self, n_rows: int) -> None:
        L = self.cfg.seq_len + 1
        while len(self._rows) < n_rows:
            doc = self.corpus.document(self._next_doc)
            self._next_doc += self.num_hosts
            self._buf = np.concatenate([self._buf, doc])
            while self._buf.shape[0] >= L:
                self._rows.append(self._buf[:L].copy())
                self._buf = self._buf[L:]

    def __next__(self) -> Dict[str, np.ndarray]:
        self._fill(self.local_batch)
        rows = np.stack(self._rows[:self.local_batch])
        self._rows = self._rows[self.local_batch:]
        self._step += 1
        return {"tokens": rows[:, :-1].astype(np.int32),
                "labels": rows[:, 1:].astype(np.int32)}


def make_train_iterator(cfg: DataConfig, *, start_step: int = 0,
                        host_id: int = 0, num_hosts: int = 1,
                        sharding: Optional[Any] = None,
                        frontend: str = "tokens",
                        d_model: int = 0) -> Iterator[Dict[str, Any]]:
    """Prefetching iterator of device-ready batches.

    ``sharding`` (a NamedSharding for the (batch, seq) layout) places
    each batch; None leaves host numpy arrays (useful in tests).
    ``frontend='embeddings'`` converts tokens to deterministic embedding
    stand-ins for audio/VLM stub frontends.
    """
    it = _HostShardIterator(cfg, host_id, num_hosts)
    if start_step:
        it.seek(start_step)

    def produce() -> Dict[str, Any]:
        with TraceAnnotation("repro.data.produce"):
            batch = synthesize()
        if sharding is not None:
            with TraceAnnotation("repro.data.put"):
                batch = {k: jax.device_put(v, sharding[k])
                         if isinstance(sharding, dict)
                         else jax.device_put(v, sharding)
                         for k, v in batch.items()}
        return batch

    def synthesize() -> Dict[str, np.ndarray]:
        batch = next(it)
        if frontend == "embeddings":
            toks = batch.pop("tokens")
            scale = 1.0 / np.sqrt(max(d_model, 1))
            emb = (_mix(toks.astype(np.uint64)[..., None]
                        * np.uint64(d_model)
                        + np.arange(d_model, dtype=np.uint64))
                   % np.uint64(2048)).astype(np.float32)
            batch["embeds"] = ((emb / 1024.0 - 1.0) * scale) \
                .astype(np.float32)
        return batch

    q: "queue.Queue" = queue.Queue(maxsize=cfg.prefetch)
    stop = threading.Event()

    def worker():
        pending = None
        while not stop.is_set():
            if pending is None:
                pending = produce()
            try:
                q.put(pending, timeout=0.5)
                pending = None          # only drop once delivered
            except queue.Full:
                continue

    th = threading.Thread(target=worker, daemon=True)
    th.start()

    class _Iter:
        def __iter__(self):
            return self

        def __next__(self):
            with TraceAnnotation("repro.data.wait"):
                return q.get()

        def close(self):
            stop.set()

    return _Iter()

"""Batched serving driver: continuous-batching-lite over prefill/decode.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
        --requests 16 --max-new 32 --whiten cache

Serving model:
  * requests arrive with variable prompt lengths and a tenant id; the
    scheduler packs them into fixed decode batches (slots),
  * prefill runs right-padded at a bucketed length and writes each
    sequence's KV/state cache into its slot — the bucket ladder is
    AOT-precompiled up front, so a long-tailed length distribution
    cannot accumulate compiles mid-serve (``prefill_compiles`` in the
    report counts every compile, precompiled or fallback),
  * decode advances ALL live slots one token per step; finished slots
    (EOS or max-new) are refilled from the queue without stopping the
    batch — the standard continuous-batching loop,
  * per-request symmetric statistics (activation Grams -> whitened
    prompt embeddings) are served from the multi-tenant packed cache
    (launch/serving_cache.py): ``--whiten cache`` folds each prompt's
    final-norm features into the per-(tenant, arch, layer) packed EMA
    and reads the latest *ready* whitening factor — the factor refresh
    (coupled Newton–Schulz on the packed words, routed ``repro.blas``)
    runs on a background executor, never on the decode loop.
    ``--whiten sync`` is the pre-cache baseline: a from-scratch Gram +
    dense eigh whitening per admitted request, on the hot loop — what
    this cache exists to amortize.  ``--whiten off`` skips statistics.
  * per-request latency (p50/p99), TTFT, and aggregate tokens/s are
    reported; generated tokens are independent of the whiten mode (the
    embedding is a per-request side output), so cache-on/off compare
    identical token work.

On a pod the same step functions shard via the production mesh
(launch/dryrun.py proves prefill_32k / decode_32k lower + compile on
16×16 and 2×16×16); here the driver runs the smoke config on CPU.
"""
from __future__ import annotations

import argparse
import json
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import blas
from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.serving_cache import ServingGramCache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.model import init_cache, init_params
from repro.optim.gram import packed_gram, whitening_from_packed


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (len,) int32
    tenant: str = "default"
    arrived: float = 0.0
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None
    generated: List[int] = field(default_factory=list)
    embedding: Optional[np.ndarray] = None   # whitened prompt embedding


def synthetic_requests(n: int, vocab: int, seed: int = 0,
                       lo: int = 8, hi: int = 48,
                       tenants: int = 1) -> List[Request]:
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(
        1, vocab, size=int(rng.integers(lo, hi))).astype(np.int32),
        tenant=f"tenant{i % max(1, tenants)}")
        for i in range(n)]


class Server:
    """Slot-based continuous batching around jitted prefill/decode.

    ``whiten``: "off" (no per-request statistics), "cache" (packed
    Gram EMA + async-refreshed factor from ``gram_cache``), or "sync"
    (per-request from-scratch Gram + dense eigh on the admit path —
    the uncached baseline).  ``precompile=True`` AOT-compiles the full
    prefill bucket ladder in the constructor; on-demand fallback
    compiles are LRU-capped at ``prefill_cache_cap`` entries and both
    are counted in ``prefill_compiles``.
    """

    def __init__(self, cfg, params, *, slots: int, s_max: int,
                 max_new: int, eos_id: int = 0, whiten: str = "off",
                 gram_cache: Optional[ServingGramCache] = None,
                 precompile: bool = True, prefill_cache_cap: int = 8):
        if whiten not in ("off", "cache", "sync"):
            raise ValueError(f"whiten must be off/cache/sync: {whiten!r}")
        if whiten == "cache" and gram_cache is None:
            gram_cache = ServingGramCache()
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.s_max = s_max
        self.max_new = max_new
        self.eos_id = eos_id
        self.whiten = whiten
        self.gram_cache = gram_cache
        self.decode = jax.jit(make_decode_step(cfg))
        self._prefill_base = make_prefill_step(
            cfg, s_max=s_max, return_hidden=whiten != "off")
        self._prefill: "OrderedDict[int, object]" = OrderedDict()
        self.prefill_cache_cap = max(prefill_cache_cap, 1)
        self.prefill_compiles = 0
        if precompile:
            for b in self.bucket_ladder():
                self._compile_bucket(b)
        self.cache = init_cache(cfg, slots, s_max)
        self.pos = np.zeros(slots, np.int32)        # next position
        self.live: List[Optional[Request]] = [None] * slots
        self.last_tok = np.zeros((slots, 1), np.int32)
        if whiten != "off":
            # Jitted per-admit statistics pipeline.  feats stay at the
            # BUCKET length with padded columns masked to zero (zero
            # columns add nothing to X·Xᵀ, and pooling divides by the
            # true L), so jax's shape-keyed jit cache compiles at most
            # once per ladder bucket — an eager per-request pipeline
            # costs ~10 dispatches per admit and dominates the very
            # statistics work being measured.
            def _prep(hidden, L):
                feats = hidden[0].astype(jnp.float32)     # (bucket, d)
                mask = (jnp.arange(feats.shape[0]) < L)[:, None]
                feats = jnp.where(mask, feats, 0.0).T     # (d, bucket)
                pooled = feats.sum(axis=1) / L.astype(jnp.float32)
                return feats, pooled
            self._prep = jax.jit(_prep)
            self._apply_w = jax.jit(
                lambda w, p: blas.symm(w, p[:, None])[:, 0])
            if whiten == "sync":
                d = cfg.d_model
                self._sync_whiten = jax.jit(
                    lambda f: whitening_from_packed(
                        packed_gram(f), d, method="eigh"))
            if precompile:
                self._warm_statistics()

    def _bucket(self, n: int) -> int:
        b = 16
        while b < n:
            b *= 2
        return min(b, self.s_max)

    def bucket_ladder(self) -> List[int]:
        """Every bucket :meth:`_bucket` can emit: the 16·2^k sizes up
        to s_max, plus the s_max clamp itself."""
        ladder = []
        b = 16
        while b < self.s_max:
            ladder.append(b)
            b *= 2
        ladder.append(self.s_max)
        return ladder

    def _compile_bucket(self, bucket: int):
        """AOT compile the prefill step for one bucket length."""
        spec = {"tokens": jax.ShapeDtypeStruct((1, bucket), jnp.int32)}
        fn = jax.jit(self._prefill_base).lower(self.params,
                                               spec).compile()
        self.prefill_compiles += 1
        self._prefill[bucket] = fn
        while len(self._prefill) > max(self.prefill_cache_cap,
                                       len(self.bucket_ladder())):
            self._prefill.popitem(last=False)       # LRU evict
        return fn

    def _prefill_fn(self, bucket: int):
        fn = self._prefill.get(bucket)
        if fn is None:
            fn = self._compile_bucket(bucket)
        else:
            self._prefill.move_to_end(bucket)
        return fn

    def _warm_statistics(self) -> None:
        """Pre-compile the per-admit statistics pipeline for every
        ladder bucket (pure calls on zeros — cache state untouched), the
        AOT-ladder discipline applied to the embedding path: without
        this the first admit per bucket pays the jit compile mid-serve,
        which at small request counts dominates the very statistics
        work being measured."""
        d = self.cfg.d_model
        hdt = jax.tree.leaves(self.params)[0].dtype
        self._apply_w(jnp.eye(d, dtype=jnp.float32),
                      jnp.zeros((d,), jnp.float32))
        for b in self.bucket_ladder():
            self._prep(jnp.zeros((1, b, d), hdt), jnp.int32(1))
            if self.whiten == "sync":
                self._sync_whiten(jnp.zeros((d, b), jnp.float32))
        if self.whiten == "cache":
            self.gram_cache.warm_compile(d, self.bucket_ladder())

    def _embed(self, req: Request, hidden: jax.Array, L: int) -> None:
        """Per-request whitened prompt embedding from the final-norm
        features.  "cache": packed EMA update + latest ready factor
        (async refresh off this path); "sync": from-scratch Gram +
        dense eigh per request — the uncached hot-loop baseline."""
        feats, pooled = self._prep(hidden, jnp.int32(L))
        if self.whiten == "cache":
            self.gram_cache.update(req.tenant, self.cfg.name, "final",
                                   feats)
            w = self.gram_cache.factor(req.tenant, self.cfg.name,
                                       "final")
            if w is None:                                 # cold start
                req.embedding = np.asarray(pooled)
                return
        else:                                             # "sync"
            w = self._sync_whiten(feats)
        req.embedding = np.asarray(
            self._apply_w(w, pooled))                     # routed SYMM

    def admit(self, req: Request, slot: int) -> None:
        """Prefill one request into a slot."""
        L = len(req.prompt)
        bucket = self._bucket(L)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :L] = req.prompt
        out = self._prefill_fn(bucket)(
            self.params, {"tokens": jnp.asarray(toks)})
        if self.whiten != "off":
            logits, cache1, hidden = out
            self._embed(req, hidden, L)
        else:
            logits, cache1 = out
        # copy the batch-1 prefill cache into this slot
        def put(dst, src):
            return dst.at[slot:slot + 1].set(src[0:1])
        self.cache = jax.tree.map(put, self.cache, cache1)
        nxt = int(jnp.argmax(logits[0, -1]))
        req.first_token_t = time.perf_counter()
        req.generated.append(nxt)
        self.live[slot] = req
        self.pos[slot] = L
        self.last_tok[slot, 0] = nxt

    def step(self) -> None:
        """One decode step over every slot (dead slots idle on pad)."""
        tok = jnp.asarray(self.last_tok)
        pos = jnp.asarray(self.pos[:, None])
        nxt, _, self.cache = self.decode(self.params, tok, pos,
                                         self.cache)
        nxt = np.asarray(nxt)
        now = time.perf_counter()
        for s, req in enumerate(self.live):
            if req is None:
                continue
            t = int(nxt[s, 0])
            req.generated.append(t)
            self.pos[s] += 1
            self.last_tok[s, 0] = t
            if t == self.eos_id or len(req.generated) >= self.max_new \
                    or self.pos[s] >= self.s_max - 1:
                req.done_t = now
                self.live[s] = None

    def free_slot(self) -> Optional[int]:
        for s, r in enumerate(self.live):
            if r is None:
                return s
        return None


def serve(args) -> Dict:
    cfg = get_smoke_config(args.arch) if args.smoke \
        else get_config(args.arch)
    params = init_params(cfg, jax.random.key(args.seed))
    reqs = synthetic_requests(args.requests, cfg.vocab, args.seed,
                              lo=args.prompt_lo, hi=args.prompt_hi,
                              tenants=args.tenants)
    gram_cache = None
    if args.whiten == "cache":
        gram_cache = ServingGramCache(
            refresh_stride=args.refresh_stride)
        if args.warm_start:
            n = gram_cache.warm_start(args.warm_start)
            print(f"[serve] warm start: {n} cache entries from "
                  f"{args.warm_start}")
    queue = list(reqs)
    t_build = time.perf_counter()
    srv = Server(cfg, params, slots=args.slots, s_max=args.s_max,
                 max_new=args.max_new, eos_id=-1 if args.no_eos else 0,
                 whiten=args.whiten, gram_cache=gram_cache)
    # the clock starts when the server can admit: tokens/s and latency
    # measure steady-state serving, with the one-time AOT bring-up
    # (prefill ladder + statistics pipeline) reported as startup_s
    t0 = time.perf_counter()
    for r in queue:
        r.arrived = t0

    done: List[Request] = []
    steps = 0
    while queue or any(r is not None for r in srv.live):
        # refill free slots (continuous batching)
        while queue:
            s = srv.free_slot()
            if s is None:
                break
            srv.admit(queue.pop(0), s)
        srv.step()
        steps += 1
        done = [r for r in reqs if r.done_t is not None]
        if steps > args.requests * args.max_new:
            break
    t1 = time.perf_counter()
    if gram_cache is not None:
        gram_cache.drain()
        if args.save_cache:
            gram_cache.save(args.save_cache, step=0)
            print(f"[serve] cache state saved to {args.save_cache}")

    done = [r for r in reqs if r.done_t is not None]
    toks = sum(len(r.generated) for r in reqs)
    ttfts = [r.first_token_t - r.arrived for r in done]
    lats = [r.done_t - r.arrived for r in done]
    pct = lambda xs, q: float(np.percentile(xs, q)) if xs else None
    out = {"arch": cfg.name, "requests": len(reqs),
           "tenants": args.tenants, "whiten": args.whiten,
           "completed": len(done), "decode_steps": steps,
           "total_new_tokens": toks,
           "tokens_per_s": toks / (t1 - t0),
           "startup_s": t0 - t_build,
           "mean_ttft_s": float(np.mean(ttfts)) if ttfts else None,
           "p50_ttft_s": pct(ttfts, 50), "p99_ttft_s": pct(ttfts, 99),
           "mean_latency_s": float(np.mean(lats)) if lats else None,
           "p50_latency_s": pct(lats, 50),
           "p99_latency_s": pct(lats, 99),
           "prefill_compiles": srv.prefill_compiles,
           "bucket_ladder": srv.bucket_ladder()}
    if gram_cache is not None:
        out["cache"] = gram_cache.snapshot_stats()
    print("[serve] done:", json.dumps(out))
    return out


def build_argparser():
    ap = argparse.ArgumentParser(description="batched serving driver")
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--s-max", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--prompt-lo", type=int, default=8)
    ap.add_argument("--prompt-hi", type=int, default=48)
    ap.add_argument("--tenants", type=int, default=1)
    ap.add_argument("--whiten", choices=("off", "cache", "sync"),
                    default="off",
                    help="per-request whitened embeddings: 'cache' = "
                         "multi-tenant packed Gram cache with async "
                         "factor refresh; 'sync' = from-scratch Gram + "
                         "eigh per request (uncached baseline)")
    ap.add_argument("--refresh-stride", type=int, default=8,
                    help="cache mode: refresh the whitening factor "
                         "every N Gram updates per (tenant, layer)")
    ap.add_argument("--warm-start", default=None,
                    help="cache mode: packed checkpoint dir to restore "
                         "Gram state from before serving")
    ap.add_argument("--save-cache", default=None,
                    help="cache mode: save Gram state to this dir "
                         "after serving")
    ap.add_argument("--no-eos", action="store_true", default=True,
                    help="synthetic prompts rarely emit EOS; cap by "
                         "--max-new instead")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    args = build_argparser().parse_args(argv)
    enable_compilation_cache()
    serve(args)


if __name__ == "__main__":
    main()

"""Plain reference of a dense decoder train step with Muon.

Straight ``jax.numpy`` in float32 at ``Precision.HIGHEST``: no kernel,
no cache, no sharding rule of the program.  It reads the configuration
file (Hugging Face key names) and imports nothing of the program.

Model: token embedding; per layer a pre-norm block (LayerNorm with bias,
or RMSNorm as ``x·(1 + scale)``), grouped-query attention with rotary
embeddings on the first ``partial_rotary_factor`` of each head (pairs
interleaved), causal softmax; a pre-norm SwiGLU MLP ``(silu(x Wi) ⊙ x Wg)
Wo``; final norm; untied head; mean cross-entropy over every token.

Departures from the published models, each a weight-layout convention
that random weights cannot tell apart: rotary pairs are interleaved
(Hugging Face rotates halves: a fixed permutation of q/k columns), and
RMSNorm's gain is stored as ``scale`` with gain ``1 + scale``.

Optimizer (Muon as the traffic file states it): gradients at the
parameters' storage type, clipped by global norm; momentum in float32;
each matrix (rank ≥ 2, both trailing sides ≥ ``ns_min_side``) is
orthogonalized per trailing 2-D slice on its short side by the quintic
Newton–Schulz chain and scaled by sqrt(max(1, rows/cols)); every other
leaf takes a sign step.  Parameters are stored at their type after each
step, as the configuration states.

Variants, each the reference put in the program's place:
  ``reference``    as above;
  ``control``      each stated precision one step down: matmuls the
                   configuration runs in bfloat16 take float8 (e4m3)
                   operands, float32 ones (attention scores, the head,
                   the NS chain) take bfloat16;
  ``ns_control``   the NS chain alone one step down, in bfloat16
                   (operands, products and iterates), the model as in
                   ``reference``;
  ``half_batch``   loss and gradients over the first half of the rows;
  ``no_exchange``  each matrix's NS chain on column shards of
                   ``exchange_shards`` with no sum between them, as a
                   mesh step that left out its collectives would.

Required work: ``model_flop(cfg, batch, seq)`` is the flop one step of
this model needs, from the published shapes (``bench/work.py``'s dense
count: every matmul parameter active for every token, attention at
``head_dim``); the harness's ``step_mfu`` divides it, with the NS
chains, by the chip's peak.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from bench import weights
from bench.work import model_flop  # noqa: F401  (the required work)

HIGHEST = jax.lax.Precision.HIGHEST
VARIANTS = ("reference", "control", "ns_control", "half_batch",
            "no_exchange")


# ------------------------------------------------------------------ layout
def layout(cfg: Dict) -> List[tuple]:
    """(path, shape, dtype, init rule) of every parameter."""
    d, ff, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    L = cfg["num_hidden_layers"]
    wdt, ndt = cfg["param_dtype"], cfg["norm_dtype"]
    init = cfg["init"]
    out = [("embed", (v, d), wdt, init["embed"])]
    if not cfg["tie_word_embeddings"]:
        out.append(("unembed", (d, v), wdt, init["matrix"]))

    def norm(prefix, lead):
        leaves = [(f"{prefix}/scale", lead + (d,), ndt, init["norm_scale"])]
        if cfg["norm"] == "layernorm":
            leaves.append((f"{prefix}/bias", lead + (d,), ndt,
                           init["norm_bias"]))
        return leaves

    out += norm("final_norm", ())
    blk = "periods/b0"
    out += norm(f"{blk}/norm1", (L,))
    out += [(f"{blk}/mixer/wq", (L, d, h * hd), wdt, init["matrix"]),
            (f"{blk}/mixer/wk", (L, d, kv * hd), wdt, init["matrix"]),
            (f"{blk}/mixer/wv", (L, d, kv * hd), wdt, init["matrix"]),
            (f"{blk}/mixer/wo", (L, h * hd, d), wdt, init["matrix"])]
    out += norm(f"{blk}/norm2", (L,))
    out += [(f"{blk}/mlp/wi", (L, d, ff), wdt, init["matrix"]),
            (f"{blk}/mlp/wg", (L, d, ff), wdt, init["matrix"]),
            (f"{blk}/mlp/wo", (L, ff, d), wdt, init["matrix"])]
    return out


# ------------------------------------------------------------------- model
def _round(x, dtype):
    """``x`` rounded to ``dtype`` in the forward pass; the backward pass
    takes the cotangent through unrounded (straight through), as a
    lower-precision forward with a float32 gradient path would."""
    return x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)


class _Math:
    """Matmuls of one variant: ``lo`` rounds operands the configuration
    multiplies in bfloat16, ``hi`` those it multiplies in float32."""

    def __init__(self, variant: str):
        control = variant == "control"
        self.lo_t = jnp.float8_e4m3fn if control else None
        self.hi_t = jnp.bfloat16 if control else None

    def _mm(self, spec, a, b, t):
        if t is not None:
            a, b = _round(a, t), _round(b, t)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    def lo(self, spec, a, b):
        return self._mm(spec, a, b, self.lo_t)

    def hi(self, spec, a, b):
        return self._mm(spec, a, b, self.hi_t)


def _norm(cfg, p, x):
    if cfg["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + cfg["layer_norm_eps"])
        return y * p["scale"] + p["bias"]
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + cfg["rms_norm_eps"]) * (1.0 + p["scale"])


def _rope(cfg, x, pos):
    """x (B, S, H, D): rotate interleaved pairs of the first fraction."""
    d = x.shape[-1]
    rot = int(d * cfg["partial_rotary_factor"])
    rot -= rot % 2
    if rot == 0:
        return x
    inv = 1.0 / (cfg["rope_theta"]
                 ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = pos[:, :, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :rot:2], x[..., 1:rot:2]
    r = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([r.reshape(x[..., :rot].shape), x[..., rot:]], -1)


def _layer(cfg, mth: _Math, x, lp):
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    b, s, d = x.shape
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    a = _norm(cfg, lp["norm1"], x)
    att = lp["mixer"]
    q = _rope(cfg, mth.lo("bsd,de->bse", a, att["wq"]).reshape(b, s, h, hd),
              pos)
    k = _rope(cfg, mth.lo("bsd,de->bse", a, att["wk"]).reshape(b, s, kv, hd),
              pos)
    v = mth.lo("bsd,de->bse", a, att["wv"]).reshape(b, s, kv, hd)
    q = q.reshape(b, s, kv, h // kv, hd) * hd ** -0.5
    logits = mth.hi("bqkgd,bpkd->bkgqp", q, k)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    o = mth.hi("bkgqp,bpkd->bqkgd", w, v).reshape(b, s, h * hd)
    x = x + mth.lo("bse,ed->bsd", o, att["wo"])
    m = _norm(cfg, lp["norm2"], x)
    mlp = lp["mlp"]
    gate = jax.nn.silu(mth.lo("bsd,df->bsf", m, mlp["wi"]))
    up = mth.lo("bsd,df->bsf", m, mlp["wg"])
    return x + mth.lo("bsf,fd->bsd", gate * up, mlp["wo"])


def loss_fn(cfg, variant: str, params, tokens, labels, row_chunk: int = 512):
    """Mean cross-entropy of the batch (rows of ``tokens``)."""
    mth = _Math(variant)
    if variant == "half_batch":
        half = tokens.shape[0] // 2
        tokens, labels = tokens[:half], labels[:half]
    x = params["embed"].astype(jnp.float32)[tokens]
    body = jax.checkpoint(lambda c, lp: (_layer(cfg, mth, c, lp), None))
    x, _ = jax.lax.scan(body, x, params["periods"]["b0"])
    fn = jax.tree.map(lambda a: a.astype(jnp.float32), params["final_norm"])
    x = _norm(cfg, fn, x)
    head = (params["embed"].T if cfg["tie_word_embeddings"]
            else params["unembed"]).astype(jnp.float32)
    rows = x.reshape(-1, x.shape[-1])
    lab = labels.reshape(-1)
    n = rows.shape[0]
    c = math.gcd(n, row_chunk)

    def chunk(acc, inp):
        r, lb = inp
        lg = mth.hi("rd,dv->rv", r, head)
        lse = jax.nn.logsumexp(lg, axis=-1)
        ll = jnp.take_along_axis(lg, jnp.maximum(lb, 0)[:, None], -1)[:, 0]
        valid = lb >= 0
        return (acc[0] + jnp.sum((lse - ll) * valid),
                acc[1] + jnp.sum(valid)), None

    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(chunk), (jnp.float32(0), jnp.float32(0)),
        (rows.reshape(n // c, c, -1), lab.reshape(n // c, c)))
    return tot / jnp.maximum(cnt, 1.0)


# --------------------------------------------------------------- optimizer
def _orth(x, job, variant):
    """Newton–Schulz on one (m, n) slice, m <= n, float32 in and out."""
    a, b, c = job["ns_coeffs"]
    t = jnp.bfloat16 if variant in ("control", "ns_control") else jnp.float32

    def mm(u, w):
        return jnp.matmul(u.astype(t), w.astype(t), precision=HIGHEST,
                          preferred_element_type=t)

    x = (x / (jnp.linalg.norm(x) + 1e-7)).astype(t)
    for _ in range(job["ns_steps"]):
        s = mm(x, x.T)
        y = b * s + c * mm(s, s)
        x = (a * x + mm(y, x)).astype(t)
    return x.astype(jnp.float32)


def orthogonalize(m, job, variant, shards: int = 1):
    """(..., r, c) momentum -> orthogonalized update, per trailing slice
    on its short side; ``shards`` > 1 splits the long side into blocks
    that never see each other (the exchange left out)."""
    transpose = m.shape[-2] > m.shape[-1]
    x = jnp.swapaxes(m, -1, -2) if transpose else m
    lead, (r, c) = x.shape[:-2], x.shape[-2:]
    flat = x.reshape((-1, r, c))

    def one(s):
        if shards > 1 and c % shards == 0:
            blocks = jnp.split(s, shards, axis=-1)
            return jnp.concatenate([_orth(bk, job, variant)
                                    for bk in blocks], -1)
        return _orth(s, job, variant)

    o = jax.vmap(one)(flat).reshape(lead + (r, c))
    return jnp.swapaxes(o, -1, -2) if transpose else o


def _is_matrix(shape, job) -> bool:
    return len(shape) >= 2 and min(shape[-2:]) >= job["ns_min_side"]


# ---------------------------------------------------------------- driving
def _mesh() -> Mesh:
    return Mesh(np.array(jax.devices()), ("x",))


def _leaf_sharding(mesh, shape):
    """Shard the largest dimension that divides over every chip."""
    n = mesh.shape["x"]
    dims = sorted(range(len(shape)), key=lambda i: -shape[i])
    for i in dims:
        if shape[i] % n == 0 and shape[i] >= n:
            spec = [None] * len(shape)
            spec[i] = "x"
            return NamedSharding(mesh, P(*spec))
    return NamedSharding(mesh, P())


def follow(cfg: Dict, job: Dict, seed: int, batches: Sequence[Dict],
           variant: str = "reference", exchange_shards: int = 4
           ) -> Dict[str, Any]:
    """Run the reference from the seed's weights through ``batches``.

    Returns the loss of each step, the norm of each leaf's first
    gradient as the optimizer gets it (clipped), and the norm of each
    leaf's change after the last step."""
    assert variant in VARIANTS, variant
    shards = exchange_shards if variant == "no_exchange" else 1
    lay = layout(cfg)
    mesh = _mesh()
    sh = {p: _leaf_sharding(mesh, s) for p, s, _, _ in lay}
    rep = NamedSharding(mesh, P())
    params = weights.generate(seed, lay, weights.nest(sh))
    per_chip = sum(math.prod(s) for _, s, _, _ in lay) * 4 / mesh.size
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit", 16e9)
    on_host = 3 * per_chip > limit

    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(
            functools.partial(loss_fn, cfg, variant)))
        mom = {}
        losses, grad_norms = [], {}
        for i, batch in enumerate(batches):
            tok = jax.device_put(np.asarray(batch["tokens"]), rep)
            lab = jax.device_put(np.asarray(batch["labels"]), rep)
            loss, grads = grad_fn(params, tok, lab)
            losses.append(float(loss))
            g = weights.flatten(grads)
            flat = weights.flatten(params)
            del grads, params
            gn = float(jnp.sqrt(sum(_sq(x) for x in g.values())))
            scale = min(1.0, job["clip_norm"] / (gn + 1e-9))
            for path, shape, _, _ in lay:
                prev = mom.get(path)
                if prev is not None and on_host:
                    prev = jax.device_put(prev, sh[path])
                m_new, p_new = _leaf_update(
                    g.pop(path), prev, flat[path], scale, job, variant,
                    shards, _is_matrix(shape, job))
                if i == 0:
                    grad_norms[path] = float(_norm2(m_new))
                mom[path] = np.asarray(m_new) if on_host else m_new
                flat[path] = p_new
                del m_new, p_new
            params = weights.nest(flat)
        flat = weights.flatten(params)
        update_norms = {
            leaf[0]: float(weights.change_norm(seed, leaf, flat[leaf[0]]))
            for leaf in lay}
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": update_norms}


@jax.jit
def _sq(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


@jax.jit
def _norm2(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7),
                   donate_argnums=(1,))
def _leaf_update_jit(g, m, p, scale, job_items, variant, shards, matrix):
    job = dict(job_items)
    gc = g.astype(jnp.float32) * scale
    m = gc if m is None else job["momentum"] * m + gc
    if matrix:
        o = orthogonalize(m, job, variant, shards)
        delta = o * math.sqrt(max(1.0, p.shape[-2] / p.shape[-1]))
        new = p.astype(jnp.float32) - job["lr"] * delta
    else:
        new = p.astype(jnp.float32) - job["fallback_lr"] * jnp.sign(m)
    return m, new.astype(p.dtype)


def _leaf_update(g, m, p, scale, job, variant, shards, matrix):
    items = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                         for k, v in job.items()
                         if k in ("ns_coeffs", "ns_steps", "momentum", "lr",
                                  "fallback_lr")))
    return _leaf_update_jit(g, m, p, jnp.float32(scale), items, variant,
                            shards, matrix)

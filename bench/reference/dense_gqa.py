"""Plain reference of a dense GQA decoder served at W1A8 with an int8 KV cache.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, one layer
at a time, over a request's whole sequence (its prompt and the tokens it was
served).  It imports nothing of the program and takes nothing the program
made: the weights are made here from the seed, by the same recipe the
configuration's file states (``weights``).

The semantics it holds the program to, per layer (pre-norm residual):

* RMSNorm with unit gain;
* every projection: the input fake-quantized per token to ``act_bits``
  (min/max affine grid), times ``alpha * sign(w)`` with ``alpha`` the mean
  of ``|w|`` over the input dimension;
* RoPE (rotate-half) on q and k;
* the KV cache: one affine int8 grid per request, calibrated on its prompt's
  k (and v); later tokens are clipped to that grid;
* q quantized to ``act_bits`` on one grid over the whole prompt (all heads),
  and on its own grid for each later token;
* causal softmax in float32; the probabilities on the grid ``1/(2^bits-1)``;
* SiLU-GLU feed-forward;
* the head: the final RMSNorm times the bf16-stored table.

A token's logits at position ``t`` come from the prompt and the served
tokens before it, so prefill (the first served token) and every decode
step through the cache are covered.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BUCKET = 512  # sequences are padded to a multiple of this (few compiles)


def _keys(c: Dict, seed: int):
    """Embedding, unembedding and per-layer keys (the file's ``weights``)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    (stack,) = jax.random.split(ks[2], 1)  # no unrolled prefix layers
    (period,) = jax.random.split(stack, 1)  # a period of one block kind
    return ks[0], ks[1], jax.random.split(period, c["n_layers"])


def _binarized(key, k: int, n: int, scale: float = 1.0):
    w = jax.random.normal(key, (k, n), jnp.float32) * (scale / k**0.5)
    alpha = jnp.maximum(jnp.mean(jnp.abs(w), axis=0, keepdims=True), 1e-8)
    return jnp.where(w >= 0, alpha, -alpha)


@functools.partial(jax.jit, static_argnums=0)
def _layer_weights(dims: Tuple[int, ...], key):
    d, h, kvh, dh, ff = dims
    blk = jax.random.split(key, 6)
    ak = jax.random.split(blk[0], 6)
    fk = jax.random.split(blk[1], 3)
    return {
        "q": _binarized(ak[0], d, h * dh),
        "k": _binarized(ak[1], d, kvh * dh),
        "v": _binarized(ak[2], d, kvh * dh),
        "o": _binarized(ak[3], h * dh, d, 0.5),
        "up": _binarized(fk[0], d, ff),
        "down": _binarized(fk[1], ff, d, 0.5),
        "gate": _binarized(fk[2], d, ff),
    }


@functools.partial(jax.jit, static_argnums=(0, 1))
def _table(v: int, d: int, key):
    return (jax.random.normal(key, (v, d), jnp.float32) * 0.02).astype(jnp.bfloat16)


def _fq(x, bits: int, lo, hi):
    """Fake-quantize on the affine grid spanned by ``lo``..``hi``."""
    qmax = 2.0**bits - 1
    sc = jnp.maximum((hi - lo) / qmax, 1e-8)
    return jnp.round(jnp.clip((x - lo) / sc, 0.0, qmax)) * sc + lo


def _fq_rows(x, bits: int):
    """Per-row grid over every axis but the first."""
    axes = tuple(range(1, x.ndim))
    return _fq(x, bits, jnp.min(x, axes, keepdims=True), jnp.max(x, axes, keepdims=True))


def _prompt_minmax(x, prompt):
    """Min and max over the prompt's rows (``prompt``: (S,) bool)."""
    m = prompt.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.min(jnp.where(m, x, jnp.inf)), jnp.max(jnp.where(m, x, -jnp.inf))


def _rms(x, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta: float):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims", "theta", "eps", "bits", "kv_bits"))
def _layer(w, x, p, *, dims, theta, eps, bits, kv_bits):
    """One block over a padded sequence ``x`` (S, d) whose prompt is ``p`` long."""
    d, h, kvh, dh, ff = dims
    s = x.shape[0]
    rows = jnp.arange(s)
    prompt = rows < p

    def mm(a, b):
        return jnp.dot(a, b, precision=HI)

    a = _fq_rows(_rms(x, eps), bits)
    q = _rope(mm(a, w["q"]).reshape(s, h, dh), rows, theta)
    k = _rope(mm(a, w["k"]).reshape(s, kvh, dh), rows, theta)
    v = mm(a, w["v"]).reshape(s, kvh, dh)
    k = _fq(k, kv_bits, *_prompt_minmax(k, prompt))
    v = _fq(v, kv_bits, *_prompt_minmax(v, prompt))
    q_lo, q_hi = _prompt_minmax(q, prompt)
    row_lo = jnp.min(q, axis=(1, 2), keepdims=True)
    row_hi = jnp.max(q, axis=(1, 2), keepdims=True)
    pm = prompt[:, None, None]
    q = _fq(q, bits, jnp.where(pm, q_lo, row_lo), jnp.where(pm, q_hi, row_hi))

    qg = q.reshape(s, kvh, h // kvh, dh)
    causal = rows[None, :] <= rows[:, None]
    pmax = 2.0**bits - 1

    def group(j):
        sc = jnp.einsum("sgd,td->gst", qg[:, j], k[:, j], precision=HI) / math.sqrt(dh)
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -1e30), axis=-1)
        pr = jnp.round(pr * pmax) / pmax
        return jnp.einsum("gst,td->sgd", pr, v[:, j], precision=HI)

    ctx = jax.lax.map(group, jnp.arange(kvh))  # (kvh, S, g, dh)
    ctx = ctx.transpose(1, 0, 2, 3).reshape(s, h * dh)
    x = x + mm(_fq_rows(ctx, bits), w["o"])
    a = _fq_rows(_rms(x, eps), bits)
    m = jax.nn.silu(mm(a, w["gate"])) * mm(a, w["up"])
    return x + mm(_fq_rows(m, bits), w["down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, idx, head, *, eps):
    return jnp.dot(_rms(x[idx], eps), head.astype(jnp.float32).T, precision=HI)


def _embed(table, tokens: np.ndarray, d: int):
    n = len(tokens)
    padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
    padded[:n] = tokens
    return jnp.take(table, jnp.asarray(padded), axis=0).astype(jnp.float32) * math.sqrt(d)


def _hidden(c: Dict, seed: int, seqs, streams) -> Tuple[Dict, jax.Array]:
    """Final residual stream of each sequence for each activation width."""
    dims = (c["d_model"], c["n_heads"], c["n_kv_heads"], c["d_head"], c["d_ff"])
    emb_key, unemb_key, layer_keys = _keys(c, seed)
    table = _table(c["vocab_size"], c["d_model"], emb_key)
    inputs = [np.concatenate([np.asarray(p, np.int32), np.asarray(t[:-1], np.int32)]) for p, t in seqs]
    xs = {b: [_embed(table, tok, c["d_model"]) for tok in inputs] for b in streams}
    kw = dict(dims=dims, theta=float(c["rope_theta"]), eps=float(c["norm_eps"]), kv_bits=c["kv_cache_bits"])
    for i in range(c["n_layers"]):
        w = _layer_weights(dims, layer_keys[i])
        for b in streams:
            xs[b] = [_layer(w, x, len(p), bits=b, **kw) for x, (p, _) in zip(xs[b], seqs)]
        del w
    head = table if c["tie_embeddings"] else _table(c["vocab_size"], c["d_model"], unemb_key)
    return xs, head


def served_logits(
    c: Dict,
    seed: int,
    seqs: Sequence[Tuple[np.ndarray, Sequence[int]]],
    widths: Sequence[int] = (),
) -> Dict[int, List[np.ndarray]]:
    """Logits at every served position of ``seqs`` (prompt, served tokens).

    Row ``j`` of a sequence's (n_served, vocab) array holds the logits from
    which its served token ``j`` was chosen.  One array list per activation
    width in ``widths`` (default: the configuration's).
    """
    widths = list(widths) or [c["act_bits"]]
    xs, head = _hidden(c, seed, seqs, widths)
    out = {}
    for b in widths:
        out[b] = [
            np.asarray(_logits(x, jnp.arange(len(p) - 1, len(p) - 1 + len(t)), head, eps=float(c["norm_eps"])))
            for x, (p, t) in zip(xs[b], seqs)
        ]
    return out

"""Plain reference of a DeepSeek-V2 decoder (MLA + routed and shared experts)
served at W1A8 with an int8 latent cache.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, one layer
at a time, over a request's whole sequence (its prompt and the tokens it was
served).  It imports nothing of the program and takes nothing the program
made: the weights are made here from the seed, by the recipe the
configuration's file states (``weights``), and the routing is computed here.

The semantics it holds the program to, per layer (pre-norm residual,
``first_k_dense_replace`` dense layers, then MoE layers):

* RMSNorm with unit gain (the block's two, and the latent's ``kv_norm``);
* every projection: the input fake-quantized per token to ``act_bits``
  (min/max affine grid), times ``alpha * sign(w)`` with ``alpha`` the mean
  of ``|w|`` over the input dimension;
* attention (no q LoRA): ``q = x W_q`` split into ``q_nope`` (128) and
  ``q_rope`` (64) per head; the latent ``c = kv_norm(x W_kv_down)`` (512)
  and one rope key ``k_rope = x W_k_rope`` shared by the heads;
* RoPE (rotate-half) on ``q_rope`` and ``k_rope`` with YaRN's
  frequencies (``rope_scaling``: ``factor``, ``beta_fast``, ``beta_slow``,
  ``original_max_position_embeddings``), cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, the softmax
  scale ``(qk_nope + qk_rope)^-0.5 * mscale(factor, mscale_all_dim)^2``,
  ``mscale(s, m) = 0.1 m ln s + 1``;
* the prompt's positions, decompressed as the program prefills them:
  ``k_nope`` and ``v`` from the latent quantized per token, float scores,
  causal softmax in float32, float PV;
* every later position, absorbed as the program decodes it through its
  cache: the latent on one affine int8 grid per request, calibrated on the
  prompt's latents (later ones clipped to it); the rope key rounded to
  bf16; ``q_nope W_k_up`` quantized per token (all heads) to
  ``act_bits``; latent and rope scores, causal softmax in float32, the
  probabilities on the grid ``1/(2^act_bits-1)``, PV against the int8
  latent, then ``W_v_up``;
* the dense layer: a SiLU-GLU feed-forward;
* a MoE layer: the router ``x W_r`` in float32 from the unquantized
  normed input (fp32 weights), softmax over the experts, the top
  ``num_experts_per_tok`` by ``lax.top_k``, their probabilities as weights,
  not renormalised (``norm_topk_prob`` false, ``routed_scaling_factor``
  1); every token through all of its routed experts (dropless), each a
  SiLU-GLU of width ``moe_intermediate_size``, plus the shared experts as
  one SiLU-GLU of ``n_shared_experts`` times that width;
* the head: the final RMSNorm times the bf16-stored untied table.

Departures from the published equations, each a relabelling or the
program's serving contract: HF's checkpoint applies RoPE to interleaved
pairs and permutes them to halves first, which with random weights is a
relabelling of ``W_q``'s and ``W_k_rope``'s columns; weights are
binarized and activations quantized as above (the program's W1A8), where
the published model runs in bf16.

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


def _mm(a, b):
    return jnp.dot(a, b, precision=HI)


def _keys(c: Dict, seed: int):
    """Embedding, unembedding and per-layer keys (the file's ``weights``)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    n_dense = c["first_k_dense_replace"]
    stack = jax.random.split(ks[2], n_dense + 1)  # dense layers unrolled, then the period
    (period,) = jax.random.split(stack[-1], 1)  # a period of one block kind
    moe = jax.random.split(period, c["num_hidden_layers"] - n_dense)
    return ks[0], ks[1], list(stack[:n_dense]) + list(moe)


def _binarized(key, shape, scale: float = 1.0):
    """``alpha * sign(w)`` of ``w ~ N(0, 1) * scale / sqrt(K)``, K = ``shape[-2]``."""
    w = jax.random.normal(key, shape, jnp.float32) * (scale / shape[-2] ** 0.5)
    alpha = jnp.maximum(jnp.mean(jnp.abs(w), axis=-2, keepdims=True), 1e-8)
    return jnp.where(w >= 0, alpha, -alpha)


def _glu_weights(key, d: int, ff: int, experts: Tuple[int, ...] = ()):
    fk = jax.random.split(key, 3)
    return {
        "up": _binarized(fk[0], experts + (d, ff)),
        "down": _binarized(fk[1], experts + (ff, d), 0.5),
        "gate": _binarized(fk[2], experts + (d, ff)),
    }


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_weights(dims: Tuple[int, ...], moe: bool, key):
    d, h, r, dn, dr, dv, ff, n_exp, eff, sff = dims
    blk = jax.random.split(key, 6)
    ak = jax.random.split(blk[0], 8)
    w = {
        "q": _binarized(ak[1], (d, h * (dn + dr))),
        "kv_down": _binarized(ak[2], (d, r)),
        "k_rope": _binarized(ak[3], (d, dr)),
        "k_up": _binarized(ak[4], (r, h * dn)),
        "v_up": _binarized(ak[5], (r, h * dv)),
        "o": _binarized(ak[6], (h * dv, d), 0.5),
    }
    if not moe:
        w["ffn"] = _glu_weights(blk[1], d, ff)
        return w
    mk = jax.random.split(blk[1], 8)
    w["router"] = jax.random.normal(mk[0], (d, n_exp), jnp.float32) * 0.02
    w["experts"] = {
        "up": _binarized(mk[1], (n_exp, d, eff)),
        "gate": _binarized(mk[2], (n_exp, d, eff)),
        "down": _binarized(mk[3], (n_exp, eff, d), 0.5),
    }
    w["shared"] = _glu_weights(mk[4], d, sff)
    return w


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


def _mscale(scale: float, m: float) -> float:
    return 0.1 * m * math.log(scale) + 1.0 if scale > 1 else 1.0


def _yarn(c: Dict):
    """(inverse frequencies, cos/sin factor, softmax scale) of the config."""
    d, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    ys = c["rope_scaling"]
    i = np.arange(d // 2, dtype=np.float64)
    extra = base ** (-2.0 * i / d)
    n = ys["original_max_position_embeddings"]

    def dim_of(rotations):
        return d * math.log(n / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(ys["beta_fast"])), 0)
    high = min(math.ceil(dim_of(ys["beta_slow"])), d - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = extra / ys["factor"] * ramp + extra * (1.0 - ramp)
    cos_sin = _mscale(ys["factor"], ys["mscale"]) / _mscale(ys["factor"], ys["mscale_all_dim"])
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    scale = qk**-0.5 * _mscale(ys["factor"], ys["mscale_all_dim"]) ** 2
    return tuple(inv_freq.astype(np.float32).tolist()), cos_sin, scale


def _rope(x, pos, inv_freq, cos_sin: float):
    """Rotate-half RoPE of ``x`` (S, [H,] dr) at positions ``pos`` (S,)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq, jnp.float32)
    if x.ndim == 3:
        ang = ang[:, None]
    cos, sin = jnp.cos(ang) * cos_sin, jnp.sin(ang) * cos_sin
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _glu(w, a, bits: int):
    """SiLU-GLU of an input already on its activation grid."""
    m = jax.nn.silu(_mm(a, w["gate"])) * _mm(a, w["up"])
    return _mm(_fq_rows(m, bits), w["down"])


def _attention(w, x, p, *, dims, yarn, eps, bits, kv_bits):
    """MLA over a padded sequence ``x`` (S, d) whose prompt is ``p`` long:
    decompressed at prompt rows, absorbed over the int8 latent after."""
    d, h, r, dn, dr, dv = dims[:6]
    inv_freq, cos_sin, scale = yarn
    s = x.shape[0]
    rows = jnp.arange(s)
    prompt = rows < p
    causal = rows[None, :] <= rows[:, None]

    a = _fq_rows(_rms(x, eps), bits)
    q = _mm(a, w["q"]).reshape(s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], rows, inv_freq, cos_sin)
    lat = _rms(_mm(a, w["kv_down"]), eps)  # (S, r)
    k_rope = _rope(_mm(a, w["k_rope"]), rows, inv_freq, cos_sin)  # (S, dr)
    w_uk = w["k_up"].reshape(r, h, dn)
    w_uv = w["v_up"].reshape(r, h, dv)

    # prompt rows: decompressed, float scores
    lat8 = _fq_rows(lat, bits)
    k_nope = jnp.einsum("sr,rhd->shd", lat8, w_uk, precision=HI)
    v = jnp.einsum("sr,rhd->shd", lat8, w_uv, precision=HI)
    sc = jnp.einsum("shd,thd->hst", q_nope, k_nope, precision=HI)
    sc = (sc + jnp.einsum("shd,td->hst", q_rope, k_rope, precision=HI)) * scale
    pr = jax.nn.softmax(jnp.where(causal[None], sc, -1e30), axis=-1)
    ctx_prefill = jnp.einsum("hst,thd->shd", pr, v, precision=HI)

    # later rows: absorbed over the cache
    lat_c = _fq(lat, kv_bits, *_prompt_minmax(lat, prompt))
    rope_c = k_rope.astype(jnp.bfloat16).astype(jnp.float32)
    q_abs = _fq_rows(jnp.einsum("shd,rhd->shr", q_nope, w_uk, precision=HI), bits)
    sc = jnp.einsum("shr,tr->hst", q_abs, lat_c, precision=HI)
    sc = (sc + jnp.einsum("shd,td->hst", q_rope, rope_c, precision=HI)) * scale
    pr = jax.nn.softmax(jnp.where(causal[None], sc, -1e30), axis=-1)
    pmax = 2.0**bits - 1
    pr = jnp.round(pr * pmax) / pmax
    ctx_lat = jnp.einsum("hst,tr->shr", pr, lat_c, precision=HI)
    ctx_decode = jnp.einsum("shr,rhd->shd", ctx_lat, w_uv, precision=HI)

    ctx = jnp.where(prompt[:, None, None], ctx_prefill, ctx_decode).reshape(s, h * dv)
    return _mm(_fq_rows(ctx, bits), w["o"])


def _moe(w, hn, *, top_k: int, bits: int):
    """Routed experts (every token through its top-k) plus the shared ones."""
    a = _fq_rows(hn, bits)
    probs = jax.nn.softmax(_mm(hn, w["router"]), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, top_k)
    coef = jnp.zeros_like(probs).at[jnp.arange(hn.shape[0])[:, None], top_i].set(top_w)

    def one(acc, ex):
        we, ce = ex
        return acc + ce[:, None] * _glu(we, a, bits), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(hn), (w["experts"], coef.T))
    return routed + _glu(w["shared"], a, bits)


@functools.partial(jax.jit, static_argnames=("dims", "yarn", "eps", "bits", "kv_bits", "top_k"))
def _layer(w, x, p, *, dims, yarn, eps, bits, kv_bits, top_k):
    """One block over a padded sequence ``x`` (S, d) whose prompt is ``p`` long."""
    x = x + _attention(w, x, p, dims=dims, yarn=yarn, eps=eps, bits=bits, kv_bits=kv_bits)
    hn = _rms(x, eps)
    if "experts" in w:
        return x + _moe(w, hn, top_k=top_k, bits=bits)
    return x + _glu(w["ffn"], _fq_rows(hn, bits), bits)


@functools.partial(jax.jit, static_argnames=("eps",))
def _logits(x, idx, head, *, eps):
    return jnp.dot(_rms(x[idx], eps), head.astype(jnp.float32).T, precision=HI)


def _embed(table, tokens: np.ndarray, d: int):
    n = len(tokens)
    padded = np.zeros(-(-n // BUCKET) * BUCKET, np.int32)
    padded[:n] = tokens
    return jnp.take(table, jnp.asarray(padded), axis=0).astype(jnp.float32) * math.sqrt(d)


def _dims(c: Dict) -> Tuple[int, ...]:
    return (
        c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
        c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
        c["intermediate_size"], c["n_routed_experts"], c["moe_intermediate_size"],
        c["n_shared_experts"] * c["moe_intermediate_size"],
    )


def _hidden(c: Dict, seed: int, seqs, streams) -> Tuple[Dict, jax.Array]:
    """Final residual stream of each sequence for each activation width."""
    dims = _dims(c)
    d = c["hidden_size"]
    emb_key, unemb_key, layer_keys = _keys(c, seed)
    table = _table(c["vocab_size"], d, emb_key)
    inputs = [np.concatenate([np.asarray(p, np.int32), np.asarray(t[:-1], np.int32)]) for p, t in seqs]
    xs = {b: [_embed(table, tok, d) for tok in inputs] for b in streams}
    kw = dict(dims=dims, yarn=_yarn(c), eps=float(c["rms_norm_eps"]),
              kv_bits=c["kv_cache_bits"], top_k=c["num_experts_per_tok"])
    for i, key in enumerate(layer_keys):
        w = _layer_weights(dims, i >= c["first_k_dense_replace"], key)
        for b in streams:
            xs[b] = [_layer(w, x, len(p), bits=b, **kw) for x, (p, _) in zip(xs[b], seqs)]
        del w
    head = _table(c["vocab_size"], d, unemb_key)
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
            np.asarray(_logits(x, jnp.arange(len(p) - 1, len(p) - 1 + len(t)), head, eps=float(c["rms_norm_eps"])))
            for x, (p, t) in zip(xs[b], seqs)
        ]
    return out

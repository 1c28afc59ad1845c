"""Mixture-of-Experts FFN (deepseek-style: shared + routed top-k) — quant-aware.

The router stays full precision: fp32 logits at ``Precision.HIGHEST``,
softmax (or sigmoid, deepseek-v3) scores, ``lax.top_k``; it is tiny and
accuracy-critical, the same rationale as the paper's FP softmax.  Routed
and shared experts are binary-weight QMMs.

Dispatch depends on the mode:

* ``train`` and ``float``: capacity-based scatter/gather (GShard lineage).
  Tokens are sorted by expert and placed in each expert's capacity buffer,
  and the expert MMs run as one stacked batched matmul
  ``(E, C, D) x (E, D, F)``, the form that shards cleanly (experts over the
  ``model`` axis, capacity over ``data``).  A token past an expert's
  capacity (``capacity_factor`` sizes the buffer) is dropped there.
* ``serve``: dropless.  Every token gets all of its top-k experts, in
  prefill and in decode, and its output depends on no other token.  At
  decode's few tokens on a TPU (:func:`expert_kernel_engages`) the rows
  routed to each expert are grouped into tiles and multiplied by that
  expert's packed words, unpacked in VMEM
  (``kernels.binary_qmm.expert_decode_qmm``, from :func:`expert_qlinear`):
  only the experts the step's routing selects are read, in place in the
  stack of every layer's words that the scan over layers leaves whole
  (:func:`hold_expert_words`).  Otherwise (a
  prompt's many tokens, or off the TPU) a scan over the experts runs each
  expert's FFN over all the tokens through ``layers.qlinear`` and keeps
  each token's rows of its own experts: no ``E x T x d`` buffer, and one
  expert's weights unpacked at a time.

Device work runs under ``jax.named_scope``: ``moe.router``,
``moe.dispatch`` (the tile layout), ``moe.experts``, ``moe.combine``; the
shared experts are the QMM sites ``moe.shared.{gate,up,down}`` and the
routed ones ``moe.experts.{gate,up,down}`` in the site log.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, QuantConfig
from repro.core import flow_abstraction as FA
from repro.core import qmm as QE
from repro.core import quantization as Q
from repro.core import site_log
from repro.kernels import ops
from repro.models import layers as L

__all__ = [
    "init_moe",
    "moe_ffn",
    "expert_qlinear",
    "expert_kernel_engages",
    "hold_expert_words",
    "lend_expert_words",
    "pack_experts_for_serving",
]


# ---------------------------------------------------------------------------
# stacked expert linear (E, K, N)
# ---------------------------------------------------------------------------


def init_experts(key, n_experts: int, d_in: int, d_out: int, scale: float = 1.0):
    std = scale / (d_in**0.5)
    return {"w": jax.random.normal(key, (n_experts, d_in, d_out), jnp.float32) * std}


#: Sublanes of a TPU tile of 32-bit words: the padding of an expert's K/32.
_WORD_ROWS = 8


def pack_experts_for_serving(p: dict, quant: QuantConfig) -> dict:
    if not quant.enabled:
        return {"w": p["w"].astype(jnp.bfloat16)}
    wq = Q.binarize_weight(p["w"])  # scale per (E, 1, N)
    colsum = FA.weight_corrections(wq)  # (E, N)
    packed = wq.pack(axis=1)
    # K/32 padded with zero words to a multiple of 8 (deepseek-v2-lite's down,
    # 44 -> 48): else the TPU lays the stack out expert-minor to save its
    # tile padding, and every decode step copies it whole to the row-major
    # layout the grouped kernel reads.  Zero words against the activations'
    # zero padding add nothing; the unpack path slices K back.
    kw = packed.mantissa.shape[1]
    words = jnp.pad(packed.mantissa, ((0, 0), (0, -kw % _WORD_ROWS), (0, 0)))
    return {
        "w_packed": words,  # uint32 (E, K/32 to a multiple of 8, N)
        "w_scale": packed.scale.astype(jnp.float32),
        "w_offset": packed.offset.astype(jnp.float32),
        "w_colsum": colsum.astype(jnp.int32),
    }


def expert_kernel_engages(n_tokens: int, quant: QuantConfig) -> bool:
    """Does serving run the routed experts of a step of ``n_tokens`` tokens in
    the grouped decode kernel?  Yes for packed 1-bit experts at up to
    ``PACKED_CORE_MAX_ROWS`` tokens on a TPU: decode's slots.  Prompts (89
    tokens and more in every benchmark cell) take the scan over experts, and
    off the TPU, where the kernel would only be interpreted, every step does.
    """
    return (
        quant.enabled
        and quant.weight_bits == 1
        and n_tokens <= QE.PACKED_CORE_MAX_ROWS
        and ops.on_tpu()
    )


def expert_qlinear(
    p: dict,
    x: jax.Array,
    quant: QuantConfig,
    mode: str,
    k: int,
    tiles=None,
    name: str = "",
):
    """``x @ W[e]`` per expert, in the execution mode.

    ``train``/``float``: ``x (E, C, K)`` capacity buffers against the
    stacked ``W (E, K, N)``.

    ``serve``: ``x (R, K)`` routed rows, row ``r`` against the expert that
    ``tiles`` (``kernels.ops.expert_tiles`` of the rows' experts) gives it,
    in the grouped decode kernel over the packed words: one layer's ``(E,
    K/32, N)``, or every layer's with ``p["layer"]`` (:func:`lend_expert_words`).
    Each row is quantized on its own grid (per token), so a token's result
    never depends on which tokens share the step; the epilogue is the flow
    abstraction's, with the row's expert's weight affine and colsum.
    """
    if mode == "float" or not quant.enabled:
        return jnp.einsum("eck,ekn->ecn", x, p["w"].astype(x.dtype))
    if mode == "train":
        if quant.prebinarize_gather:
            w_hat = p["w"]  # pre-binarized via the packed-gather STE
        else:
            w_hat = Q.fake_binarize_weight(p["w"])  # (E,K,N), scales (E,1,N)
        x_hat = Q.fake_quant(x, quant.act_bits)
        return jnp.einsum("eck,ekn->ecn", x_hat, w_hat.astype(x.dtype))

    bits = quant.act_bits
    xq = Q.quantize_activation(x.astype(jnp.float32), bits, per_channel_axis=0)
    if site_log.is_recording():
        site_log.record(
            kind="qlinear",
            site=name,
            bits=bits,
            cfg_bits=quant.act_bits,
            mantissa_dtype=str(xq.mantissa.dtype),
            backend="mxu",
            int_core="packed",
        )
    e = tiles.routed_expert
    wq = Q.QuantTensor(
        mantissa=p["w_packed"],
        scale=p["w_scale"][e, 0],  # (R, N): each row's expert
        offset=p["w_offset"][e, 0],
        bits=1,
        packed=True,
        packed_axis=-2,
        length=k,
    )
    layer = p.get("layer")
    out = FA.qmm_flow(
        xq,
        wq,
        w_colsum=p["w_colsum"][e],
        packed_int_matmul=lambda a, w: ops.expert_decode_qmm_int(a, tiles, w, layer),
    )
    return out.astype(x.dtype)


_ROUTED = ("gate", "up", "down")


def hold_expert_words(period: list) -> Tuple[list, list]:
    """Take each MoE block's routed-expert words, ``(L, E, K/32, N)``, out of
    the serving stack's period params before the scan over layers slices
    them: ``(period without them, [words by linear, or None, per block])``.

    Sliced, a layer's ``(E, K/32, N)`` words would be copied whole, every
    expert, before the grouped kernel picks the routed ones; held, the
    kernel reads them in the stack (:func:`lend_expert_words`).
    """
    scanned, held = [], []
    for blk in period:
        moe = blk.get("moe") if isinstance(blk, dict) else None
        if moe is None or "w_packed" not in moe["gate"]:
            scanned.append(blk)
            held.append(None)
            continue
        words = {m: moe[m]["w_packed"] for m in _ROUTED}
        rest = {m: {n: v for n, v in moe[m].items() if n != "w_packed"} for m in _ROUTED}
        scanned.append({**blk, "moe": {**moe, **rest}})
        held.append(words)
    return scanned, held


def lend_expert_words(blk: dict, words: dict, layer: jax.Array) -> dict:
    """One scan step's block params with the held stack of words back in
    place, and the step's ``layer`` to read of it."""
    moe = dict(blk["moe"])
    for m in _ROUTED:
        moe[m] = {**moe[m], "w_packed": words[m], "layer": layer}
    return {**blk, "moe": moe}


# ---------------------------------------------------------------------------
# the MoE block
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ArchConfig) -> dict:
    e = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    p = {
        "router": {"w": jax.random.normal(ks[0], (d, e.n_routed), jnp.float32) * 0.02},
        "up": init_experts(ks[1], e.n_routed, d, e.d_expert_ff),
        "gate": init_experts(ks[2], e.n_routed, d, e.d_expert_ff),
        "down": init_experts(ks[3], e.n_routed, e.d_expert_ff, d, scale=0.5),
    }
    if e.n_shared:
        p["shared"] = L.init_ffn(ks[4], cfg.ffn_type, d, e.shared_ff)
    return p


def _route(logits: jax.Array, e, top_k: int):
    """Router scores -> (weights (T, k), experts (T, k)). fp32 throughout."""
    if e.router_scoring == "sigmoid":  # deepseek-v3
        scores = jax.nn.sigmoid(logits)
        w, idx = jax.lax.top_k(scores, top_k)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * e.route_scale
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(scores, top_k)
    return w, idx


def moe_ffn(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    mode: str,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux_load_balance_loss scalar)."""
    e = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    with jax.named_scope("moe.router"):
        logits = jnp.einsum(
            "td,de->te",
            xf.astype(jnp.float32),
            p["router"]["w"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        weights, experts = _route(logits, e, e.top_k)  # (T, k)
        # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
        probs_mean = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)  # (E,)
        counts = jnp.zeros((e.n_routed,), jnp.float32).at[experts.reshape(-1)].add(1.0)
        frac = counts / jnp.float32(t * e.top_k)
        aux = jnp.float32(e.n_routed) * jnp.sum(frac * probs_mean)

    if mode == "serve":
        combined = _routed_dropless(p, xf, weights, experts, cfg)
    else:
        combined = _routed_capacity(p, xf, weights, experts, cfg, mode)

    # --- shared experts (dense FFN, also binary) ---
    if "shared" in p:
        combined = combined + L.ffn(
            p["shared"], xf, cfg.ffn_type, cfg.quant, mode, name="moe.shared"
        )

    return combined.reshape(b, s, d), aux


def _routed_dropless(p, xf, weights, experts, cfg: ArchConfig):
    """Serving: every token through all of its top-k experts; (T, D)."""
    e, quant = cfg.moe, cfg.quant
    t, d = xf.shape
    ff = e.d_expert_ff
    if expert_kernel_engages(t, quant):
        with jax.named_scope("moe.dispatch"):
            tiles = ops.expert_tiles(experts.reshape(-1), e.n_routed)
            rows = jnp.repeat(xf, e.top_k, axis=0)  # row r: token r // k
        with jax.named_scope("moe.experts"):
            gate = expert_qlinear(p["gate"], rows, quant, "serve", d, tiles, "moe.experts.gate")
            up = expert_qlinear(p["up"], rows, quant, "serve", d, tiles, "moe.experts.up")
            h = jax.nn.silu(gate.astype(jnp.float32)).astype(xf.dtype) * up
            out = expert_qlinear(p["down"], h, quant, "serve", ff, tiles, "moe.experts.down")
            out = out.reshape(t, e.top_k, d)
    else:
        with jax.named_scope("moe.experts"):
            out = _experts_scan(p, xf, experts, cfg)
    with jax.named_scope("moe.combine"):
        y = jnp.sum(weights[..., None] * out.astype(jnp.float32), axis=1)
        return y.astype(xf.dtype)


def _experts_scan(p, xf, experts, cfg: ArchConfig) -> jax.Array:
    """Each expert's FFN over every token, one expert a step; a token keeps
    the rows of its own top-k experts.  Returns (T, k, D)."""
    e, quant = cfg.moe, cfg.quant
    t, d = xf.shape

    def one(out, step):
        i, pe = step
        gate = L.qlinear(pe["gate"], xf, quant, "serve", name="moe.experts.gate")
        up = L.qlinear(pe["up"], xf, quant, "serve", name="moe.experts.up")
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(xf.dtype) * up
        y = L.qlinear(pe["down"], h, quant, "serve", name="moe.experts.down")
        return jnp.where((experts == i)[..., None], y[:, None, :], out), None

    stacked = {m: _layer_words(p[m]) for m in _ROUTED}
    out0 = jnp.zeros((t, e.top_k, d), xf.dtype)
    out, _ = jax.lax.scan(one, out0, (jnp.arange(e.n_routed), stacked))
    return out


def _layer_words(p: dict) -> dict:
    """An expert linear's params of one layer (the held stack's slice)."""
    if "layer" not in p:
        return p
    p = dict(p)
    p["w_packed"] = jax.lax.dynamic_index_in_dim(p["w_packed"], p.pop("layer"), keepdims=False)
    return p


def _routed_capacity(p, xf, weights, experts, cfg: ArchConfig, mode: str):
    """Training and float: capacity dispatch over stacked buffers; (T, D)."""
    e, quant = cfg.moe, cfg.quant
    t, d = xf.shape
    tk = t * e.top_k
    capacity = int(max(1, round(e.capacity_factor * tk / e.n_routed)))
    flat_expert = experts.reshape(tk)
    flat_weight = weights.reshape(tk).astype(jnp.float32)
    flat_token = jnp.repeat(jnp.arange(t), e.top_k)

    order = jnp.argsort(flat_expert)
    se = flat_expert[order]
    st = flat_token[order]
    sw = flat_weight[order].astype(xf.dtype)  # combine weights ride in bf16
    first = jnp.searchsorted(se, se, side="left")
    pos = jnp.arange(tk) - first  # position within expert group
    keep = pos < capacity
    dest = jnp.where(keep, se * capacity + pos, e.n_routed * capacity)  # drop slot

    # gather tokens into (E*C [+1 drop], D)
    buf = jnp.zeros((e.n_routed * capacity + 1, d), xf.dtype)
    buf = buf.at[dest].set(xf[st].astype(xf.dtype))
    h_in = buf[: e.n_routed * capacity].reshape(e.n_routed, capacity, d)

    # --- stacked expert FFN (binary QMMs) ---
    up = expert_qlinear(p["up"], h_in, quant, mode, d)
    gate = expert_qlinear(p["gate"], h_in, quant, mode, d)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(xf.dtype) * up
    out_e = expert_qlinear(p["down"], h, quant, mode, e.d_expert_ff)

    # --- combine ---
    out_flat = out_e.reshape(e.n_routed * capacity, d)
    out_flat = jnp.concatenate([out_flat, jnp.zeros((1, d), xf.dtype)], axis=0)
    gathered = out_flat[dest] * sw[:, None]  # dropped -> slot E*C
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    return jnp.zeros((t, d), xf.dtype).at[st].add(gathered)

"""Attention mixers: GQA (global/local), MLA, cross-attention — quant-aware.

BETA-specific parts:

* In ``serve`` mode the two attention matmuls (QK^T and PV) run as
  **activation x activation QMMs** through the flow abstraction — the QMM
  type the paper highlights as unsupported by prior accelerators (§II).
  Softmax stays full-precision (paper keeps non-linear ops FP).
* The KV cache is stored **quantized** (int8 mantissa + affine), so the
  decode-time memory roofline term shrinks ~2x vs bf16 (and the cache *is*
  the right operand of the act x act QMM — no dequantization pass).
* Scales: Q/K per-tensor; K-cache per-token scales would also factor through
  the flow abstraction (per-column of K^T), but per-tensor is within test
  tolerance and keeps the epilogue rank-1; V per-tensor (per-reduction-dim
  scales do not factor out of an integer MM — DESIGN.md §7).

Layouts: activations ``(B, S, D)``; q ``(B, S, H, dh)``; caches
``(B, T, kvH, dh)``; decode processes ``S = 1`` with positions from the
cache cursor.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, QuantConfig
from repro.core import backend_registry, packing
from repro.core import flow_abstraction as FA
from repro.core import quantization as Q
from repro.core import site_log
from repro.kernels import decode_attn as K_da
from repro.kernels import ops as K_ops
from repro.models import layers as L

__all__ = [
    "init_attention",
    "attention",
    "init_kv_cache",
    "init_mla",
    "mla_attention",
    "init_mla_cache",
    "decode_core_engages",
]

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig, cross: bool = False) -> dict:
    h, kvh, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.d_head, cfg.d_model
    ks = jax.random.split(key, 6)
    p = {
        "q": L.init_linear(ks[0], d, h * dh),
        "k": L.init_linear(ks[1], d, kvh * dh),
        "v": L.init_linear(ks[2], d, kvh * dh),
        "o": L.init_linear(ks[3], h * dh, d, scale=0.5),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((dh,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# KV cache (quantized)
# ---------------------------------------------------------------------------


def init_kv_cache(
    batch: int, max_len: int, cfg: ArchConfig, kind: str = "g", dtype=jnp.bfloat16
) -> dict:
    """KV cache with PER-ROW serving state.

    ``pos`` and the calibration affines are shape ``(batch,)``: each batch
    row (a serving *slot*) carries its own cursor and quantization grid, so
    a packed decode batch may hold requests at different sequence positions
    (continuous batching) and a slot prefilled alone is bit-identical to the
    same request served in a full batch.
    """
    kvh, dh = cfg.n_kv_heads, cfg.d_head
    q = cfg.quant
    if kind == "l" and cfg.window_size:
        # ring buffer: local layers never need more than window_size slots
        max_len = min(max_len, cfg.window_size)
    if q.enabled and q.kv_cache_bits in (4, 8):
        if _binary_scores_site(q, "attn.qk") is not None:
            # Bitwise attention engaged: K rows are stored as PACKED 1-bit
            # planes (uint32, dh bits little-endian along the last axis) —
            # the ~8-16x KV memory shrink vs int8/bf16.  V stays int8 (the
            # PV act x act QMM is unchanged).
            dw = packing.packed_len(dh, 1)
            return {
                "k": jnp.zeros((batch, max_len, kvh, dw), jnp.uint32),
                "v": jnp.zeros((batch, max_len, kvh, dh), jnp.int8),
                "k_scale": jnp.ones((batch,), jnp.float32),
                "k_offset": jnp.zeros((batch,), jnp.float32),
                "v_scale": jnp.ones((batch,), jnp.float32),
                "v_offset": jnp.zeros((batch,), jnp.float32),
                "pos": jnp.zeros((batch,), jnp.int32),
            }
        return {
            "k": jnp.zeros((batch, max_len, kvh, dh), jnp.int8),
            "v": jnp.zeros((batch, max_len, kvh, dh), jnp.int8),
            "k_scale": jnp.ones((batch,), jnp.float32),
            "k_offset": jnp.zeros((batch,), jnp.float32),
            "v_scale": jnp.ones((batch,), jnp.float32),
            "v_offset": jnp.zeros((batch,), jnp.float32),
            "pos": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, max_len, kvh, dh), dtype),
        "v": jnp.zeros((batch, max_len, kvh, dh), dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def _cache_quantized(cache: dict) -> bool:
    return cache is not None and "k_scale" in cache


def _per_row(s, ndim: int):
    """Broadcast a per-row ``(B,)`` cache affine against a ``(B, ...)``
    operand of rank ``ndim`` (legacy scalar values pass through)."""
    s = jnp.asarray(s)
    if s.ndim == 0:
        return s
    return s.reshape(s.shape + (1,) * (ndim - 1))


def _calibrate_rows(x: jax.Array):
    """Per-row affine calibration: min/offset and (max-min)/255 scale reduced
    over every axis but the batch row — co-batched requests never share a
    quantization grid (the batch-invariance contract)."""
    x32 = x.astype(jnp.float32).reshape(x.shape[0], -1)
    off = jnp.min(x32, axis=-1)
    sc = jnp.maximum((jnp.max(x32, axis=-1) - off) / 255.0, 1e-8)
    return sc, off


def _quantize_to_cache(x: jax.Array, scale, offset) -> jax.Array:
    """Quantize with a FIXED affine (prefill-calibrated), re-centered int8."""
    scale = _per_row(scale, x.ndim)
    offset = _per_row(offset, x.ndim)
    q = jnp.clip(jnp.round((x.astype(jnp.float32) - offset) / scale), 0.0, 255.0)
    return (q - 128.0).astype(jnp.int8)


def _dequantize_from_cache(m: jax.Array, scale, offset, dtype):
    scale = _per_row(scale, m.ndim)
    offset = _per_row(offset, m.ndim)
    return ((m.astype(jnp.float32) + 128.0) * scale + offset).astype(dtype)


# ---------------------------------------------------------------------------
# bitwise attention (Bitformer scores via the scores backend family)
# ---------------------------------------------------------------------------


def _binary_scores_site(quant: QuantConfig, site: str) -> Optional[str]:
    """The scores-only backend configured for ``site``, or None.

    A scores-only name ("binary", "float") engages the bitwise attention
    path at that site; "auto" and qmm-family names leave the int8 path
    untouched — binarizing K is a precision choice, so it is strictly
    opt-in via ``backend_overrides={"attn.qk": "binary"}``.
    """
    if not (quant.enabled and quant.quantize_attention):
        return None
    name = quant.backend_for(site)
    if name == "auto":
        return None
    try:
        spec = backend_registry.get_backend(name)
    except ValueError:
        return None
    if "scores" in spec.families and "qmm" not in spec.families:
        return name
    return None


def _scores_core(site_backend: str) -> str:
    """Map a site override to the integer-core backend name.

    "binary" is the family engagement: its core is autotuned ("auto" over
    the scores candidates — binary vs mxu-int vs float, all bit-exact, so
    the verdict is pure speed).  Any other scores-only name pins its own
    core — "float" is the differential oracle's deterministic compute path.
    """
    return "auto" if site_backend == "binary" else site_backend


def _cache_binary(cache: Optional[dict]) -> bool:
    """Does this cache hold packed binary K planes (uint32 rows)?"""
    return cache is not None and "k" in cache and cache["k"].dtype == jnp.uint32


def _binarize_rows(x: jax.Array) -> Q.QuantTensor:
    """Per-row elastic binarization (BiT): the engine's 1-bit activation
    grid, min/max reduced over every axis but the batch row — co-batched
    requests never share a binarization grid (batch invariance)."""
    return Q.quantize_activation(x.astype(jnp.float32), 1, per_channel_axis=0)


def _binarize_to_cache(k: jax.Array, scale, offset) -> jax.Array:
    """Binarize with a FIXED affine (prefill-calibrated) and pack: the
    decode-time analogue of ``_quantize_to_cache`` for packed binary rows."""
    scale = _per_row(scale, k.ndim)
    offset = _per_row(offset, k.ndim)
    bit = jnp.clip(jnp.round((k.astype(jnp.float32) - offset) / scale), 0.0, 1.0)
    return packing.pack_bits(bit.astype(jnp.uint32), 1, axis=-1)


def _pack_q_heads(bits: jax.Array) -> jax.Array:
    """(B, S, H, dh) {0,1} mantissas -> (B, H, S, dw) packed uint32 planes
    (the scores-core operand layout)."""
    planes = packing.pack_bits(bits.astype(jnp.uint32), 1, axis=-1)
    return planes.transpose(0, 2, 1, 3)


def _plane_popcounts(planes: jax.Array) -> jax.Array:
    """Per-row bit totals straight off packed planes — exact (tail bits are
    zero by packing) and cheaper than unpacking just to sum."""
    return jnp.sum(
        jax.lax.population_count(planes).astype(jnp.int32), axis=-1
    ).astype(jnp.float32)


def _scores_binary(q, k_planes_t, k_scale, k_offset, dh: int, site: str, backend: str):
    """Bitwise QK^T: elastic 1-bit Q against packed binary K planes.

    AND-popcount counts from the dispatched scores core, then the affine
    epilogue back to the real-valued score domain (the algebra is in
    ``kernels.binary_attn``):

        scores = aq*ak*counts + aq*gk*rowsum(qb) + gq*ak*colsum(kb) + gq*gk*dh

    q: (B,S,H,dh) float.  k_planes_t: (B,kvH,T,dw) packed key bits.
    k_scale/k_offset: (B,) binarization affine of the cached keys (qmax=1
    grid — NO re-centering shift, unlike the int8 cache epilogue).
    """
    b, s, h, _ = q.shape
    g = h // k_planes_t.shape[1]
    qq = _binarize_rows(q)
    if site_log.is_recording():
        site_log.record(
            kind="attn",
            site=site,
            bits=1,
            mantissa_dtype=str(qq.mantissa.dtype),
            backend=backend,
            int_core="xla",
        )
    q_planes = _pack_q_heads(qq.mantissa)  # (B,H,S,dw)
    counts = K_ops.binary_attn_scores(
        q_planes, k_planes_t, dh=dh, backend=_scores_core(backend)
    ).astype(jnp.float32)
    row = _plane_popcounts(q_planes)[..., None]  # (B,H,S,1)
    col = jnp.repeat(_plane_popcounts(k_planes_t), g, axis=1)[:, :, None, :]
    a1 = jnp.reshape(qq.scale, (b, 1, 1, 1))
    g1 = jnp.reshape(qq.offset, (b, 1, 1, 1))
    a2 = _per_row(k_scale, 4)
    g2 = _per_row(k_offset, 4)
    return counts * (a1 * a2) + (a1 * g2) * row + (g1 * a2) * col + g1 * g2 * dh


def _scores_binary_latent(q_abs, ckv_m, ckv_scale, ckv_offset, site: str, backend: str):
    """Bitwise absorbed-MLA scores against the int8 latent cache.

    The latent cache layout is UNCHANGED (int8 also feeds the PV QMM), so
    the key side re-binarizes each int8 mantissa at its grid midpoint:
    ``bit = (m >= 0)`` — per-element and deterministic, hence stale-free
    and batch-invariant — with the induced affine ``ak = 128*sc``,
    ``gk = off + 64*sc``.  The packed-cache memory win is GQA-only; this
    path buys the bitwise O(n^2) score core.  Returns (B,H,S,T).
    """
    b, s, h, r = q_abs.shape
    qq = _binarize_rows(q_abs)
    if site_log.is_recording():
        site_log.record(
            kind="attn",
            site=site,
            bits=1,
            mantissa_dtype=str(qq.mantissa.dtype),
            backend=backend,
            int_core="xla",
        )
    q_planes = _pack_q_heads(qq.mantissa)  # (B,H,S,rw)
    k_bits = (ckv_m >= 0).astype(jnp.uint32)
    k_planes = packing.pack_bits(k_bits, 1, axis=-1)[:, None]  # (B,1,T,rw)
    counts = K_ops.binary_attn_scores(
        q_planes, k_planes, dh=r, backend=_scores_core(backend)
    ).astype(jnp.float32)
    row = _plane_popcounts(q_planes)[..., None]  # (B,H,S,1)
    col = _plane_popcounts(k_planes)[:, :, None, :]  # (B,1,1,T)
    sc = jnp.asarray(ckv_scale, jnp.float32)
    off = jnp.asarray(ckv_offset, jnp.float32)
    a1 = jnp.reshape(qq.scale, (b, 1, 1, 1))
    g1 = jnp.reshape(qq.offset, (b, 1, 1, 1))
    a2 = _per_row(128.0 * sc, 4)
    g2 = _per_row(off + 64.0 * sc, 4)
    return counts * (a1 * a2) + (a1 * g2) * row + (g1 * a2) * col + g1 * g2 * r


# ---------------------------------------------------------------------------
# core attention math
# ---------------------------------------------------------------------------


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def _merge_heads(x):
    return x.reshape(*x.shape[:-2], -1)


def _gqa_expand(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, T, kvH, dh) -> (B, T, H, dh) by repeating groups.

    Kept only for reference/tests — the attention paths use the GROUPED
    einsums below, which never materialize (or all-gather) the expanded
    KV: repeating a model-sharded head axis forced XLA to gather the whole
    cache every step (the §Perf gemma3-decode baseline pathology)."""
    b, t, kvh, dh = k.shape
    if kvh == n_heads:
        return k
    rep = n_heads // kvh
    return jnp.repeat(k, rep, axis=2)


def _mask(
    s_q: int,
    s_k: int,
    q_start,
    causal: bool,
    window: int,
) -> jax.Array:
    """(s_q, s_k) additive mask. q_start: absolute position of query row 0."""
    qi = q_start + jnp.arange(s_q)[:, None]
    kj = jnp.arange(s_k)[None, :]
    ok = jnp.ones((s_q, s_k), bool)
    if causal:
        ok &= kj <= qi
    if window:
        ok &= kj > qi - window
    return jnp.where(ok, 0.0, _NEG_INF).astype(jnp.float32)


def _scores_float(q, k, dtype=jnp.float32):
    """Grouped GQA scores: q (B,S,H,dh) x k (B,T,kvH,dh) -> (B,H,S,T).

    q heads are reshaped (kvH, group) so the contraction runs against the
    UN-expanded k — kv heads stay sharded, no repeat, no gather.  Head
    ordering matches jnp.repeat semantics (head h -> kv h // group)."""
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, dh)
    out = jnp.einsum("bskgd,btkd->bkgst", qg.astype(dtype), k.astype(dtype))
    return out.reshape(b, h, s, k.shape[1])


def _pv_float(probs, v, out_dtype):
    """Grouped GQA context: probs (B,H,S,T) x v (B,T,kvH,dh) -> (B,S,H,dh)."""
    b, h, s, t = probs.shape
    kvh = v.shape[2]
    g = h // kvh
    pg = probs.reshape(b, kvh, g, s, t)
    ctx = jnp.einsum("bkgst,btkd->bskgd", pg.astype(out_dtype), v.astype(out_dtype))
    return ctx.reshape(b, s, h, v.shape[3])


def _int_einsum(spec: str, a: jax.Array, b: jax.Array) -> jax.Array:
    """int8 x int8 einsum with int32 accumulation.

    Keeps ALL batch dims explicit — merging a data-sharded batch dim with a
    model-sharded head dim (the reshape+batched-matmul formulation) forced
    the partitioner to all-gather whole KV caches per decode step
    (§Perf gemma3 baseline).  int32 safety: callers' contraction dims are
    dh (<=256) or a window/cache axis <= 128k; 128*128*131072 < 2^31.
    """
    return jnp.einsum(spec, a, b, preferred_element_type=jnp.int32)


def _quantize_q(q, attn_bits: int, backend: str, int_core: str) -> Q.QuantTensor:
    """q on its re-centered per-slot grid (axis 0 kept: co-batched slots stay
    independent), and the ``attn.qk`` site record of the core that takes it."""
    qq = Q.quantize_activation(q.astype(jnp.float32), attn_bits, per_channel_axis=0)
    qr = Q.recenter(qq)
    if site_log.is_recording():
        site_log.record(
            kind="attn",
            site="attn.qk",
            bits=attn_bits,
            mantissa_dtype=str(qr.mantissa.dtype),
            backend=backend,
            int_core=int_core,
        )
    return qr


def _scores_int(q, k_mantissa, k_scale, k_offset, attn_bits: int, backend: str = "auto"):
    """Integer QK^T via the flow abstraction (act x act QMM, paper type 2),
    GROUPED over kv heads (k stays un-expanded and kv-sharded; no dim
    merging — see _int_einsum).

    q: (B,S,H,dh) float -> quantized per-tensor.
    k_mantissa: (B,T,kvH,dh) int8 re-centered cache mantissas.
    ``backend`` is the site's configured name (site_log bookkeeping only —
    scores-only names never reach this function; see _binary_scores_site).
    """
    b, s, h, dh = q.shape
    t, kvh = k_mantissa.shape[1], k_mantissa.shape[2]
    g = h // kvh
    qr = _quantize_q(q, attn_bits, backend, "xla")
    x1 = qr.mantissa.reshape(b, s, kvh, g, dh)  # int8
    x2 = k_mantissa.astype(jnp.int8)  # (B,T,kvH,dh)
    xy = _int_einsum("bskgd,btkd->bkgst", x1, x2).astype(jnp.float32)
    # affine epilogue: q = a1*x1 + g1 ; k = a2*x2 + g2 (cache affine, recentered)
    a1 = jnp.reshape(qr.scale, (b, 1, 1, 1, 1))
    g1 = jnp.reshape(qr.offset, (b, 1, 1, 1, 1))
    a2 = _per_row(k_scale, 5)
    g2 = _per_row(k_offset, 5) + 128.0 * a2  # cache mantissa was re-centered by 128
    row = jnp.sum(x1, axis=-1, dtype=jnp.int32).astype(jnp.float32)  # (B,S,kvH,G)
    row = row.transpose(0, 2, 3, 1)[..., None]  # (B,kvH,G,S,1)
    col = jnp.sum(x2, axis=-1, dtype=jnp.int32).astype(jnp.float32)  # (B,T,kvH)
    col = col.transpose(0, 2, 1)[:, :, None, None, :]  # (B,kvH,1,1,T)
    out = xy * (a1 * a2) + (a1 * g2) * row + (g1 * a2) * col + g1 * g2 * dh
    return out.reshape(b, h, s, t)


def decode_core_engages(
    cache: Optional[dict],
    cfg: ArchConfig,
    kind: str,
    mode: str,
    s: int,
    kv_override=None,
) -> bool:
    """Does a decode step over ``cache`` run in the ``decode_attn`` kernel
    (``kernels.decode_attn``, which reads each slot's live K and V rows once)
    rather than the XLA chain ``_scores_int`` -> mask -> softmax ->
    ``_pv_int`` over all ``max_len`` rows?

    Yes for one query token (``s == 1``) in serve mode with int8 attention,
    against a global int8 GQA cache (no ring buffer, no window) read in
    grouped mode, at shapes the kernel takes (``dh`` a multiple of 128, kv
    heads a multiple of 8, a slot's scores and V rows within its VMEM
    budget), on a TPU.  Prefill, packed-K (bitwise) and float caches, local
    layers, ``gqa_mode="expand"``, cross-attention and CPU runs keep the XLA
    chain; MLA's latent cache never comes here.
    """
    quant = cfg.quant
    if not (
        s == 1
        and mode == "serve"
        and kv_override is None
        and quant.enabled
        and quant.quantize_attention
        and _cache_quantized(cache)
        and cache["k"].dtype == jnp.int8
        and not (kind == "l" and cfg.window_size)
        and cfg.gqa_mode != "expand"
    ):
        return False
    _, t, kvh, dh = cache["k"].shape
    return (
        dh % 128 == 0
        and kvh % 8 == 0
        and K_da.fits(t, kvh, dh, cfg.n_heads)
        and K_ops.on_tpu()
    )


def _decode_attn_int(
    q, k_mantissa, v_mantissa, k_scale, k_offset, v_scale, v_offset, pos,
    attn_bits: int, backend: str,
):
    """Decode attention in one kernel (``ops.decode_attn``): q on the grid
    ``_scores_int`` puts it on, against each slot's live cache rows.
    Returns the context (B, 1, H, dh) in float32, as ``_pv_int`` does."""
    qr = _quantize_q(q, attn_bits, backend, "decode_attn")
    ctx = K_ops.decode_attn(
        qr.mantissa[:, 0], qr.scale, qr.offset, k_mantissa, v_mantissa,
        k_scale, k_offset, v_scale, v_offset, pos,
    )
    return ctx[:, None]


def _write_prefill_cache(
    cache, k_m, v_m, s, cache_len, windowed, k_sc, k_off, v_sc, v_off
):
    """Write prefilled k/v (already in cache representation) into the cache.

    Full cache: place at [pos, pos+s).  Ring (windowed): keep only the last
    ``cache_len`` tokens, rolled so entry at absolute position p lands in
    slot ``p % W`` (assumes prefill starts from an empty cache — serving
    resets slots between requests).

    ``pos`` is per-row ``(B,)``; prefill requires all rows at the same
    cursor (in serving, prefill always runs on a freshly reset cache), so
    row 0's cursor indexes the batched write."""
    pos = jnp.reshape(cache["pos"], (-1,))[0]
    if windowed and s >= cache_len:
        keep_k = k_m[:, s - cache_len :]
        keep_v = v_m[:, s - cache_len :]
        shift = (s - cache_len) % cache_len
        new_k = jnp.roll(keep_k, shift, axis=1)
        new_v = jnp.roll(keep_v, shift, axis=1)
    else:
        new_k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_m, pos, 1)
        new_v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_m, pos, 1)
    out = dict(cache, k=new_k, v=new_v, pos=cache["pos"] + s)
    if k_sc is not None:
        out.update(k_scale=k_sc, k_offset=k_off, v_scale=v_sc, v_offset=v_off)
    return out


def _scores_int_latent(
    q_abs, ckv_m, ckv_scale, ckv_offset, attn_bits: int, backend: str = "auto"
):
    """Absorbed-MLA scores as one act x act QMM against the shared latent
    cache: ``scores[b,h,s,t] = sum_r q_abs[b,s,h,r] * ckv[b,t,r]``.

    The latent is head-shared, so heads fold into the M dim of a single
    integer MM per batch element (no H-fold copies of the int8 cache).
    """
    b, s, h, r = q_abs.shape
    t = ckv_m.shape[1]
    # per-row (per-slot) activation grid: co-scheduled requests must not
    # couple through a shared calibration (batch invariance)
    qq = Q.quantize_activation(q_abs.astype(jnp.float32), attn_bits, per_channel_axis=0)
    qr = Q.recenter(qq)
    if site_log.is_recording():
        site_log.record(
            kind="attn",
            site="attn.qk_latent",
            bits=attn_bits,
            mantissa_dtype=str(qr.mantissa.dtype),
            backend=backend,
            int_core="xla",
        )
    x1 = qr.mantissa.reshape(b, s * h, r)
    x2 = jnp.swapaxes(ckv_m, -1, -2).astype(jnp.int8)  # (b, r, t)
    xy = FA.default_int_matmul(x1, x2, attn_bits, 8).astype(jnp.float32)
    a1 = jnp.reshape(qr.scale, (b, 1, 1))
    g1 = jnp.reshape(qr.offset, (b, 1, 1))
    a2 = _per_row(ckv_scale, 3)
    g2 = _per_row(ckv_offset, 3) + 128.0 * a2
    row = jnp.sum(x1, axis=-1, dtype=jnp.int32)[..., None].astype(jnp.float32)
    col = jnp.sum(x2, axis=-2, dtype=jnp.int32)[..., None, :].astype(jnp.float32)
    out = xy * (a1 * a2) + (a1 * g2) * row + (g1 * a2) * col + g1 * g2 * r
    return out.reshape(b, s, h, t).transpose(0, 2, 1, 3)


def _pv_int(p_probs, v_mantissa, v_scale, v_offset):
    """Integer P @ V via the flow abstraction, GROUPED over kv heads (no
    dim merging — see _int_einsum).

    p_probs: (B,H,S,T) softmax output in [0,1] — quantized exactly with
    scale 1/255, offset 0 (the engine's W8 activation grid).
    v_mantissa: (B,T,kvH,dh) int8 re-centered (un-expanded).
    """
    b, h, s, t = p_probs.shape
    kvh, dh = v_mantissa.shape[2], v_mantissa.shape[3]
    g = h // kvh
    pm = jnp.clip(jnp.round(p_probs * 255.0), 0, 255.0)
    x1 = (pm - 128.0).astype(jnp.int8).reshape(b, kvh, g, s, t)
    a1, g1 = jnp.float32(1.0 / 255.0), jnp.float32(128.0 / 255.0)
    x2 = v_mantissa.astype(jnp.int8)  # (B,T,kvH,dh)
    a2 = _per_row(v_scale, 5)
    g2 = _per_row(v_offset, 5) + 128.0 * a2
    xy = _int_einsum("bkgst,btkd->bkgsd", x1, x2).astype(jnp.float32)
    row = jnp.sum(x1, axis=-1, dtype=jnp.int32)[..., None].astype(jnp.float32)
    col = jnp.sum(x2, axis=1, dtype=jnp.int32).astype(jnp.float32)  # (B,kvH,dh)
    col = col[:, :, None, None, :]  # (B,kvH,1,1,dh)
    out = xy * (a1 * a2) + (a1 * g2) * row + (g1 * a2) * col + g1 * g2 * t
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh)


# ---------------------------------------------------------------------------
# the mixer
# ---------------------------------------------------------------------------


def attention(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    kind: str,
    mode: str,
    positions: jax.Array,
    cache: Optional[dict] = None,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
    causal: Optional[bool] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    """One attention mixer application.

    Args:
      p: params from init_attention.
      x: (B, S, D) activations.
      cfg: arch config; ``kind`` "g" (global) or "l" (window cfg.window_size).
      mode: "train" | "serve" | "float".
      positions: (B, S) absolute positions of x.
      cache: KV cache dict (serving). None -> stateless full-seq attention.
      kv_override: (k, v) from an encoder (cross-attention); bypasses cache
        update and uses these as the full key/value set.
      causal: override cfg.causal (e.g. encoder self-attn inside a decoder
        stack).

    Returns:
      (out (B, S, D), updated cache or None)
    """
    quant = cfg.quant
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head

    q = _split_heads(L.qlinear(p["q"], x, quant, mode, name="attn.q"), h, dh)
    if kv_override is None:
        k = _split_heads(L.qlinear(p["k"], x, quant, mode, name="attn.k"), kvh, dh)
        v = _split_heads(L.qlinear(p["v"], x, quant, mode, name="attn.v"), kvh, dh)
    else:
        k, v = kv_override

    with jax.named_scope("attn.core"):
        ctx, new_cache = _attention_core(
            p, x, q, k, v, cfg, kind, mode, positions, cache, kv_override, causal
        )
    out = L.qlinear(
        p["o"], _merge_heads(ctx).astype(x.dtype), quant, mode, name="attn.o"
    )
    return out, new_cache


def _attention_core(p, x, q, k, v, cfg, kind, mode, positions, cache, kv_override, causal):
    """Everything between the k/v projections and ``attn.o``: norms, rope,
    the cache write (``attn.cache``), the scores with mask and scale
    (``attn.qk``) and softmax with AV (``attn.av``).  Returns the context
    (B, S, H, dh) and the updated cache."""
    quant = cfg.quant
    h, dh = cfg.n_heads, cfg.d_head
    b, s, _ = x.shape
    causal = cfg.causal if causal is None else causal
    window = cfg.window_size if kind == "l" else 0

    if cfg.qk_norm:
        q = L.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        if kv_override is None:
            k = L.rmsnorm(p["k_norm"], k, cfg.norm_eps)

    if cfg.pos_embedding == "rope" and kv_override is None:
        theta = (
            cfg.local_rope_theta
            if (kind == "l" and cfg.local_rope_theta)
            else cfg.rope_theta
        )
        q = L.rope(q, positions, theta)
        k = L.rope(k, positions, theta)

    # Cache geometry: local ("l") layers get a RING BUFFER of window_size
    # slots (init_kv_cache) — decode writes at ``pos % W`` and the slot's
    # absolute position is reconstructed for masking.  This bounds the
    # long-context memory term for local layers (the long_500k cells).
    cache_len = cache["k"].shape[1] if cache is not None else 0
    windowed = (
        cache is not None and kind == "l" and 0 < cfg.window_size == cache_len
    )
    quantized = _cache_quantized(cache)
    use_int = (
        mode == "serve"
        and quant.enabled
        and quant.quantize_attention
        and kv_override is None
        and (cache is None or quantized)
    )
    # Bitwise attention: a scores-only backend override on "attn.qk"
    # rebinarizes Q per call and stores K as packed 1-bit planes; the score
    # core dispatches through the scores backend family.
    qk_backend = quant.backend_for("attn.qk")
    use_binary = (
        use_int
        and _binary_scores_site(quant, "attn.qk") is not None
        and (cache is None or _cache_binary(cache))
    )
    new_cache = cache

    if s > 1 or cache is None:
        # ---- full-sequence attention over the in-flight k/v -------------
        # (training, or serving prefill from an empty cache)
        sdt = jnp.bfloat16 if cfg.attn_scores_dtype == "bf16" else jnp.float32
        expand = cfg.gqa_mode == "expand"
        # k/v take their cache representation under attn.qk: the int scores read it
        with jax.named_scope("attn.qk"):
            if use_int and use_binary:
                kq = _binarize_rows(k)
                # cache affines are per-row (B,) — drop the keepdims axes
                k_sc = jnp.reshape(kq.scale, (b,))
                k_off = jnp.reshape(kq.offset, (b,))
                k_m = packing.pack_bits(kq.mantissa.astype(jnp.uint32), 1, axis=-1)
                v_sc, v_off = _calibrate_rows(v)
                v_m = _quantize_to_cache(v, v_sc, v_off)
                scores = _scores_binary(
                    q, k_m.transpose(0, 2, 1, 3), k_sc, k_off, dh, "attn.qk", qk_backend
                )
            elif use_int:
                k_sc, k_off = _calibrate_rows(k)
                v_sc, v_off = _calibrate_rows(v)
                k_m = _quantize_to_cache(k, k_sc, k_off)
                v_m = _quantize_to_cache(v, v_sc, v_off)
                k_s = _gqa_expand(k_m, h) if expand else k_m
                scores = _scores_int(q, k_s, k_sc, k_off, quant.attn_act_bits, qk_backend)
            else:
                qf = q
                kf = k
                if mode == "train" and quant.enabled and quant.quantize_attention:
                    qf = Q.fake_quant(q, quant.attn_act_bits)
                    kf = Q.fake_quant(k, quant.attn_act_bits)
                scores = _scores_float(qf, _gqa_expand(kf, h) if expand else kf, sdt)
            t_k = k.shape[1]  # == s for self-attn; encoder length for cross
            mask = _mask(s, t_k, 0, causal, window)
            scores = scores.astype(sdt) / jnp.sqrt(sdt(dh)) + mask[None, None].astype(sdt)
        with jax.named_scope("attn.av"):
            probs = jax.nn.softmax(scores, axis=-1)
            if use_int:
                v_s = _gqa_expand(v_m, h) if expand else v_m
                ctx = _pv_int(probs.astype(jnp.float32), v_s, v_sc, v_off)
            else:
                if mode == "train" and quant.enabled and quant.quantize_attention:
                    probs = Q.fake_quant(probs, quant.attn_act_bits)
                ctx = _pv_float(probs, _gqa_expand(v, h) if expand else v, x.dtype)
        with jax.named_scope("attn.cache"):
            if cache is not None and kv_override is None:
                if not quantized:
                    k_m = k.astype(cache["k"].dtype)
                    v_m = v.astype(cache["v"].dtype)
                    k_sc = v_sc = k_off = v_off = None
                elif not use_int:
                    k_sc, k_off = _calibrate_rows(k)
                    v_sc, v_off = _calibrate_rows(v)
                    k_m = _quantize_to_cache(k, k_sc, k_off)
                    v_m = _quantize_to_cache(v, v_sc, v_off)
                new_cache = _write_prefill_cache(
                    cache, k_m, v_m, s, cache_len, windowed,
                    k_sc, k_off, v_sc, v_off,
                )
    else:
        # ---- single-token decode over the cache --------------------------
        # ``pos`` is per-row: every slot advances its own cursor, so a packed
        # continuous-batching batch mixes requests at unrelated positions.
        with jax.named_scope("attn.cache"):
            pos = jnp.broadcast_to(jnp.reshape(cache["pos"], (-1,)), (b,))  # (B,)
            slot = pos % cache_len if windowed else pos
            if quantized:
                k_sc, k_off = cache["k_scale"], cache["k_offset"]
                v_sc, v_off = cache["v_scale"], cache["v_offset"]
                if use_binary:
                    # stream ONE packed row: binarize on the fixed prefill grid
                    k_m = _binarize_to_cache(k, k_sc, k_off)
                else:
                    k_m = _quantize_to_cache(k, k_sc, k_off)
                v_m = _quantize_to_cache(v, v_sc, v_off)
            else:
                k_m = k.astype(cache["k"].dtype)
                v_m = v.astype(cache["v"].dtype)
            row_write = jax.vmap(
                lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0)
            )
            new_k = row_write(cache["k"], k_m, slot)
            new_v = row_write(cache["v"], v_m, slot)
            new_cache = dict(cache, k=new_k, v=new_v, pos=cache["pos"] + 1)

        if decode_core_engages(cache, cfg, kind, mode, s, kv_override):
            with jax.named_scope("attn.qk"):
                ctx = _decode_attn_int(
                    q, new_k, new_v, k_sc, k_off, v_sc, v_off, pos,
                    quant.attn_act_bits, qk_backend,
                )
            return ctx, new_cache
        with jax.named_scope("attn.qk"):
            t = cache_len
            posc = pos[:, None]  # (B, 1)
            if windowed:
                # absolute position held by slot j after writing at `slot`
                j = jnp.arange(t)[None, :]
                slot_abs = j + t * ((posc - j) // t)
                valid = slot_abs >= 0
                rel_ok = slot_abs > posc - cfg.window_size  # ring holds exactly W
                valid &= rel_ok & (slot_abs <= posc)
            else:
                valid = jnp.arange(t)[None, :] <= posc
                if window:
                    valid &= jnp.arange(t)[None, :] > posc - window
            expand = cfg.gqa_mode == "expand"
            if use_int and use_binary:
                scores = _scores_binary(
                    q, new_k.transpose(0, 2, 1, 3), k_sc, k_off, dh, "attn.qk", qk_backend
                )
            elif use_int:
                k_s = _gqa_expand(new_k, h) if expand else new_k
                scores = _scores_int(q, k_s, k_sc, k_off, quant.attn_act_bits, qk_backend)
            else:
                src_k = new_k
                if quantized:
                    src_k = _dequantize_from_cache(src_k, k_sc, k_off, x.dtype)
                scores = _scores_float(q, _gqa_expand(src_k, h) if expand else src_k)
            scores = scores / jnp.sqrt(jnp.float32(dh))
            scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
        with jax.named_scope("attn.av"):
            probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
            if use_int:
                v_s = _gqa_expand(new_v, h) if expand else new_v
                ctx = _pv_int(probs, v_s, v_sc, v_off)
            else:
                src_v = new_v
                if quantized:
                    src_v = _dequantize_from_cache(src_v, v_sc, v_off, x.dtype)
                ctx = _pv_float(probs, _gqa_expand(src_v, h) if expand else src_v, x.dtype)
    return ctx, new_cache


# ---------------------------------------------------------------------------
# MLA — multi-head latent attention (deepseek v2/v3)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ArchConfig) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    ks = jax.random.split(key, 8)
    p = {}
    if m.q_lora_rank:
        p["q_down"] = L.init_linear(ks[0], d, m.q_lora_rank)
        p["q_norm_lora"] = jnp.zeros((m.q_lora_rank,), jnp.float32)
        p["q_up"] = L.init_linear(ks[1], m.q_lora_rank, h * qd)
    else:
        p["q_proj"] = L.init_linear(ks[1], d, h * qd)
    p["kv_down"] = L.init_linear(ks[2], d, m.kv_lora_rank)
    p["kv_norm"] = jnp.zeros((m.kv_lora_rank,), jnp.float32)
    p["k_rope"] = L.init_linear(ks[3], d, m.qk_rope_dim)
    p["k_up"] = L.init_linear(ks[4], m.kv_lora_rank, h * m.qk_nope_dim)
    p["v_up"] = L.init_linear(ks[5], m.kv_lora_rank, h * m.v_head_dim)
    p["o"] = L.init_linear(ks[6], h * m.v_head_dim, d, scale=0.5)
    return p


def init_mla_cache(batch: int, max_len: int, cfg: ArchConfig) -> dict:
    """Latent cache with per-row ``pos`` / calibration (see init_kv_cache)."""
    m = cfg.mla
    q = cfg.quant
    base = {
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), jnp.bfloat16),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if q.enabled and q.kv_cache_bits in (4, 8):
        base.update(
            ckv=jnp.zeros((batch, max_len, m.kv_lora_rank), jnp.int8),
            ckv_scale=jnp.ones((batch,), jnp.float32),
            ckv_offset=jnp.zeros((batch,), jnp.float32),
        )
    else:
        base["ckv"] = jnp.zeros((batch, max_len, m.kv_lora_rank), jnp.bfloat16)
    return base


def _mla_q(p, x, cfg, mode):
    """Project queries -> (q_nope (B,S,H,dn), q_rope (B,S,H,dr)), before RoPE."""
    m, h = cfg.mla, cfg.n_heads
    qd = m.qk_nope_dim + m.qk_rope_dim
    if m.q_lora_rank:
        qc = L.qlinear(p["q_down"], x, cfg.quant, mode, name="attn.q_down")
        qc = L.rmsnorm(p["q_norm_lora"], qc, cfg.norm_eps)
        q = L.qlinear(p["q_up"], qc, cfg.quant, mode, name="attn.q_up")
    else:
        q = L.qlinear(p["q_proj"], x, cfg.quant, mode, name="attn.q")
    q = q.reshape(*x.shape[:-1], h, qd)
    return q[..., : m.qk_nope_dim], q[..., m.qk_nope_dim :]


def mla_attention(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    mode: str,
    positions: jax.Array,
    cache: Optional[dict] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    """MLA mixer.  Prefill/train run the decompressed form; decode runs the
    *absorbed* form over the compressed (quantized) latent cache — the
    latent cache is both the memory win (kv_lora + rope per token instead of
    2*H*dh) and the right operand of the serving act x act QMMs.

    The projections are QMM sites (``attn.q``, ``attn.kv_down``,
    ``attn.k_rope``, ``attn.o``); everything between them runs under
    ``jax.named_scope("attn.core")``, as in :func:`attention`."""
    m, h = cfg.mla, cfg.n_heads
    quant = cfg.quant

    q_nope, q_rope = _mla_q(p, x, cfg, mode)
    ckv = L.qlinear(p["kv_down"], x, quant, mode, name="attn.kv_down")
    ckv = L.rmsnorm(p["kv_norm"], ckv, cfg.norm_eps)
    k_rope = L.qlinear(p["k_rope"], x, quant, mode, name="attn.k_rope")  # (B,S,dr)
    with jax.named_scope("attn.core"):
        ctx, cache = _mla_core(p, x, q_nope, q_rope, ckv, k_rope, cfg, mode, positions, cache)
    b, s, _ = x.shape
    out = L.qlinear(
        p["o"], ctx.reshape(b, s, h * m.v_head_dim).astype(x.dtype), quant, mode, name="attn.o"
    )
    return out, cache


def _mla_core(p, x, q_nope, q_rope, ckv, k_rope, cfg, mode, positions, cache):
    """Everything between the projections and ``attn.o``: RoPE (YaRN where
    the config scales it), the latent cache write (``attn.cache``), the
    scores with scale and mask (``attn.qk``: the ``k_up`` absorb, latent and
    rope scores) and softmax with the latent PV and the ``v_up`` absorb
    (``attn.av``).  Returns the context (B, S, H, dv) and the cache."""
    m, h = cfg.mla, cfg.n_heads
    b, s, _ = x.shape
    quant = cfg.quant
    scale = L.attention_scale(m.qk_nope_dim + m.qk_rope_dim, cfg.rope_scaling)
    q_rope = L.rope(q_rope, positions, cfg.rope_theta, scaling=cfg.rope_scaling)
    k_rope = L.rope(k_rope, positions, cfg.rope_theta, scaling=cfg.rope_scaling)

    decode = cache is not None and s == 1
    if cache is not None:
        with jax.named_scope("attn.cache"):
            cache = _mla_cache_write(cache, ckv, k_rope, b, s, decode)

    if decode:
        # ---- absorbed decode over the latent cache ----
        t = cache["ckv"].shape[1]
        quantized = "ckv_scale" in cache
        with jax.named_scope("attn.qk"):
            w_uk = p["k_up"]["w"] if "w" in p["k_up"] else None
            if w_uk is None:
                # serving params: dequantize the tiny up-projection once per
                # step (kv_lora x H*dn — weight-bits packed); the absorbed
                # matmuls then run against the integer latent cache.
                w_uk = _serving_dense(p["k_up"], m.kv_lora_rank, quant)
            w_uk_h = w_uk.reshape(m.kv_lora_rank, h, m.qk_nope_dim)
            # q_absorbed[b,1,h,r] = sum_dn q_nope[b,1,h,dn] * w_uk[r,h,dn]
            q_abs = jnp.einsum(
                "bshd,rhd->bshr", q_nope.astype(jnp.float32), w_uk_h.astype(jnp.float32)
            )
            lat_backend = quant.backend_for("attn.qk_latent")
            if quantized and quant.quantize_attention and (
                _binary_scores_site(quant, "attn.qk_latent") is not None
            ):
                scores_lat = _scores_binary_latent(
                    q_abs,
                    cache["ckv"],
                    cache["ckv_scale"],
                    cache["ckv_offset"],
                    "attn.qk_latent",
                    lat_backend,
                )
            elif quantized and quant.quantize_attention:
                scores_lat = _scores_int_latent(
                    q_abs,
                    cache["ckv"],
                    cache["ckv_scale"],
                    cache["ckv_offset"],
                    quant.attn_act_bits,
                    lat_backend,
                )
            else:
                ckv_all = cache["ckv"]
                if quantized:
                    ckv_all = _dequantize_from_cache(
                        ckv_all, cache["ckv_scale"], cache["ckv_offset"], jnp.float32
                    )
                scores_lat = jnp.einsum(
                    "bshr,btr->bhst", q_abs, ckv_all.astype(jnp.float32)
                )
            scores_rope = jnp.einsum(
                "bshd,btd->bhst",
                q_rope.astype(jnp.float32),
                cache["k_rope"].astype(jnp.float32),
            )
            scores = (scores_lat + scores_rope) * scale
            valid = jnp.arange(t)[None, :] < jnp.reshape(cache["pos"], (-1, 1))
            scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)
        with jax.named_scope("attn.av"):
            probs = jax.nn.softmax(scores, axis=-1)  # (B,H,1,T)
            if quantized and quant.quantize_attention:
                ctx_lat = _pv_int_latent(
                    probs, cache["ckv"], cache["ckv_scale"], cache["ckv_offset"]
                )
            else:
                ckv_all = cache["ckv"]
                if quantized:
                    ckv_all = _dequantize_from_cache(
                        ckv_all, cache["ckv_scale"], cache["ckv_offset"], jnp.float32
                    )
                ctx_lat = jnp.einsum("bhst,btr->bshr", probs, ckv_all.astype(jnp.float32))
            w_uv = p["v_up"]["w"] if "w" in p["v_up"] else None
            if w_uv is None:
                w_uv = _serving_dense(p["v_up"], m.kv_lora_rank, quant)
            w_uv_h = w_uv.reshape(m.kv_lora_rank, h, m.v_head_dim)
            ctx = jnp.einsum("bshr,rhd->bshd", ctx_lat, w_uv_h.astype(jnp.float32))
        return ctx, cache

    # ---- decompressed prefill / train ----
    sdt = jnp.bfloat16 if cfg.attn_scores_dtype == "bf16" else jnp.float32
    with jax.named_scope("attn.qk"):
        k_nope = L.qlinear(
            p["k_up"], ckv, quant, mode, name="attn.k_up"
        ).reshape(b, s, h, m.qk_nope_dim)
        if mode == "train" and quant.enabled and quant.quantize_attention:
            q_nope = Q.fake_quant(q_nope, quant.attn_act_bits)
            k_nope = Q.fake_quant(k_nope, quant.attn_act_bits)
        scores = (
            jnp.einsum("bshd,bthd->bhst", q_nope.astype(sdt), k_nope.astype(sdt))
            + jnp.einsum("bshd,btd->bhst", q_rope.astype(sdt), k_rope.astype(sdt))
        ) * sdt(scale)
        mask = _mask(s, s, positions[0, 0] * 0, cfg.causal, 0)
        scores = scores + mask[None, None].astype(sdt)
    with jax.named_scope("attn.av"):
        v = L.qlinear(
            p["v_up"], ckv, quant, mode, name="attn.v_up"
        ).reshape(b, s, h, m.v_head_dim)
        probs = jax.nn.softmax(scores, axis=-1)
        if mode == "train" and quant.enabled and quant.quantize_attention:
            probs = Q.fake_quant(probs, quant.attn_act_bits)
        ctx = jnp.einsum("bhst,bthd->bshd", probs.astype(x.dtype), v)
    return ctx, cache


def _mla_cache_write(cache, ckv, k_rope, b, s, decode):
    """Write the step's latent rows: the int8 grid calibrated on a prompt (per
    row), or the slot's stored grid for a decode token; rope keys as the
    cache leaf's dtype."""
    # per-row cursor: slots may sit at different sequence positions
    pos = jnp.broadcast_to(jnp.reshape(cache["pos"], (-1,)), (b,))
    row_write = jax.vmap(
        lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0)
    )

    def write(leaf, rows):
        if decode:
            return row_write(leaf, rows, pos)
        # prefill contract: fresh/uniform cache rows (row-0 cursor)
        return jax.lax.dynamic_update_slice_in_dim(leaf, rows, pos[0], 1)

    # rope slot dtype derives from the cache leaf (never a literal:
    # a write/init mismatch is how the cache dtype drifted once)
    new_rope = write(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype))
    if "ckv_scale" not in cache:
        new_ckv = write(cache["ckv"], ckv.astype(cache["ckv"].dtype))
        return dict(cache, ckv=new_ckv, k_rope=new_rope, pos=cache["pos"] + s)
    if s > 1:
        sc, off = _calibrate_rows(ckv)
    else:
        sc = jnp.broadcast_to(jnp.reshape(cache["ckv_scale"], (-1,)), (b,))
        off = jnp.broadcast_to(jnp.reshape(cache["ckv_offset"], (-1,)), (b,))
    new_ckv = write(cache["ckv"], _quantize_to_cache(ckv, sc, off))
    return dict(
        cache,
        ckv=new_ckv,
        ckv_scale=sc,
        ckv_offset=off,
        k_rope=new_rope,
        pos=cache["pos"] + s,
    )


def _pv_int_latent(p_probs, ckv_m, ckv_scale, ckv_offset):
    """Absorbed-MLA context as act x act QMM: ``P (B,H,S,T) @ ckv (B,T,R)``
    with heads folded into M (latent is head-shared).  Returns (B,S,H,R)."""
    b, h, s, t = p_probs.shape
    r = ckv_m.shape[-1]
    pm = jnp.clip(jnp.round(p_probs * 255.0), 0.0, 255.0)
    x1 = (pm - 128.0).astype(jnp.int8).transpose(0, 2, 1, 3).reshape(b, s * h, t)
    a1, g1 = jnp.float32(1.0 / 255.0), jnp.float32(128.0 / 255.0)
    x2 = ckv_m.astype(jnp.int8)  # (b, t, r)
    a2 = _per_row(ckv_scale, 3)
    g2 = _per_row(ckv_offset, 3) + 128.0 * a2
    xy = FA.default_int_matmul(x1, x2, 8, 8).astype(jnp.float32)
    row = jnp.sum(x1, axis=-1, dtype=jnp.int32)[..., None].astype(jnp.float32)
    col = jnp.sum(x2, axis=-2, dtype=jnp.int32)[..., None, :].astype(jnp.float32)
    out = xy * (a1 * a2) + (a1 * g2) * row + (g1 * a2) * col + g1 * g2 * t
    return out.reshape(b, s, h, r)


def _serving_dense(p: dict, k: int, quant: QuantConfig) -> jax.Array:
    """Materialize a small packed weight back to float (absorbed-path use)."""
    wq = Q.QuantTensor(
        mantissa=p["w_packed"],
        scale=p["w_scale"],
        offset=p["w_offset"],
        bits=quant.weight_bits,
        packed=True,
        packed_axis=0,
        length=k,
    )
    return wq.dequantize(jnp.float32)

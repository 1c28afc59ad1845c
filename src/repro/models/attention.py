"""Attention mixers: GQA (global/local), MLA, cross-attention — quant-aware.

BETA-specific parts:

* In ``serve`` mode the two attention matmuls (QK^T and PV) run as
  **activation x activation QMMs** through the flow abstraction — the QMM
  type the paper highlights as unsupported by prior accelerators (§II).
  Softmax stays full-precision (paper keeps non-linear ops FP).
* Scales: one affine per slot (batch row) for Q, K and V.  V's cannot be
  per token: its tokens lie along PV's contracted axis, and a scale that
  varies along the contracted axis does not factor out of the integer sum.

Representations.  :func:`representation` names a layer's, from the quant
config or an existing cache's leaves; nothing else decides them:

* ``FLOAT``: cache and math in float (training, cross-attention, 16-bit cache).
* ``INT8``: the cache as int8 mantissas on per-slot grids, ~2x smaller than
  bf16 and, as stored, the right operand of the act x act QMMs.
* ``BITS``: K as packed 1-bit planes (~8-16x smaller), V int8; AND-popcount
  scores (Bitformer), opt-in by a scores-only backend on ``attn.qk``.
  MLA's latent stays int8 under bitwise ``attn.qk_latent`` scores.

``_to_cache`` and ``_write_kv`` are each one's cache write, ``_scores_*`` and
``_pv_*`` its scores and PV, shared by prefill and decode.

Layouts: activations ``(B, S, D)``; q ``(B, S, H, dh)``; caches
``(B, T, kvH, dh)``; decode processes ``S = 1`` with positions from the
cache cursor.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

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
    "representation",
]

_NEG_INF = -1e30
FLOAT, INT8, BITS = "float", "int8", "bits"


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
# the representation decision
# ---------------------------------------------------------------------------


class Representation(NamedTuple):
    store: str  # what the cache holds: FLOAT, INT8 or BITS
    compute: str  # what the scores and PV run in: FLOAT, INT8 or BITS


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
    scores_only = set(backend_registry.backend_names(family="scores")) - set(
        backend_registry.backend_names(family="qmm")
    )
    return name if name in scores_only else None


def _cache_quantized(cache: dict) -> bool:
    return "k_scale" in cache or "ckv_scale" in cache


def representation(
    quant: QuantConfig,
    *,
    latent: bool = False,
    cache: Optional[dict] = None,
    mode: str = "serve",
    kv_override=None,
) -> Representation:
    """One layer's attention representation (GQA, or MLA's ``latent``).

    ``store``: what ``cache`` holds, read off its leaves, or with no cache
    what the config allocates.  ``compute``: integer only in ``serve`` mode
    with quantized attention over self-attention k/v in flight or in a
    quantized cache; bitwise where the layer's scores site (``attn.qk`` or
    ``attn.qk_latent``) names a scores-only backend.
    """
    binary = _binary_scores_site(quant, "attn.qk_latent" if latent else "attn.qk")
    if cache is None:
        quantized = quant.enabled and quant.kv_cache_bits in (4, 8)
        store = (BITS if binary and not latent else INT8) if quantized else FLOAT
    elif not _cache_quantized(cache):
        store = FLOAT
    else:
        store = BITS if not latent and cache["k"].dtype == jnp.uint32 else INT8
    integer = (
        mode == "serve"
        and quant.enabled
        and quant.quantize_attention
        and kv_override is None
        and (cache is None or store != FLOAT)
    )
    compute = (BITS if binary else INT8) if integer else FLOAT
    return Representation(store, compute)


# ---------------------------------------------------------------------------
# KV cache: allocation, representation, writes
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
    if kind == "l" and cfg.window_size:
        # ring buffer: local layers never need more than window_size slots
        max_len = min(max_len, cfg.window_size)
    store = representation(cfg.quant).store
    k_width = packing.packed_len(dh, 1) if store == BITS else dh  # BITS: uint32 words
    k_dtype = {FLOAT: dtype, INT8: jnp.int8, BITS: jnp.uint32}[store]
    cache = {
        "k": jnp.zeros((batch, max_len, kvh, k_width), k_dtype),
        "v": jnp.zeros((batch, max_len, kvh, dh), dtype if store == FLOAT else jnp.int8),
    }
    if store != FLOAT:
        cache.update(
            k_scale=jnp.ones((batch,), jnp.float32),
            k_offset=jnp.zeros((batch,), jnp.float32),
            v_scale=jnp.ones((batch,), jnp.float32),
            v_offset=jnp.zeros((batch,), jnp.float32),
        )
    cache["pos"] = jnp.zeros((batch,), jnp.int32)
    return cache


def _per_row(s, ndim: int):
    """Broadcast a per-row ``(B,)`` cache affine against a ``(B, ...)``
    operand of rank ``ndim``."""
    s = jnp.asarray(s)
    return s.reshape(s.shape + (1,) * (ndim - 1))


def _calibrate_rows(x: jax.Array):
    """Per-row affine calibration: min/offset and (max-min)/255 scale reduced
    over every axis but the batch row — co-batched requests never share a
    quantization grid (the batch-invariance contract)."""
    x32 = x.astype(jnp.float32).reshape(x.shape[0], -1)
    off = jnp.min(x32, axis=-1)
    sc = jnp.maximum((jnp.max(x32, axis=-1) - off) / 255.0, 1e-8)
    return sc, off


def _on_grid(x: jax.Array, scale, offset, qmax: float) -> jax.Array:
    """x's levels ``0..qmax`` on a FIXED per-row affine (prefill-calibrated)."""
    scale = _per_row(scale, x.ndim)
    offset = _per_row(offset, x.ndim)
    return jnp.clip(jnp.round((x.astype(jnp.float32) - offset) / scale), 0.0, qmax)


def _dequantize_from_cache(m: jax.Array, scale, offset, dtype):
    scale = _per_row(scale, m.ndim)
    offset = _per_row(offset, m.ndim)
    return ((m.astype(jnp.float32) + 128.0) * scale + offset).astype(dtype)


def _to_cache(store: str, xs, grid=None, dtype=None):
    """``xs`` (k and v, or the latent) in the cache representation ``store``,
    and the per-row grids ``((scale, offset), ...)`` they are on.

    ``grid`` None calibrates each row on a prompt; a decode token takes the
    slot's stored grid.  INT8 mantissas are re-centered by 128.  Under
    ``BITS`` the first tensor (K) is binarized and packed (dh bits
    little-endian along the last axis of each uint32 word), the others
    int8.  ``FLOAT`` casts to ``dtype`` (the cache leaf's) and has no grid."""
    if store == FLOAT:
        return tuple(x.astype(dtype) for x in xs), None
    bits = ()
    if grid is None:
        if store == BITS:
            # per-row elastic binarization (BiT): the engine's 1-bit grid,
            # reduced over every axis but the batch row (batch invariance)
            k = xs[0]
            kq = Q.quantize_activation(k.astype(jnp.float32), 1, per_channel_axis=0)
            b = k.shape[0]
            grid = ((jnp.reshape(kq.scale, (b,)), jnp.reshape(kq.offset, (b,))),)
            bits = (packing.pack_bits(kq.mantissa.astype(jnp.uint32), 1, axis=-1),)
        else:
            grid = (_calibrate_rows(xs[0]),)
        grid += tuple(_calibrate_rows(x) for x in xs[1:])
    if store == BITS and not bits:  # a decode token's K on the slot's 1-bit grid
        k_bits = _on_grid(xs[0], *grid[0], 1.0).astype(jnp.uint32)
        bits = (packing.pack_bits(k_bits, 1, axis=-1),)
    rest = zip(xs[len(bits):], grid[len(bits):])
    int8 = tuple((_on_grid(x, *g, 255.0) - 128.0).astype(jnp.int8) for x, g in rest)
    return bits + int8, grid


def _write_rows(leaf: jax.Array, rows: jax.Array, at) -> jax.Array:
    """Write ``rows`` (B, S, ...) into a cache leaf (B, T, ...): at each
    slot's own cursor (``at`` (B,), a decode token per slot), or from one
    cursor for every row (``at`` a scalar: a prompt, which serving prefills
    into a freshly reset cache)."""
    if jnp.ndim(at) == 0:
        return jax.lax.dynamic_update_slice_in_dim(leaf, rows, at, 1)
    row_write = jax.vmap(lambda c, u, i: jax.lax.dynamic_update_slice_in_dim(c, u, i, 0))
    return row_write(leaf, rows, at)


def _write_kv(cache, k_m, v_m, grid, at, s: int, ring: bool = False) -> dict:
    """The cache after ``s`` k/v rows per slot, already in its representation
    (``_to_cache``), are written at ``at`` (``_write_rows``) on ``grid``.

    ``ring``: a prompt at least as long as a local layer's ring buffer keeps
    its last ``W`` rows, rolled so the entry at absolute position p lands in
    slot ``p % W`` (the prompt starts from an empty cache)."""
    if ring:
        w = cache["k"].shape[1]
        keep_k = k_m[:, s - w :]
        keep_v = v_m[:, s - w :]
        shift = (s - w) % w
        new_k = jnp.roll(keep_k, shift, axis=1)
        new_v = jnp.roll(keep_v, shift, axis=1)
    else:
        new_k = _write_rows(cache["k"], k_m, at)
        new_v = _write_rows(cache["v"], v_m, at)
    out = dict(cache, k=new_k, v=new_v, pos=cache["pos"] + s)
    if grid is not None:
        (k_sc, k_off), (v_sc, v_off) = grid
        out.update(k_scale=k_sc, k_offset=k_off, v_scale=v_sc, v_offset=v_off)
    return out


# ---------------------------------------------------------------------------
# scores and PV: one function per representation
# ---------------------------------------------------------------------------


def _quantize_q(q, bits: int, site: str, backend: str, int_core: str = "xla") -> Q.QuantTensor:
    """q on its per-slot grid (axis 0 kept: co-batched slots stay
    independent; re-centered above 1 bit), and the record of the scores
    site that takes it and the core it runs in."""
    qq = Q.quantize_activation(q.astype(jnp.float32), bits, per_channel_axis=0)
    qr = Q.recenter(qq)
    if site_log.is_recording():
        site_log.record(
            kind="attn",
            site=site,
            bits=bits,
            mantissa_dtype=str(qr.mantissa.dtype),
            backend=backend,
            int_core=int_core,
        )
    return qr


def _affine(xy, a1, g1, a2, g2, row, col, n):
    """The real product of two affine operands ``x = a1*m1 + g1`` and
    ``y = a2*m2 + g2`` from the integer product ``xy = m1 . m2`` over ``n``
    terms, the row sums of m1 and the column sums of m2 (the flow
    abstraction's epilogue)."""
    return xy * (a1 * a2) + (a1 * g2) * row + (g1 * a2) * col + g1 * g2 * n


def _plane_popcounts(planes: jax.Array) -> jax.Array:
    """Per-row bit totals straight off packed planes — exact (tail bits are
    zero by packing) and cheaper than unpacking just to sum."""
    return jnp.sum(
        jax.lax.population_count(planes).astype(jnp.int32), axis=-1
    ).astype(jnp.float32)


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def _merge_heads(x):
    return x.reshape(*x.shape[:-2], -1)


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


def _scores_float(q, k):
    """Grouped GQA scores: q (B,S,H,dh) x k (B,T,kvH,dh) -> (B,H,S,T).

    q heads are reshaped (kvH, group) so the contraction runs against the
    UN-expanded k — kv heads stay sharded, no repeat, no gather.  Head
    ordering matches jnp.repeat semantics (head h -> kv h // group)."""
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, dh)
    out = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32))
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


def _scores_int(q, k_mantissa, k_scale, k_offset, attn_bits: int, backend: str = "auto"):
    """Integer QK^T via the flow abstraction (act x act QMM, paper type 2),
    GROUPED over kv heads (k stays un-expanded and kv-sharded; no dim
    merging — see _int_einsum).

    q: (B,S,H,dh) float -> quantized per slot.
    k_mantissa: (B,T,kvH,dh) int8 re-centered cache mantissas.
    ``backend`` is the site's configured name (site_log bookkeeping only).
    """
    b, s, h, dh = q.shape
    t, kvh = k_mantissa.shape[1], k_mantissa.shape[2]
    g = h // kvh
    qr = _quantize_q(q, attn_bits, "attn.qk", backend)
    x1 = qr.mantissa.reshape(b, s, kvh, g, dh)  # int8
    x2 = k_mantissa.astype(jnp.int8)  # (B,T,kvH,dh)
    xy = _int_einsum("bskgd,btkd->bkgst", x1, x2).astype(jnp.float32)
    # q = a1*x1 + g1 ; k = a2*x2 + g2 (cache affine, re-centered by 128)
    a1 = jnp.reshape(qr.scale, (b, 1, 1, 1, 1))
    g1 = jnp.reshape(qr.offset, (b, 1, 1, 1, 1))
    a2 = _per_row(k_scale, 5)
    g2 = _per_row(k_offset, 5) + 128.0 * a2
    row = jnp.sum(x1, axis=-1, dtype=jnp.int32).astype(jnp.float32)  # (B,S,kvH,G)
    row = row.transpose(0, 2, 3, 1)[..., None]  # (B,kvH,G,S,1)
    col = jnp.sum(x2, axis=-1, dtype=jnp.int32).astype(jnp.float32)  # (B,T,kvH)
    col = col.transpose(0, 2, 1)[:, :, None, None, :]  # (B,kvH,1,1,T)
    return _affine(xy, a1, g1, a2, g2, row, col, dh).reshape(b, h, s, t)


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
    out = _affine(xy, a1, g1, a2, g2, row, col, t)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh)


def _scores_binary(q, k_planes_t, k_scale, k_offset, site: str, backend: str):
    """Bitwise QK^T: elastic 1-bit Q against packed binary K planes.

    AND-popcount counts from the dispatched scores core, then the affine
    epilogue back to the real-valued score domain (the algebra is in
    ``kernels.binary_attn``).  q: (B,S,H,dh) float.  k_planes_t:
    (B,kvH,T,dw) packed key bits.  k_scale/k_offset: (B,) affine of the
    key bits (qmax=1 grid — NO re-centering shift).  Returns (B,H,S,T).
    """
    b, s, h, dh = q.shape
    g = h // k_planes_t.shape[1]
    qq = _quantize_q(q, 1, site, backend)
    q_planes = packing.pack_bits(qq.mantissa.astype(jnp.uint32), 1, axis=-1)
    q_planes = q_planes.transpose(0, 2, 1, 3)  # (B,H,S,dw)
    # "binary" engages the family and autotunes its core ("auto" over the
    # scores candidates, all bit-exact, so the verdict is pure speed); any
    # other scores-only name pins its own core ("float": the oracle's)
    core = "auto" if backend == "binary" else backend
    counts = K_ops.binary_attn_scores(q_planes, k_planes_t, dh=dh, backend=core)
    counts = counts.astype(jnp.float32)
    row = _plane_popcounts(q_planes)[..., None]  # (B,H,S,1)
    col = jnp.repeat(_plane_popcounts(k_planes_t), g, axis=1)[:, :, None, :]
    a1 = jnp.reshape(qq.scale, (b, 1, 1, 1))
    g1 = jnp.reshape(qq.offset, (b, 1, 1, 1))
    a2 = _per_row(k_scale, 4)
    g2 = _per_row(k_offset, 4)
    return _affine(counts, a1, g1, a2, g2, row, col, dh)


def _scores(compute: str, q, k, grid, quant: QuantConfig, dtype):
    """GQA scores (B,H,S,T) in the representation ``compute``, of k in flight
    or as cached, on ``grid`` (``_to_cache``; float scores dequantize a
    quantized cache to ``dtype``)."""
    backend = quant.backend_for("attn.qk")
    if compute == BITS:
        return _scores_binary(q, k.transpose(0, 2, 1, 3), *grid[0], "attn.qk", backend)
    if compute == INT8:
        return _scores_int(q, k, *grid[0], quant.attn_act_bits, backend)
    if grid is not None:
        k = _dequantize_from_cache(k, *grid[0], dtype)
    return _scores_float(q, k)


def _pv(compute: str, probs, v, grid, dtype):
    """GQA context (B,S,H,dh) in the representation ``compute`` (as ``_scores``)."""
    if compute != FLOAT:  # V is int8 under BITS too
        return _pv_int(probs, v, *grid[1])
    if grid is not None:
        v = _dequantize_from_cache(v, *grid[1], dtype)
    return _pv_float(probs, v, dtype)


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

    Yes for one query token (``s == 1``) whose representation is ``INT8``
    stored and computed, against a global cache (no ring buffer, no
    window), at shapes the kernel takes (``dh`` a multiple of 128, kv heads
    a multiple of 8, a slot's scores and V rows within its VMEM budget), on
    a TPU.  Prefill, packed-K (bitwise) and float caches, local layers,
    cross-attention and CPU runs keep the XLA chain; MLA's latent cache
    never comes here.
    """
    rep = representation(cfg.quant, cache=cache, mode=mode, kv_override=kv_override)
    if not (
        s == 1
        and cache is not None
        and rep == Representation(INT8, INT8)
        and not (kind == "l" and cfg.window_size)
    ):
        return False
    _, t, kvh, dh = cache["k"].shape
    return (
        dh % 128 == 0
        and kvh % 8 == 0
        and K_da.fits(t, kvh, dh, cfg.n_heads)
        and K_ops.on_tpu()
    )


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
    (B, S, H, dh) and the updated cache.

    A prompt (or training) attends over the in-flight k/v under a causal
    mask; a decode token over the cache under each slot's position.  The
    scores and PV are the same functions in both."""
    quant = cfg.quant
    dh = cfg.d_head
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
    rep = representation(quant, cache=cache, mode=mode, kv_override=kv_override)
    new_cache = cache
    prompt = s > 1 or cache is None
    fake = prompt and mode == "train" and quant.enabled and quant.quantize_attention

    if prompt:
        with jax.named_scope("attn.qk"):
            # k/v take their cache representation here: the int scores read it
            if rep.compute == FLOAT:
                ks, vs, grid = k, v, None
                if fake:
                    q = Q.fake_quant(q, quant.attn_act_bits)
                    ks = Q.fake_quant(k, quant.attn_act_bits)
            else:
                (ks, vs), grid = _to_cache(rep.compute, (k, v))
            scores = _scores(rep.compute, q, ks, grid, quant, x.dtype)
            # k.shape[1] == s for self-attn; encoder length for cross
            mask = _mask(s, k.shape[1], 0, causal, window)
            scores = scores / jnp.sqrt(jnp.float32(dh)) + mask[None, None]
    else:
        # ``pos`` is per-row: every slot advances its own cursor, so a packed
        # continuous-batching batch mixes requests at unrelated positions.
        with jax.named_scope("attn.cache"):
            pos = jnp.broadcast_to(jnp.reshape(cache["pos"], (-1,)), (b,))  # (B,)
            at = pos % cache_len if windowed else pos
            stored = None
            if rep.store != FLOAT:
                stored = ((cache["k_scale"], cache["k_offset"]), (cache["v_scale"], cache["v_offset"]))
            (k_m, v_m), grid = _to_cache(rep.store, (k, v), stored, cache["k"].dtype)
            new_cache = _write_kv(cache, k_m, v_m, grid, at, 1)
        ks, vs = new_cache["k"], new_cache["v"]
        if decode_core_engages(cache, cfg, kind, mode, s, kv_override):
            with jax.named_scope("attn.qk"):
                # q on the grid _scores_int puts it on, against each slot's live rows
                qr = _quantize_q(
                    q, quant.attn_act_bits, "attn.qk", quant.backend_for("attn.qk"), "decode_attn"
                )
                ctx = K_ops.decode_attn(
                    qr.mantissa[:, 0], qr.scale, qr.offset, ks, vs, *grid[0], *grid[1], pos
                )[:, None]
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
            scores = _scores(rep.compute, q, ks, grid, quant, x.dtype) / jnp.sqrt(jnp.float32(dh))
            scores = jnp.where(valid[:, None, None, :], scores, _NEG_INF)

    with jax.named_scope("attn.av"):
        probs = jax.nn.softmax(scores, axis=-1)
        if fake:
            probs = Q.fake_quant(probs, quant.attn_act_bits)
        ctx = _pv(rep.compute, probs, vs, grid, x.dtype)
    if prompt and cache is not None and kv_override is None:
        with jax.named_scope("attn.cache"):
            if rep.compute == FLOAT:
                (ks, vs), grid = _to_cache(rep.store, (k, v), None, cache["k"].dtype)
            row0 = jnp.reshape(cache["pos"], (-1,))[0]  # see _write_rows
            ring = windowed and s >= cache_len
            new_cache = _write_kv(cache, ks, vs, grid, row0, s, ring)
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
    base = {
        "k_rope": jnp.zeros((batch, max_len, m.qk_rope_dim), jnp.bfloat16),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if representation(cfg.quant, latent=True).store == INT8:
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

    rep = representation(quant, latent=True, cache=cache, mode=mode)
    decode = cache is not None and s == 1
    if cache is not None:
        with jax.named_scope("attn.cache"):
            cache = _mla_cache_write(cache, ckv, k_rope, rep.store, b, s, decode)

    if decode:
        # ---- absorbed decode over the latent cache ----
        t = cache["ckv"].shape[1]
        grid = None if rep.store == FLOAT else (cache["ckv_scale"], cache["ckv_offset"])
        with jax.named_scope("attn.qk"):
            # serving params: the tiny up-projection (kv_lora x H*dn) is
            # dequantized once per step; the absorbed matmuls then run
            # against the integer latent cache.
            w_uk = _dense_weight(p["k_up"], m.kv_lora_rank, quant)
            w_uk_h = w_uk.reshape(m.kv_lora_rank, h, m.qk_nope_dim)
            # q_absorbed[b,1,h,r] = sum_dn q_nope[b,1,h,dn] * w_uk[r,h,dn]
            q_abs = jnp.einsum(
                "bshd,rhd->bshr", q_nope.astype(jnp.float32), w_uk_h.astype(jnp.float32)
            )
            backend = quant.backend_for("attn.qk_latent")
            if rep.compute == BITS:
                scores_lat = _scores_binary_latent(q_abs, cache["ckv"], *grid, backend)
            elif rep.compute == INT8:
                bits = quant.attn_act_bits
                scores_lat = _scores_int_latent(q_abs, cache["ckv"], *grid, bits, backend)
            else:
                scores_lat = jnp.einsum("bshr,btr->bhst", q_abs, _latent_f32(cache["ckv"], grid))
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
            if rep.compute == FLOAT:
                ctx_lat = jnp.einsum("bhst,btr->bshr", probs, _latent_f32(cache["ckv"], grid))
            else:
                ctx_lat = _pv_int_latent(probs, cache["ckv"], *grid)
            w_uv = _dense_weight(p["v_up"], m.kv_lora_rank, quant)
            w_uv_h = w_uv.reshape(m.kv_lora_rank, h, m.v_head_dim)
            ctx = jnp.einsum("bshr,rhd->bshd", ctx_lat, w_uv_h.astype(jnp.float32))
        return ctx, cache

    # ---- decompressed prefill / train ----
    f32 = jnp.float32
    with jax.named_scope("attn.qk"):
        k_nope = L.qlinear(
            p["k_up"], ckv, quant, mode, name="attn.k_up"
        ).reshape(b, s, h, m.qk_nope_dim)
        if mode == "train" and quant.enabled and quant.quantize_attention:
            q_nope = Q.fake_quant(q_nope, quant.attn_act_bits)
            k_nope = Q.fake_quant(k_nope, quant.attn_act_bits)
        scores = (
            jnp.einsum("bshd,bthd->bhst", q_nope.astype(f32), k_nope.astype(f32))
            + jnp.einsum("bshd,btd->bhst", q_rope.astype(f32), k_rope.astype(f32))
        ) * f32(scale)
        mask = _mask(s, s, positions[0, 0] * 0, cfg.causal, 0)
        scores = scores + mask[None, None]
    with jax.named_scope("attn.av"):
        v = L.qlinear(
            p["v_up"], ckv, quant, mode, name="attn.v_up"
        ).reshape(b, s, h, m.v_head_dim)
        probs = jax.nn.softmax(scores, axis=-1)
        if mode == "train" and quant.enabled and quant.quantize_attention:
            probs = Q.fake_quant(probs, quant.attn_act_bits)
        ctx = jnp.einsum("bhst,bthd->bshd", probs.astype(x.dtype), v)
    return ctx, cache


def _mla_cache_write(cache, ckv, k_rope, store, b, s, decode):
    """Write the step's latent rows in the cache's representation (calibrated
    per row on a prompt, or on the slot's stored grid for a decode token)
    and its rope keys as the cache leaf's dtype."""
    # per-row cursor: slots may sit at different sequence positions
    pos = jnp.broadcast_to(jnp.reshape(cache["pos"], (-1,)), (b,))

    def write(leaf, rows):
        return _write_rows(leaf, rows, pos if decode else pos[0])

    # rope slot dtype derives from the cache leaf (never a literal:
    # a write/init mismatch is how the cache dtype drifted once)
    new_rope = write(cache["k_rope"], k_rope.astype(cache["k_rope"].dtype))
    stored = None
    if store != FLOAT and decode:
        stored = ((cache["ckv_scale"], cache["ckv_offset"]),)
    (ckv_m,), grid = _to_cache(store, (ckv,), stored, cache["ckv"].dtype)
    out = dict(cache, ckv=write(cache["ckv"], ckv_m), k_rope=new_rope, pos=cache["pos"] + s)
    if grid is not None:
        out.update(ckv_scale=grid[0][0], ckv_offset=grid[0][1])
    return out


def _latent_f32(ckv_m, grid):
    """The latent cache in float32 (dequantized where it is int8)."""
    if grid is not None:
        ckv_m = _dequantize_from_cache(ckv_m, *grid, jnp.float32)
    return ckv_m.astype(jnp.float32)


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
    qr = _quantize_q(q_abs, attn_bits, "attn.qk_latent", backend)
    x1 = qr.mantissa.reshape(b, s * h, r)
    x2 = jnp.swapaxes(ckv_m, -1, -2).astype(jnp.int8)  # (b, r, t)
    xy = FA.default_int_matmul(x1, x2, attn_bits, 8).astype(jnp.float32)
    a1 = jnp.reshape(qr.scale, (b, 1, 1))
    g1 = jnp.reshape(qr.offset, (b, 1, 1))
    a2 = _per_row(ckv_scale, 3)
    g2 = _per_row(ckv_offset, 3) + 128.0 * a2
    row = jnp.sum(x1, axis=-1, dtype=jnp.int32)[..., None].astype(jnp.float32)
    col = jnp.sum(x2, axis=-2, dtype=jnp.int32)[..., None, :].astype(jnp.float32)
    out = _affine(xy, a1, g1, a2, g2, row, col, r)
    return out.reshape(b, s, h, t).transpose(0, 2, 1, 3)


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
    return _affine(xy, a1, g1, a2, g2, row, col, t).reshape(b, s, h, r)


def _scores_binary_latent(q_abs, ckv_m, ckv_scale, ckv_offset, backend: str):
    """Bitwise absorbed-MLA scores against the int8 latent cache.

    The latent cache layout is UNCHANGED (int8 also feeds the PV QMM), so
    the key side re-binarizes each int8 mantissa at its grid midpoint:
    ``bit = (m >= 0)`` — per-element and deterministic, hence stale-free
    and batch-invariant — with the induced affine ``ak = 128*sc``,
    ``gk = off + 64*sc``.  The packed-cache memory win is GQA-only; this
    path buys the bitwise O(n^2) score core.  Returns (B,H,S,T).
    """
    k_bits = (ckv_m >= 0).astype(jnp.uint32)
    k_planes = packing.pack_bits(k_bits, 1, axis=-1)[:, None]  # (B,1,T,rw)
    sc = jnp.asarray(ckv_scale, jnp.float32)
    off = jnp.asarray(ckv_offset, jnp.float32)
    return _scores_binary(q_abs, k_planes, 128.0 * sc, off + 64.0 * sc, "attn.qk_latent", backend)


def _dense_weight(p: dict, k: int, quant: QuantConfig) -> jax.Array:
    """A small projection's float weight: as trained, or its packed serving
    form materialized back to float (absorbed-path use)."""
    if "w" in p:
        return p["w"]
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

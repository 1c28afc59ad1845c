"""jit'd wrappers around the Pallas kernels: padding, dispatch, epilogues.

Public entry points take logical (unpadded) shapes, pad to kernel block
multiples, invoke the kernel, slice back, and (for the QuantTensor entry)
apply the flow-abstraction epilogue.  ``interpret`` defaults to
auto-detection: real kernels on TPU, interpret mode elsewhere — the same
switch the model layer uses.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import backend_registry, flow_abstraction, packing, quantization
from repro.core.quantization import QuantTensor
from repro.kernels import binary_attn as _ba
from repro.kernels import binary_qmm as _bq
from repro.kernels import bitserial_qmm as _bs
from repro.kernels import fused_qmm as _fq
from repro.kernels import popcount_qmm as _pq

__all__ = [
    "on_tpu",
    "binary_qmm_int",
    "popcount_qmm_int",
    "bitserial_qmm_int",
    "qmm_pallas",
    "qmm_fused",
    "binary_attn_scores",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _auto_interpret(interpret: Optional[bool]) -> bool:
    return (not on_tpu()) if interpret is None else interpret


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _fit_block(block, m: int, kw: int):
    """Shrink a ``(bm, bn, bkw)`` popcount-kernel block to the problem: ``bm``
    to M rounded up to a sublane multiple (decode's M=8 is not padded to 64),
    ``bkw`` to the whole Kw when it fits in one block.  Callers then pad Kw
    to a multiple of ``bkw`` — the whole Kw or 128-word lane-aligned slabs."""
    bm, bn, bkw = block
    return min(bm, -(-m // 8) * 8), bn, min(bkw, kw)


def binary_qmm_int(
    a: jax.Array,
    w_packed: jax.Array,
    k: int,
    *,
    block=_bq.DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``a (M, K) int8 @ unpack(w_packed) (K, N)`` with auto-padding.

    Zero padding is exact: padded activation columns hit padded (zero) weight
    rows; padded rows/cols are sliced off.
    """
    bm, bn, bk = block
    m, _ = a.shape
    n = w_packed.shape[1]
    a_p = _pad_to(_pad_to(a, 0, bm), 1, bk)
    kp = a_p.shape[1]
    # pad packed weights along words to kp/32, then columns to bn
    w_p = _pad_to(_pad_to(w_packed, 0, kp // 32), 1, bn)
    out = _bq.binary_qmm(
        a_p, w_p, k=kp, block=block, interpret=_auto_interpret(interpret)
    )
    return out[:m, :n]


def popcount_qmm_int(
    a_packed: jax.Array,
    b_packed: jax.Array,
    *,
    block=_pq.DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Binary x binary over packed operands with auto-padding (M, N, Kw)."""
    m, kw = a_packed.shape
    n = b_packed.shape[1]
    block = _fit_block(block, m, kw)
    bm, bn, bkw = block
    a_p = _pad_to(_pad_to(a_packed, 0, bm), 1, bkw)
    b_p = _pad_to(_pad_to(b_packed, 0, a_p.shape[1]), 1, bn)
    out = _pq.popcount_qmm(a_p, b_p, block=block, interpret=_auto_interpret(interpret))
    return out[:m, :n]


def bitserial_qmm_int(
    a_planes: jax.Array,
    b_planes: jax.Array,
    *,
    block=_bs.DEFAULT_BLOCK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Multi-bit act x act from packed planes with auto-padding."""
    _, m, kw = a_planes.shape
    n = b_planes.shape[2]
    block = _fit_block(block, m, kw)
    bm, bn, bkw = block
    a_p = _pad_to(_pad_to(a_planes, 1, bm), 2, bkw)
    b_p = _pad_to(_pad_to(b_planes, 1, a_p.shape[2]), 2, bn)
    out = _bs.bitserial_qmm(a_p, b_p, block=block, interpret=_auto_interpret(interpret))
    return out[:m, :n]


def qmm_pallas(
    x: QuantTensor,
    w: QuantTensor,
    *,
    w_colsum: Optional[jax.Array] = None,
    out_dtype=jnp.float32,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """QuantTensor QMM routed through the Pallas kernels + flow epilogue.

    Dispatch (mirrors BETA's mode table, Fig. 4):
      * weight_bits == 1 and act mantissa int8-representable -> binary_qmm
        (fused unpack + MXU int8) — act x weight, any act precision.
      * 1-bit x 1-bit -> popcount_qmm on fully packed operands.
      * multi-bit act x act -> bitserial_qmm over bit-planes.

    Only rank-2 operands hit the kernels; callers flatten leading batch dims
    (the model layer does).  Falls back to the jnp paths for other cases.
    """
    x_l = x.logical_shape
    w_l = w.logical_shape
    if len(w_l) != 2 or len(x_l) != 2:
        raise ValueError("qmm_pallas expects rank-2 operands; flatten batch dims")
    k = x_l[-1]

    if x.bits == 1 and w.bits == 1:
        a_packed = (
            x.mantissa if x.packed else packing.pack_bits(x.mantissa, 1, axis=-1)
        )
        b_packed = (
            w.mantissa if w.packed else packing.pack_bits(w.mantissa, 1, axis=0)
        )
        xy = popcount_qmm_int(a_packed, b_packed, interpret=interpret)
        return _epilogue(x, w, xy, k, w_colsum, out_dtype)

    if w.bits == 1:
        # act x weight: re-center activations (exact), unpack to int8.
        xr = quantization.recenter(x)
        a8 = xr.unpack(dtype=jnp.int8).mantissa
        b_packed = (
            w.mantissa if w.packed else packing.pack_bits(w.mantissa, 1, axis=0)
        )
        xy = binary_qmm_int(a8, b_packed, k, interpret=interpret)
        return _epilogue(xr, w, xy, k, w_colsum, out_dtype)

    # multi-bit act x act: bit-serial planes (unsigned mantissas).
    a_planes = packing.pack_bitplanes(
        x.unpack(dtype=jnp.int32).mantissa.astype(jnp.uint32), x.bits, axis=-1
    )
    b_planes = packing.pack_bitplanes(
        w.unpack(dtype=jnp.int32).mantissa.astype(jnp.uint32), w.bits, axis=-2
    )
    xy = bitserial_qmm_int(a_planes, b_planes, interpret=interpret)
    return _epilogue(x, w, xy, k, w_colsum, out_dtype)


def qmm_fused(
    x: QuantTensor,
    w: QuantTensor,
    *,
    w_colsum: Optional[jax.Array] = None,
    out_dtype=jnp.float32,
    interpret: Optional[bool] = None,
    block=_fq.DEFAULT_BLOCK,
) -> jax.Array:
    """QuantTensor QMM through the *fused* bit-serial kernel.

    One Pallas pass does everything: packed planes in, AND-popcount
    cross-plane accumulation, and the affine epilogue on-chip — the integer
    MM never round-trips HBM (contrast ``qmm_pallas``, which stages the
    integer result and applies the epilogue as a separate XLA computation).

    ``w_colsum`` is accepted for signature parity with the other backends but
    ignored: the kernel accumulates ``colsum(W)`` from the same packed planes
    it is already popcounting, so a precomputed colsum saves nothing.
    """
    x_l = x.logical_shape
    w_l = w.logical_shape
    if len(w_l) != 2 or len(x_l) != 2:
        raise ValueError("qmm_fused expects rank-2 operands; flatten batch dims")
    del w_colsum  # computed in-kernel from the planes already on chip
    m, k = x_l
    n = w_l[-1]

    # Raw unsigned mantissa planes (the popcount contract — no re-centering).
    if x.packed and x.bits == 1:
        a_planes = x.mantissa.astype(jnp.uint32)[None]  # (1, M, Kw)
    else:
        a_planes = packing.pack_bitplanes(
            x.unpack(dtype=jnp.int32).mantissa.astype(jnp.uint32), x.bits, axis=-1
        )
    if w.packed and w.bits == 1:
        b_planes = w.mantissa.astype(jnp.uint32)[None]  # (1, Kw, N)
    else:
        b_planes = packing.pack_bitplanes(
            w.unpack(dtype=jnp.int32).mantissa.astype(jnp.uint32), w.bits, axis=-2
        )

    f32 = jnp.float32
    a_scale = jnp.broadcast_to(jnp.asarray(x.scale, f32), (m, 1))
    a_off = jnp.broadcast_to(jnp.asarray(x.offset, f32), (m, 1))
    w_scale = jnp.broadcast_to(jnp.asarray(w.scale, f32), (1, n))
    w_off = jnp.broadcast_to(jnp.asarray(w.offset, f32), (1, n))

    block = _fit_block(block, m, a_planes.shape[2])
    bm, bn, bkw = block
    a_p = _pad_to(_pad_to(a_planes, 1, bm), 2, bkw)
    b_p = _pad_to(_pad_to(b_planes, 1, a_p.shape[2]), 2, bn)
    out = _fq.fused_qmm(
        a_p,
        b_p,
        _pad_to(a_scale, 0, bm),
        _pad_to(a_off, 0, bm),
        _pad_to(w_scale, 1, bn),
        _pad_to(w_off, 1, bn),
        k=k,
        block=block,
        interpret=_auto_interpret(interpret),
    )[:m, :n]
    return out if out_dtype == jnp.float32 else out.astype(out_dtype)


def _epilogue(x, w, xy, k, w_colsum, out_dtype):
    """Flow-abstraction corrections on the kernel's integer MM output.

    Valid for any mantissa representation (signed/unsigned) because the
    affine identity holds verbatim — re-centering only moves the offsets.
    ``w_colsum``, when provided, must be the colsum of the mantissas exactly
    as the kernel consumed them (weight_corrections() handles this).
    """
    x1 = x.unpack(dtype=jnp.int32).mantissa
    a1 = jnp.asarray(x.scale, out_dtype)
    g1 = jnp.asarray(x.offset, out_dtype)
    a2 = jnp.asarray(w.scale, out_dtype)
    g2 = jnp.asarray(w.offset, out_dtype)
    out = xy.astype(out_dtype) * (a1 * a2)
    row = jnp.sum(x1, axis=-1, dtype=jnp.int32)[..., None].astype(out_dtype)
    out = out + (a1 * g2) * row
    col = (
        w_colsum
        if w_colsum is not None
        else jnp.sum(w.unpack(dtype=jnp.int32).mantissa, axis=-2, dtype=jnp.int32)
    )
    out = out + (g1 * a2) * col[..., None, :].astype(out_dtype)
    return out + g1 * g2 * jnp.asarray(k, out_dtype)


# ---------------------------------------------------------------------------
# Backend registration — the Pallas-backed entries of the QMM registry.
# (core.qmm registers the jnp backends "mxu" and "popcount".)
# ---------------------------------------------------------------------------

# Off-TPU the kernels run in interpret mode — a correctness fallback, not a
# performance contender; only offer them on problems small enough that one
# autotune timing probe stays cheap.
_INTERPRET_MAX_MKN = 1 << 24


def _interpret_probe(m: int, k: int, n: int) -> bool:
    return on_tpu() or m * k * n <= _INTERPRET_MAX_MKN


def _packed_operand_bytes(m, k, n, act_bits, weight_bits):
    """HBM footprint of fully bit-plane-packed operands, in bytes."""
    kw_bytes = 4 * packing.packed_len(k, 1)
    return act_bits * m * kw_bytes, weight_bits * kw_bytes * n


def _traffic_pallas(m, k, n, act_bits, weight_bits) -> int:
    # Staged kernels: the int32 MM result round-trips HBM (write + read)
    # before the XLA epilogue writes the fp32 output — 12 bytes/element of
    # output traffic vs the fused kernel's 4.
    if weight_bits == 1 and act_bits > 1:
        a_bytes = m * k  # binary_qmm path: re-centered int8 activations
        b_bytes = 4 * packing.packed_len(k, 1) * n
    else:
        a_bytes, b_bytes = _packed_operand_bytes(m, k, n, act_bits, weight_bits)
    return a_bytes + b_bytes + 12 * m * n + 8 * (m + n)


def _traffic_fused(m, k, n, act_bits, weight_bits) -> int:
    # Packed planes fetched once, fp32 out written once — nothing staged.
    a_bytes, b_bytes = _packed_operand_bytes(m, k, n, act_bits, weight_bits)
    return a_bytes + b_bytes + 4 * m * n + 8 * (m + n)


backend_registry.register(
    backend_registry.QMMBackend(
        name="pallas",
        run=qmm_pallas,
        description="staged Pallas kernels (binary/popcount/bitserial) "
        "+ XLA flow epilogue",
        rank2_only=True,
        probe=_interpret_probe,
        traffic_model=_traffic_pallas,
    )
)

backend_registry.register(
    backend_registry.QMMBackend(
        name="fused",
        run=qmm_fused,
        description="one fused Pallas kernel: bit-serial AND-popcount core "
        "+ on-chip affine epilogue",
        rank2_only=True,
        needs_unsigned_mantissas=True,
        probe=_interpret_probe,
        traffic_model=_traffic_fused,
    )
)


# ---------------------------------------------------------------------------
# Scores family: rank-4 attention-scores cores (W1A1 packed planes).
# "mxu" also serves this family (registered in core.qmm); these two are
# scores-only, so the qmm entry point rejects them by family.
# ---------------------------------------------------------------------------


def binary_attn_scores(
    q_planes: jax.Array,
    k_planes: jax.Array,
    *,
    dh: int,
    backend: str = "auto",
    tag: Optional[str] = None,
) -> jax.Array:
    """Attention-scores integer core, backend-dispatched (scores family).

    ``backend="auto"`` consults the autotune cache under the "scores" family
    key (m = B*H*S, k = dh, n = T); explicit names resolve through the
    demotion table exactly like ``qmm`` — every scores core is bit-exact
    against ``ref.binary_attn_scores_ref``, so neither autotuning nor a
    demotion can change numerics.
    """
    from repro.core import dispatch

    b, h, s, _ = q_planes.shape
    t = k_planes.shape[2]
    if backend == "auto":
        backend = dispatch.choose_scores_backend(b, h, s, t, dh, tag=tag)
    else:
        backend = dispatch.resolve_backend(backend)
    spec = backend_registry.get_backend(backend)
    if "scores" not in spec.families or spec.run_scores is None:
        raise ValueError(
            f"backend {backend!r} does not serve the scores family; "
            f"scores backends: "
            f"{', '.join(backend_registry.backend_names(family='scores'))}"
        )
    return spec.run_scores(q_planes, k_planes, dh=dh)


def _float_scores(q_planes: jax.Array, k_planes: jax.Array, *, dh: int) -> jax.Array:
    """Float-dot scores core: unpack the {0,1} planes to f32 and einsum.

    The differential oracle's compute path — exact (hence bit-exact vs the
    popcount cores) because counts are bounded by dh << 2^24, within f32's
    integer-exact range.
    """
    qb = packing.unpack_bits(q_planes, 1, dh, axis=-1, dtype=jnp.float32)
    kb = packing.unpack_bits(k_planes, 1, dh, axis=-1, dtype=jnp.float32)
    b, h, s, _ = qb.shape
    g = kb.shape[1]
    qg = qb.reshape(b, g, h // g, s, dh)
    out = jnp.einsum("bgxsd,bgtd->bgxst", qg, kb)
    return out.reshape(b, h, s, kb.shape[2]).astype(jnp.int32)


def _traffic_scores_binary(m, k, n, act_bits, weight_bits) -> int:
    # Packed planes in, int32 counts out: m and n rows of ceil(k/32) words.
    kw_bytes = 4 * packing.packed_len(k, 1)
    return m * kw_bytes + n * kw_bytes + 4 * m * n


backend_registry.register(
    backend_registry.QMMBackend(
        name="binary",
        run=_ba.binary_attn_scores_planes,  # scores-only: qmm rejects by family
        run_scores=_ba.binary_attn_scores_planes,
        description="rank-4 AND-popcount attention scores over packed "
        "uint32 Q/K bit-planes (Bitformer path)",
        precisions=frozenset({(1, 1)}),
        needs_unsigned_mantissas=True,
        families=frozenset({"scores"}),
        traffic_model=_traffic_scores_binary,
    )
)

backend_registry.register(
    backend_registry.QMMBackend(
        name="float",
        run=_float_scores,  # scores-only: qmm rejects by family
        run_scores=_float_scores,
        description="float-dot attention scores over unpacked {0,1} planes "
        "(the differential oracle's compute path)",
        precisions=frozenset({(1, 1)}),
        families=frozenset({"scores"}),
        traffic_model=lambda m, k, n, ab, wb: 4 * (m * k + n * k) + 4 * m * n,
    )
)

"""The QMM engine: precision-configurable quantized matmul dispatch.

This is the software counterpart of BETA's QMM engine (§III-C): one entry
point that serves both QMM types (activation x weight, activation x
activation) at every supported activation precision, on top of the
computation-flow abstraction (``flow_abstraction.qmm_flow``).

Backends for the integer MM core:

* ``"mxu"``      — int8 ``lax.dot_general`` (int32 accum). TPU-native: the
                   systolic array does 8-bit integer MACs at ~2x bf16 rate.
                   Default for model forward passes and the dry-run path.
                   At decode's few rows on a TPU a packed 1-bit weight goes
                   to ``kernels.binary_qmm.decode_qmm`` as packed words and
                   is unpacked in VMEM (``packed_core_engages``); otherwise
                   XLA unpacks it to a K x N int8 array in HBM first.
* ``"popcount"`` — AND+popcount over bit-packed uint32 lanes — the faithful
                   analogue of BETA's XNOR-popcount DPU. (With the unified
                   unsigned-mantissa form, +-1 XNOR-popcount becomes {0,1}
                   AND-popcount; the affine epilogue absorbs the difference,
                   which is why one datapath serves both operand kinds.)
                   Multi-bit operands run bit-serially over planes (Fig. 4).
* ``"pallas"``   — the Pallas TPU kernels in ``repro.kernels`` (fused
                   unpack -> MXU dot with VMEM tiling); falls back to
                   interpret mode off-TPU.

* ``"fused"``    — one Pallas kernel running the whole bit-serial schedule
                   (pack-plane AND-popcount, cross-plane accumulate, affine
                   epilogue) without touching HBM between stages — the
                   closest software analogue of BETA's fused datapath.

Backends are *registered*, not hardcoded: each one is a
``repro.core.backend_registry.QMMBackend`` spec (run callable + capability
flags), and ``qmm(backend=...)`` resolves names through the registry.  This
module registers ``mxu`` and ``popcount``; ``repro.kernels.ops`` registers
``pallas`` and ``fused``.  Adding a backend elsewhere requires no edits here.

All backends return results that agree exactly (integer math) and match the
dequantized FP reference to fp32 rounding — property-tested.  Because the
backends agree numerically, ``backend="auto"`` is free to pick whichever is
fastest: it consults the measured autotune cache in ``repro.core.dispatch``
(keyed on shape, precision, and backend availability) instead of a
hardcoded default.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import backend_registry, flow_abstraction, packing
from repro.core.precision import PrecisionMode
from repro.core.quantization import QuantTensor

__all__ = ["qmm", "and_popcount_matmul", "popcount_int_matmul"]

# Columns of the right operand processed per popcount sweep; bounds the
# broadcast intermediate to n_chunk * M * Kw words (VMEM-sized blocks in the
# Pallas kernel play the same role).
_POPCOUNT_N_CHUNK = 256

#: Most activation rows whose product with a packed 1-bit weight the ``mxu``
#: backend runs in the decode kernel (see :func:`packed_core_engages`).
PACKED_CORE_MAX_ROWS = 64


def and_popcount_matmul(a_packed: jax.Array, b_packed: jax.Array) -> jax.Array:
    """Binary integer MM over bit-packed operands.

    ``out[m, n] = sum_w popcount(a[m, w] & b[w, n])`` — BETA's DPU datapath
    expressed in lane-parallel jnp (the Pallas kernel tiles exactly this).

    Args:
      a_packed: uint32 ``(..., M, Kw)`` — K packed along the last axis.
      b_packed: uint32 ``(..., Kw, N)`` — K packed along the second-to-last.

    Returns:
      int32 ``(..., M, N)``.
    """
    m = a_packed.shape[-2]
    n = b_packed.shape[-1]
    out_chunks = []
    for s in range(0, n, _POPCOUNT_N_CHUNK):
        b_blk = jax.lax.slice_in_dim(b_packed, s, min(s + _POPCOUNT_N_CHUNK, n), axis=-1)
        # (..., M, 1, Kw) & (..., 1, Nc, Kw) -> popcount -> sum over Kw.
        joint = a_packed[..., :, None, :] & jnp.swapaxes(b_blk, -1, -2)[..., None, :, :]
        out_chunks.append(
            jnp.sum(jax.lax.population_count(joint).astype(jnp.int32), axis=-1)
        )
    return jnp.concatenate(out_chunks, axis=-1) if len(out_chunks) > 1 else out_chunks[0]


def popcount_int_matmul(
    x: jax.Array, y: jax.Array, x_bits: int, y_bits: int
) -> jax.Array:
    """``int_matmul`` backend built from AND-popcount + bit-serial planes.

    Accepts *unpacked* unsigned mantissas (the ``qmm_flow`` contract), packs
    bit-planes, and accumulates ``sum_ij 2^(i+j) popcount-MM(X_i, Y_j)`` —
    the paper's bit-serial schedule.  Exact for unsigned mantissas; callers
    must not pre-recenter (use ``qmm(..., backend='popcount')`` which skips
    re-centering).
    """
    a_planes = packing.pack_bitplanes(x.astype(jnp.uint32), x_bits, axis=-1)
    b_planes = packing.pack_bitplanes(y.astype(jnp.uint32), y_bits, axis=-2)
    total = None
    for i in range(x_bits):
        for j in range(y_bits):
            part = and_popcount_matmul(a_planes[i], b_planes[j]) << (i + j)
            total = part if total is None else total + part
    return total


def qmm(
    x: QuantTensor,
    w: QuantTensor,
    *,
    backend: str = "auto",
    mode: Optional[PrecisionMode] = None,
    w_colsum: Optional[jax.Array] = None,
    out_dtype=jnp.float32,
) -> jax.Array:
    """Quantized matmul through the flow abstraction, backend-dispatched.

    Args:
      x: left operand ``(..., M, K)`` QuantTensor.
      w: right operand ``(K, N)`` or ``(..., K, N)`` QuantTensor.
      backend: "auto" or any name registered in ``core.backend_registry``
        ("mxu", "popcount", "pallas", "fused", ...).
      mode: optional PrecisionMode for engine-config asserts.
      w_colsum: precomputed integer colsum of the (re-centered) right mantissa.
      out_dtype: epilogue dtype.
    """
    if mode is not None:
        if (x.bits, w.bits) not in {
            (mode.act_bits, mode.weight_bits),
            (mode.act_bits, mode.act_bits),
        }:
            raise ValueError(
                f"operands W{w.bits}A{x.bits} do not match engine mode {mode.name}"
            )
    backend = _resolve(x, w, backend)
    spec = backend_registry.get_backend(backend)  # ValueError on unknown name
    if "qmm" not in spec.families:
        raise ValueError(
            f"backend {backend!r} serves families {sorted(spec.families)}, "
            "not the qmm family; scores-only backends go through "
            "kernels.ops.binary_attn_scores"
        )
    return spec.run(x, w, w_colsum=w_colsum, out_dtype=out_dtype)


def _resolve(x: QuantTensor, w: QuantTensor, backend: str) -> str:
    """The backend ``qmm(x, w, backend=backend)`` runs."""
    from repro.core import dispatch

    if backend == "auto":
        # Measured dispatch (core.dispatch): look up — or time-and-record —
        # the winning backend for this (M, K, N, precisions, phase) key.
        # Under jax.jit this runs once at trace time (shapes are static).
        x_l, w_l = x.logical_shape, w.logical_shape
        m = 1
        for d in x_l[:-1]:
            m *= int(d)
        rank2 = len(x_l) == 2 and len(w_l) == 2  # pallas needs rank-2
        return dispatch.choose_backend(
            m, int(x_l[-1]), int(w_l[-1]), x.bits, w.bits, rank2=rank2
        )
    # Demotions override explicit names too: a backend the serving
    # engine has pinned away from must not come back via a config
    # literal or per-layer override while the pin is active.
    return dispatch.resolve_backend(backend)


def packed_core_engages(x: QuantTensor, w: QuantTensor) -> bool:
    """Does the ``mxu`` backend hand ``w``'s packed words to the decode kernel
    (``kernels.binary_qmm.decode_qmm``, which unpacks them in VMEM) rather
    than unpack them to a K x N int8 array in HBM first?

    Yes for a packed 1-bit ``(K/32, N)`` weight against at most
    ``PACKED_CORE_MAX_ROWS`` rows of a rank-2 activation on a TPU: decode's
    slots.  Prefill's prompts (89 tokens and more in every benchmark cell)
    keep the XLA path, and off the TPU, where the kernel would only be
    interpreted, every shape does.
    """
    from repro.kernels import ops

    x_l = x.logical_shape
    return (
        w.bits == 1
        and w.packed
        and w.mantissa.ndim == 2
        and w.packed_axis in (0, -2)
        and len(x_l) == 2
        and x_l[0] <= PACKED_CORE_MAX_ROWS
        and ops.on_tpu()
    )


def int_core(x: QuantTensor, w: QuantTensor, backend: str) -> str:
    """The integer core ``qmm(x, w, backend=backend)`` runs, for the site log:
    ``"packed"`` where the ``mxu`` backend's decode kernel reads the packed
    weight, ``"unpacked"`` otherwise."""
    packed = _resolve(x, w, backend) == "mxu" and packed_core_engages(x, w)
    return "packed" if packed else "unpacked"


# ---------------------------------------------------------------------------
# Built-in jnp backends (the Pallas-backed ones register in repro.kernels.ops)
# ---------------------------------------------------------------------------


def _mxu_traffic(m, k, n, act_bits, weight_bits) -> int:
    # Up to PACKED_CORE_MAX_ROWS rows of a 1-bit weight on the TPU, the
    # decode kernel reads the packed words once and unpacks them in VMEM.
    # Otherwise XLA consumes *unpacked* int8 mantissas: a u32 broadcast of
    # the packed words (4 bytes an element) and the int8 K x N array are
    # each written and read back before the dot.  The epilogue fuses into
    # the dot's consumer, so the output is written once.
    from repro.kernels import ops

    if weight_bits == 1 and m <= PACKED_CORE_MAX_ROWS and ops.on_tpu():
        w_bytes = 4 * packing.packed_len(k, 1) * n
    else:
        w_bytes = 10 * k * n
    return m * k + w_bytes + 4 * m * n + 8 * (m + n)


def _popcount_traffic(m, k, n, act_bits, weight_bits) -> int:
    # Bit-serial jnp path: each (i, j) plane pair re-reads plane i of the
    # acts and plane j of the weights — act planes are fetched weight_bits
    # times and vice versa (no cross-pair VMEM reuse outside a kernel).
    kw_bytes = 4 * packing.packed_len(k, 1)
    plane_reads = act_bits * weight_bits
    return (
        plane_reads * m * kw_bytes
        + plane_reads * kw_bytes * n
        + 4 * m * n
        + 8 * (m + n)
    )


def _mxu_scores(q_planes: jax.Array, k_planes: jax.Array, *, dh: int) -> jax.Array:
    """Scores-family core on the MXU: unpack the {0,1} planes to int8 and run
    a grouped int8 dot with int32 accumulation.  Bit-exact against the
    popcount cores (same integer math, different datapath), so the autotuner
    is free to pick either without touching numerics."""
    qb = packing.unpack_bits(q_planes, 1, dh, axis=-1, dtype=jnp.int8)
    kb = packing.unpack_bits(k_planes, 1, dh, axis=-1, dtype=jnp.int8)
    b, h, s, _ = qb.shape
    g = kb.shape[1]
    qg = qb.reshape(b, g, h // g, s, dh)
    out = jnp.einsum(
        "bgxsd,bgtd->bgxst", qg, kb, preferred_element_type=jnp.int32
    )
    return out.reshape(b, h, s, kb.shape[2])


@backend_registry.register_backend(
    "mxu",
    description="int8 dot_general on the MXU, int32 accumulation",
    traffic_model=_mxu_traffic,
    families=frozenset({"qmm", "scores"}),
    run_scores=_mxu_scores,
)
def _run_mxu(x: QuantTensor, w: QuantTensor, *, w_colsum=None, out_dtype=jnp.float32):
    return flow_abstraction.qmm_flow(
        x,
        w,
        int_matmul=None,
        packed_int_matmul=_decode_core if packed_core_engages(x, w) else None,
        w_colsum=w_colsum,
        out_dtype=out_dtype,
    )


def _decode_core(a: jax.Array, w_words: jax.Array) -> jax.Array:
    from repro.kernels import ops

    return ops.decode_qmm_int(a.astype(jnp.int8), w_words)


@backend_registry.register_backend(
    "popcount",
    description="bit-serial AND-popcount over packed uint32 lanes (jnp)",
    needs_unsigned_mantissas=True,
    traffic_model=_popcount_traffic,
)
def _run_popcount(
    x: QuantTensor, w: QuantTensor, *, w_colsum=None, out_dtype=jnp.float32
):
    # Popcount lanes consume raw unsigned planes: run the shared flow
    # abstraction without re-centering.  A caller-supplied colsum is valid
    # here only when re-centering is a no-op (1-bit weights).
    return flow_abstraction.qmm_flow(
        x,
        w,
        int_matmul=popcount_int_matmul,
        w_colsum=w_colsum if w.bits == 1 else None,
        out_dtype=out_dtype,
        recenter=False,
    )

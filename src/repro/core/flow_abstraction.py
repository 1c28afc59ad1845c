"""Computation-flow abstraction (the paper's §III-A, Fig. 2) — generalized.

Every binary-Transformer QMM operand is affine: ``alpha * X + gamma * 1``.
Instead of multiplying dequantized full-precision matrices (``N^3`` FP Ops),
the product is rewritten so that the cubic term is an **integer** matrix
multiply and every full-precision op is at most quadratic:

    (a1*X1 + g1*1)(a2*X2 + g2*1)
      = a1*a2 * (X1 @ X2)                # integer MM  (the QMM engine)
      + a1*g2 * rowsum(X1) . 1^T         # rank-1, integer rowsum
      + g1*a2 * 1 . colsum(X2)           # rank-1, integer colsum
      + g1*g2 * K * 1                    # constant

The paper's Fig. 2 is the special case ``g2 = 0`` (pure-coefficient weights):
``(aA + g*1) @ (bW) = (A@W)*(ab) + (1@W)*(gb)`` with ``ab``/``gb`` folded
offline.  This module implements the general form, which covers *both* QMM
types (activation x weight AND activation x activation) with offsets on both
operands — the capability the paper calls out as missing from prior
accelerators (VAQF et al.).

The integer MM itself is delegated to a pluggable backend (``int_matmul``):
the MXU int8 path, the Pallas fused unpack->dot kernel, or the popcount DPU
analogue — see ``repro.core.qmm`` / ``repro.kernels``.

Exactness: the rewrite is algebraically exact; property tests
(tests/test_flow_abstraction.py) assert equality with the dequantized FP
product to fp32 rounding.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import quantization
from repro.core.quantization import QuantTensor

__all__ = [
    "default_int_matmul",
    "qmm_flow",
    "weight_corrections",
    "op_counts_naive",
    "op_counts_abstracted",
]

# int8 x int8 products over K accumulate in int32; chunk K when the worst-case
# accumulator |K * qmax1 * qmax2| would overflow.
_INT32_SAFE = 2**30


def matmul_dimension_numbers(x_ndim: int, y_ndim: int):
    """dot_general dims for ``(..., M, K) @ (K, N)`` or batched
    ``(..., M, K) @ (..., K, N)`` with shared leading batch dims."""
    if y_ndim == 2:
        return (((x_ndim - 1,), (0,)), ((), ()))
    if x_ndim != y_ndim:
        raise ValueError(f"rank mismatch for batched matmul: {x_ndim} vs {y_ndim}")
    batch = tuple(range(x_ndim - 2))
    return (((x_ndim - 1,), (y_ndim - 2,)), (batch, batch))


def default_int_matmul(
    x: jax.Array, y: jax.Array, x_bits: int, y_bits: int
) -> jax.Array:
    """Integer MM on the MXU: int8 operands, int32 accumulation.

    TPU's systolic array executes 8-bit integer MACs natively (at ~2x bf16
    rate) — this is the TPU-native realization of BETA's DPU datapath for
    mantissas up to 8 bits.  Callers pass mantissas already re-centered to a
    signed range (see ``repro.core.qmm``), so ``|x| <= 2**(x_bits-1)``.

    K is chunked when int32 accumulation could overflow (only reachable for
    8-bit x 8-bit beyond K ~ 64k); chunk partials are combined in fp32 —
    exact while |partial sums| < 2**24, which is the same accumulator
    contract real integer systolic arrays ship with.
    """
    k = x.shape[-1]
    max_prod = 2 ** (x_bits - 1 + y_bits - 1) if (x_bits > 1 or y_bits > 1) else 1
    max_prod = max(max_prod, 1)
    x8 = x.astype(jnp.int8)
    y8 = y.astype(jnp.int8)
    dimension_numbers = matmul_dimension_numbers(x.ndim, y.ndim)
    if max_prod * k <= _INT32_SAFE:
        return jax.lax.dot_general(
            x8, y8, dimension_numbers, preferred_element_type=jnp.int32
        )
    n_chunks = -(-max_prod * k // _INT32_SAFE)
    chunk = -(-k // n_chunks)
    total = None
    for s in range(0, k, chunk):
        xs = jax.lax.slice_in_dim(x8, s, min(s + chunk, k), axis=x.ndim - 1)
        ys = jax.lax.slice_in_dim(y8, s, min(s + chunk, k), axis=y.ndim - 2)
        part = jax.lax.dot_general(
            xs, ys, dimension_numbers, preferred_element_type=jnp.int32
        ).astype(jnp.float32)
        total = part if total is None else total + part
    return total


def _int_sum(x: jax.Array, axis: int) -> jax.Array:
    return jnp.sum(x.astype(jnp.int32), axis=axis, dtype=jnp.int32)


def weight_corrections(w: QuantTensor) -> jax.Array:
    """Pre-compute ``colsum(X2)`` for a weight operand (offline, like the
    paper folds ``alpha*beta`` / ``gamma*beta`` offline).

    Computed on the *re-centered* mantissa so it matches what
    :func:`qmm_flow` uses internally.
    """
    x2 = quantization.recenter(w).unpack().mantissa
    return _int_sum(x2, axis=-2)


def qmm_flow(
    x: QuantTensor,
    w: QuantTensor,
    *,
    int_matmul: Optional[Callable] = None,
    w_colsum: Optional[jax.Array] = None,
    out_dtype=jnp.float32,
    recenter: bool = True,
    packed_int_matmul: Optional[Callable] = None,
) -> jax.Array:
    """Affine x affine QMM via the computation-flow abstraction.

    Args:
      x: left operand, logical shape ``(..., M, K)``. ``scale``/``offset`` are
        scalar or broadcastable to ``(..., M, 1)`` (per-token).
      w: right operand, logical shape ``(K, N)`` (act x weight) or
        ``(..., K, N)`` (act x act). ``scale``/``offset`` scalar or
        broadcastable to ``(1, N)`` (per-out-channel), or ``(..., M, N)``
        where each row of ``x`` meets a weight of its own (routed experts).
      int_matmul: integer MM backend ``f(x_int, w_int, x_bits, w_bits)``.
      w_colsum: optional precomputed ``colsum`` of the right mantissa *as the
        integer core consumes it* — re-centered when ``recenter=True``
        (``weight_corrections``), raw otherwise.  For 1-bit weights the two
        coincide (re-centering is a no-op at bits <= 1).  ``(..., N)``, or
        ``(..., M, N)`` with a weight per row (then required).
      out_dtype: accumulation dtype of the full-precision epilogue.
      recenter: shift multi-bit mantissas to the signed range before the
        integer MM (exact — absorbed into the offsets).  Backends whose
        integer core consumes raw unsigned mantissas (popcount/bit-serial
        lanes; ``QMMBackend.needs_unsigned_mantissas``) pass ``False``: the
        affine identity holds for either representation, so the epilogue is
        shared verbatim.
      packed_int_matmul: integer MM over the right operand's packed words,
        ``f(x_int (M, K), w_words uint32 (..., K/32, N)) -> int32 (M, N)``;
        takes the place of ``int_matmul`` for a 1-bit weight packed along K,
        so the K x N mantissa is never formed here.  Words with leading axes
        (a stack of experts) are the function's to pick from.

    Returns:
      The full-precision product, shape ``(..., M, N)``.
    """
    int_matmul = int_matmul or default_int_matmul
    if recenter:
        # Re-center multi-bit mantissas to the signed range so the int8 MXU
        # path applies at every precision (exact — absorbed into the offsets).
        x = quantization.recenter(x)
        w = quantization.recenter(w)
    x1 = x.unpack().mantissa
    k = x1.shape[-1]
    if w.logical_shape[-2] != k:
        raise ValueError(f"reduction mismatch: {x1.shape} @ {w.logical_shape}")

    a1 = jnp.asarray(x.scale, out_dtype)
    g1 = jnp.asarray(x.offset, out_dtype)
    a2 = jnp.asarray(w.scale, out_dtype)
    g2 = jnp.asarray(w.offset, out_dtype)

    # --- cubic term: pure integer MM on the engine ---
    if packed_int_matmul is not None:
        nd = w.mantissa.ndim
        packed_k = w.packed and nd >= 2 and w.packed_axis % nd == nd - 2
        if not (w.bits == 1 and packed_k):
            raise ValueError("packed_int_matmul needs a 1-bit (..., K/32, N) packed weight")
        xy = packed_int_matmul(x1, w.mantissa).astype(out_dtype)
        if w_colsum is None:
            # colsum of {0,1} bits = set bits per column of packed words
            w_colsum = _int_sum(jax.lax.population_count(w.mantissa), axis=-2)
    else:
        x2 = w.unpack().mantissa
        xy = int_matmul(x1, x2, x.bits, w.bits).astype(out_dtype)
        if w_colsum is None:
            w_colsum = _int_sum(x2, axis=-2)

    # --- quadratic/rank-1 corrections (the VPU's job in BETA) ---
    out = xy * (a1 * a2)
    # a1*g2 * rowsum(X1): (..., M, 1) broadcast over N.
    row = _int_sum(x1, axis=-1)[..., None].astype(out_dtype)
    out = out + (a1 * g2) * row
    # g1*a2 * colsum(X2): (..., 1, N) broadcast over M, or one per row.
    per_row = w_colsum.ndim == xy.ndim
    col = (w_colsum if per_row else w_colsum[..., None, :]).astype(out_dtype)
    out = out + (g1 * a2) * col
    # g1*g2*K constant.
    out = out + g1 * g2 * jnp.asarray(k, out_dtype)
    return out


def qmm_dequant_reference(x: QuantTensor, w: QuantTensor, out_dtype=jnp.float32):
    """The *naive* flow the paper replaces: dequantize both operands to full
    precision and multiply (N^3 FP Ops).  Kept as the correctness oracle and
    as the FP baseline for Table II reproduction."""
    xd = x.dequantize(out_dtype)
    wd = w.dequantize(out_dtype)
    dn = matmul_dimension_numbers(xd.ndim, wd.ndim)
    return jax.lax.dot_general(xd, wd, dn, preferred_element_type=out_dtype)


# ---------------------------------------------------------------------------
# Op counting (Fig. 2's complexity accounting, used by the energy model and
# the Table II benchmark).
# ---------------------------------------------------------------------------

def op_counts_naive(m: int, k: int, n: int) -> dict:
    """Full-precision MM of dequantized operands: M*N dots of length K."""
    return {"fp_ops": 2 * m * k * n, "int_ops": 0}


def op_counts_abstracted(m: int, k: int, n: int, *, weight_static: bool = True) -> dict:
    """Abstracted flow: integer MM + quadratic FP epilogue.

    Matches Fig. 2's ``2N^3 Iop + (3N^2 + 2) Op`` for m=k=n, weight_static
    (colsum offline, coefficient products offline).
    """
    int_ops = 2 * m * k * n  # the integer MM (MACs counted as 2 ops)
    int_ops += m * k  # rowsum(X1)
    if not weight_static:
        int_ops += k * n  # colsum(X2) when the right operand is an activation
    fp_ops = m * n  # scale by a1*a2
    fp_ops += m * n  # add rank-1 row correction (broadcast add)
    fp_ops += m * n  # add rank-1 col correction + constant (fused broadcast)
    fp_ops += 2  # offline coefficient products a1*a2, g1*a2 (paper's "+2")
    return {"fp_ops": fp_ops, "int_ops": int_ops}

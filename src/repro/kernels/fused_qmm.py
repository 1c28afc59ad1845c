"""Pallas TPU kernel: the *fused* bit-serial QMM — integer core + epilogue.

This is the paper's datapath (§III-A/III-C, Fig. 4) end-to-end in one kernel.
The staged Pallas paths (``binary_qmm``/``bitserial_qmm``/``popcount_qmm``)
return an integer MM to HBM and apply the flow-abstraction epilogue as a
separate XLA computation; here the whole schedule runs inside one grid pass:

* packed weight bit-planes stay resident in VMEM across the K traversal of a
  tile while activation bit-planes stream through the AND-popcount lanes;
* cross-plane accumulation ``sum_ij 2^(i+j) popcount(X_i & W_j)`` lives in an
  int32 VMEM accumulator ref, never touching HBM;
* the rank-1 flow-abstraction corrections need only ``rowsum(X)`` and
  ``colsum(W)``, and both are popcounts of the same planes already on chip —
  so they are accumulated in two narrow scratch refs alongside the MM;
* at the last K step the affine epilogue
  ``acc*(a1*a2) + (a1*g2)*row + (g1*a2)*col + g1*g2*K`` runs on the VPU and
  the fp32 result is the only thing written to HBM.

Operands are **raw unsigned mantissas** as bit-planes (the popcount contract:
no re-centering; the affine identity absorbs the representation).

Exactness contract vs ``kernels.ref.fused_qmm_ref``: the integer core (MM,
rowsum, colsum accumulators) is bit-exact, always.  The fp32 epilogue is
evaluated in the oracle's exact expression order, but compiled fp32 mul+add
chains may be contracted to fma (XLA:CPU does this and
``optimization_barrier`` does not prevent it), so epilogue equality across
two compilations is only *defined* when the arithmetic is exact: with
dyadic (power-of-two) scales whose offsets are dyadic multiples, every term
and partial sum is exactly representable and the kernel matches the oracle
bit-for-bit — that is the tested contract.  Arbitrary scales agree to
last-ulp fma-vs-mul/add differences.

Interpret mode runs the same kernel through the Pallas interpreter off-TPU
(CI's correctness fallback, same switch as the other kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitserial_qmm import check_block, plane_pair_popcounts

__all__ = ["fused_qmm", "DEFAULT_BLOCK"]

# bm, bn, bkw (words of 32 K-bits): the K-word block is the lane dim, so it
# is 128 words (4096 logical K) or the whole Kw (bitserial_qmm.check_block).
DEFAULT_BLOCK = (64, 128, 128)


def _kernel(
    a_ref,
    b_ref,
    a_scale_ref,
    a_off_ref,
    w_scale_ref,
    w_off_ref,
    o_ref,
    acc_ref,
    row_ref,
    col_ref,
    *,
    bkw: int,
    k_logical: int,
):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        row_ref[...] = jnp.zeros_like(row_ref)
        col_ref[...] = jnp.zeros_like(col_ref)

    # rowsum(X) = sum_i 2^i * popcount(plane i), colsum(W) likewise — the
    # same bits already in VMEM, no extra HBM traffic.
    row = jnp.zeros(row_ref.shape, jnp.int32)
    for i in range(a_ref.shape[0]):
        hits = jax.lax.population_count(a_ref[i]).astype(jnp.int32)
        row = row + (jnp.sum(hits, axis=1, keepdims=True) << i)
    col = jnp.zeros(col_ref.shape, jnp.int32)
    for j in range(b_ref.shape[0]):
        hits = jax.lax.population_count(b_ref[j]).astype(jnp.int32)
        col = col + (jnp.sum(hits, axis=0, keepdims=True) << j)
    acc_ref[...] = plane_pair_popcounts(a_ref, b_ref, acc_ref[...], bkw=bkw)
    row_ref[...] += row
    col_ref[...] += col

    # Fused affine epilogue (flow abstraction, §III-A): runs once per (i, j)
    # tile, after the last K slab; fp32 out is the only HBM write.
    @pl.when(kk == pl.num_programs(2) - 1)
    def _epilogue():
        a1 = a_scale_ref[...]  # (bm, 1) f32
        g1 = a_off_ref[...]
        a2 = w_scale_ref[...]  # (1, bn) f32
        g2 = w_off_ref[...]
        t0 = acc_ref[...].astype(jnp.float32) * (a1 * a2)
        t1 = (a1 * g2) * row_ref[...].astype(jnp.float32)
        t2 = (g1 * a2) * col_ref[...].astype(jnp.float32)
        t3 = g1 * g2 * jnp.float32(k_logical)
        o_ref[...] = ((t0 + t1) + t2) + t3


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def fused_qmm(
    a_planes: jax.Array,
    b_planes: jax.Array,
    a_scale: jax.Array,
    a_offset: jax.Array,
    w_scale: jax.Array,
    w_offset: jax.Array,
    *,
    k: int,
    block=DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Fused bit-serial QMM: integer MM + affine epilogue in one kernel.

    Args:
      a_planes: uint32 ``(a_bits, M, Kw)`` — left *unsigned* mantissa
        bit-planes, 1-bit-packed along the last axis.
      b_planes: uint32 ``(b_bits, Kw, N)`` — right unsigned mantissa planes,
        packed along axis -2.
      a_scale / a_offset: fp32 ``(M, 1)`` per-row affine coefficients.
      w_scale / w_offset: fp32 ``(1, N)`` per-column affine coefficients.
      k: *logical* K (pre-padding) — the constant term uses the true
        reduction length; padded zero bits contribute nothing elsewhere.
      block: ``(bm, bn, bkw)``; ``bkw`` is clamped to ``Kw``, and all operand
        dims must be pre-padded to multiples (``repro.kernels.ops.qmm_fused``
        handles padding).
      interpret: CPU validation mode.

    Returns:
      fp32 ``(M, N)`` — the full affine product
      ``(a1*X + g1)(a2*W + g2)`` evaluated via the flow abstraction.
    """
    a_bits, m, kw = a_planes.shape
    b_bits, kw2, n = b_planes.shape
    if kw != kw2:
        raise ValueError(f"packed-K mismatch: {a_planes.shape} vs {b_planes.shape}")
    if a_scale.shape != (m, 1) or a_offset.shape != (m, 1):
        raise ValueError(f"activation coefficients must be ({m}, 1)")
    if w_scale.shape != (1, n) or w_offset.shape != (1, n):
        raise ValueError(f"weight coefficients must be (1, {n})")
    bm, bn, bkw = check_block(m, n, kw, block)

    grid = (m // bm, n // bn, kw // bkw)
    coeff = jnp.float32
    return pl.pallas_call(
        functools.partial(
            _kernel, bkw=bkw, k_logical=int(k)
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((a_bits, bm, bkw), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((b_bits, bkw, bn), lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.int32),  # cross-plane MM accumulator
            pltpu.VMEM((bm, 1), jnp.int32),  # rowsum(X)
            pltpu.VMEM((1, bn), jnp.int32),  # colsum(W)
        ],
        interpret=interpret,
    )(
        a_planes,
        b_planes,
        a_scale.astype(coeff),
        a_offset.astype(coeff),
        w_scale.astype(coeff),
        w_offset.astype(coeff),
    )

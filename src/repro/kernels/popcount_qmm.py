"""Pallas TPU kernel: AND+popcount binary QMM — the faithful DPU analogue.

This is BETA's dot-product unit (Fig. 3b) transcribed to the TPU *vector*
unit: both operands stay bit-packed in uint32 lanes; a PE-sequence step is
``and`` + ``population_count`` on whole VREGs (32 binary MACs per lane-op),
and the compressor-tree is the int32 accumulator tile carried across the
K-grid in VMEM (the compressor-tree *loop*).

With the unified unsigned-mantissa form ({0,1} rather than +-1), XNOR-
popcount becomes AND-popcount; the affine flow-abstraction epilogue absorbs
the difference — one datapath for both operand kinds, like BETA.

W1A1 is the one-plane case of the bit-serial schedule, so this kernel is
``bitserial_qmm`` with a single plane per operand: the same word loop
(``bitserial_qmm.plane_pair_popcounts``) and the same lane-aligned blocks.

Blocking: grid = (M/bm, N/bn, Kw/bkw), K innermost.
  A  (bm, bkw)  uint32  — packed left mantissas (K packed along -1)
  B  (bkw, bn)  uint32  — packed right mantissas (K packed along -2)
  O  (bm, bn)   int32
"""

from __future__ import annotations

import functools

import jax

from repro.kernels.bitserial_qmm import DEFAULT_BLOCK, bitserial_qmm

__all__ = ["popcount_qmm", "DEFAULT_BLOCK"]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def popcount_qmm(
    a_packed: jax.Array,
    b_packed: jax.Array,
    *,
    block=DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Binary integer MM over packed operands: ``unpack(a) @ unpack(b)``.

    Args:
      a_packed: uint32 ``(M, Kw)``; K bit-packed along the last axis.
      b_packed: uint32 ``(Kw, N)``; K bit-packed along the first axis.
      block: (bm, bn, bkw) tile sizes; ``bkw`` is clamped to ``Kw``.
      interpret: CPU validation mode.

    Returns:
      int32 ``(M, N)`` — popcount-accumulated binary dot products.
    """
    if a_packed.shape[1] != b_packed.shape[0]:
        raise ValueError(f"packed-K mismatch: {a_packed.shape} vs {b_packed.shape}")
    return bitserial_qmm(
        a_packed[None], b_packed[None], block=block, interpret=interpret
    )

"""Pallas TPU kernel: bit-serial multi-bit act x act QMM (paper Fig. 4).

BETA runs a ``Wa x Aa`` activation x activation product by traversing one
operand bit-plane per cycle on the binary engine and shifting partial
results into place: ``A @ B = sum_ij 2^(i+j) (A_i (x) B_j)``.  This kernel is
that schedule with the planes unrolled inside one VMEM-resident block: the
(i, j) plane pairs reuse the same operand tiles, so packed bits are fetched
from HBM exactly once (the compute-buffer reuse idea of §III-C).

Blocking: grid = (M/bm, N/bn, Kw/bkw), K innermost; operand tiles carry the
plane axis whole (a_bits, b_bits <= 8, so worst case 8x8 = 64 plane pairs of
AND+popcount work per tile — still VPU-bound, as on BETA where the same pass
count shows up as `accumulation times`).

``plane_pair_popcounts`` is the word loop shared with ``popcount_qmm`` (one
plane each) and ``fused_qmm``.  Mosaic tiling rules: the K-word block is the
lane dim, so it is a multiple of 128 words or the whole ``Kw``; ``bm`` is a
multiple of 8 or the whole ``M``; ``bn`` a multiple of 128 or the whole
``N`` (``check_block``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["bitserial_qmm", "plane_pair_popcounts", "check_block", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = (64, 128, 128)  # bm, bn, bkw (bkw in 32-bit WORDS of K)
LANES = 128


def check_block(m: int, n: int, kw: int, block) -> tuple:
    """The block a kernel runs with: ``bkw`` clamped to ``Kw``, then the
    divisibility and Mosaic (8, 128) tiling rules enforced."""
    bm, bn, bkw = block
    bkw = min(bkw, kw)
    if m % bm or n % bn or kw % bkw:
        raise ValueError(f"shapes ({m},{kw},{n}) not multiples of block {block}")
    for name, size, full, unit in (
        ("bm", bm, m, 8), ("bn", bn, n, LANES), ("bkw", bkw, kw, LANES)
    ):
        if size != full and size % unit:
            raise ValueError(
                f"{name}={size} is neither a multiple of {unit} nor the whole dim {full}"
            )
    return bm, bn, bkw


def _lane_column(x: jax.Array, lane: jax.Array, w) -> jax.Array:
    """Column ``w`` (traced) of a ``(rows, lanes)`` uint32 value, as ``(rows, 1)``.

    Mosaic cannot slice a value's lane dim at a traced offset, so the column
    is masked out and reduced across lanes — exact, one nonzero term."""
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    col = jnp.sum(jnp.where(lane == w, xi, 0), axis=1, keepdims=True)
    return jax.lax.bitcast_convert_type(col, jnp.uint32)


def plane_pair_popcounts(a_ref, b_ref, acc: jax.Array, *, bkw: int) -> jax.Array:
    """``acc + sum_ij 2^(i+j) sum_w popcount(A_i[:, w] & B_j[w, :])`` for one tile.

    ``a_ref``: uint32 ``(a_bits, bm, bkw)`` (K-words on lanes); ``b_ref``:
    uint32 ``(b_bits, bkw, bn)`` (K-words on sublanes).  One word per loop
    step: each activation column is extracted once and AND-ed against every
    weight plane's row — 32 binary MACs per lane op.
    """
    a_bits, b_bits = a_ref.shape[0], b_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, a_ref.shape[1:], 1)

    def word(w, acc):
        cols = [_lane_column(a_ref[i], lane, w) for i in range(a_bits)]
        for j in range(b_bits):
            row = b_ref[j, pl.ds(w, 1), :]  # (1, bn)
            for i, col in enumerate(cols):
                hits = jax.lax.population_count(col & row).astype(jnp.int32)
                acc = acc + (hits << (i + j))
        return acc

    return jax.lax.fori_loop(0, bkw, word, acc)


def _kernel(a_ref, b_ref, o_ref, *, bkw: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] = plane_pair_popcounts(a_ref, b_ref, o_ref[...], bkw=bkw)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def bitserial_qmm(
    a_planes: jax.Array,
    b_planes: jax.Array,
    *,
    block=DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Multi-bit integer MM from packed bit-planes.

    Args:
      a_planes: uint32 ``(a_bits, M, Kw)`` — left mantissa bit-planes,
        1-bit-packed along the last axis.
      b_planes: uint32 ``(b_bits, Kw, N)`` — right mantissa bit-planes,
        packed along axis -2.
      block: (bm, bn, bkw); ``bkw`` is clamped to ``Kw``.
      interpret: CPU validation mode.

    Returns:
      int32 ``(M, N)`` == ``A @ B`` of the original multi-bit mantissas.
    """
    a_bits, m, kw = a_planes.shape
    b_bits, kw2, n = b_planes.shape
    if kw != kw2:
        raise ValueError(f"packed-K mismatch: {a_planes.shape} vs {b_planes.shape}")
    bm, bn, bkw = check_block(m, n, kw, block)

    grid = (m // bm, n // bn, kw // bkw)
    return pl.pallas_call(
        functools.partial(_kernel, bkw=bkw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((a_bits, bm, bkw), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((b_bits, bkw, bn), lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(a_planes, b_planes)

"""Pallas TPU kernel: fused unpack -> MXU integer dot (BETA's QMM engine).

The TPU-native adaptation of BETA's DPU (DESIGN.md §2): binary weights stay
**bit-packed in HBM** (1/16th the bf16 footprint — the memory-roofline win),
are unpacked to int8 inside VMEM, and the MAC work runs on the MXU's 8-bit
integer datapath (~2x bf16 rate) instead of an FPGA XNOR/popcount fabric.

Blocking (BlockSpec):
  grid = (M/bm, N/bn, K/bk), K innermost so the fp32/int32 accumulator tile
  stays resident in VMEM across the K sweep (the Pallas analogue of the
  compressor-tree *loop* carrying partial sums; the final flush is the
  carry-select-adder step).

  A  (bm, bk)   int8   — quantized activation mantissas (re-centered)
  Wp (bk/32,bn) uint32 — packed binary weight mantissas {0,1}
  O  (bm, bn)   int32  — integer MM result (flow-abstraction epilogue is
                          applied outside, fused by XLA)

VMEM @ defaults (bm=bn=128, bk=512): A 64 KiB + Wp 8 KiB + unpacked W 64 KiB
+ acc 64 KiB ~= 200 KiB — comfortably within a v5e core's ~16 MiB VMEM and
MXU-aligned (every matmul dim a multiple of 128).

``decode_qmm`` is the sibling for decode's few rows, which the ``mxu``
backend runs: ``bm`` is M rounded up to 32, one block holds the whole packed
K (to 512 words) and up to 1 MiB of words across N (``decode_block``), so
granite-8b's ``ffn.gate`` takes 14 grid steps where ``DEFAULT_BLOCK`` takes
896, and the words are unpacked a byte plane at a time.  VMEM at the largest
block (bm 64, bkw 512, bn 512): words 1 MiB and activations 1 MiB, each
double-buffered, 1 MiB of shifted words, a 128 KiB accumulator and two
128 KiB output blocks ~= 5.4 MiB, within the 16 MiB scoped by default.

``expert_decode_qmm`` is its grouped sibling for routed experts (below).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "binary_qmm",
    "DEFAULT_BLOCK",
    "decode_qmm",
    "decode_block",
    "expert_decode_qmm",
    "expert_block",
    "EXPERT_TILE_ROWS",
]

DEFAULT_BLOCK = (128, 128, 512)  # bm, bn, bk
_LANES_PER_WORD = 32


def _kernel(a_ref, wp_ref, o_ref, *, bk: int):
    """One (bm, bn) tile x one bk-slice of the reduction."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # --- fused unpack: (bk/32, bn) uint32 -> (bk, bn) int8 {0,1} ---
    wp = wp_ref[...]
    shifts = jnp.arange(_LANES_PER_WORD, dtype=jnp.uint32)[None, :, None]
    w_bits = (wp[:, None, :] >> shifts) & jnp.uint32(1)
    w = w_bits.reshape(bk, wp.shape[-1]).astype(jnp.int8)

    # --- MXU integer MAC, int32 accumulation (compressor-tree analogue) ---
    a = a_ref[...]
    o_ref[...] += jax.lax.dot_general(
        a,
        w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


@functools.partial(
    jax.jit, static_argnames=("k", "block", "interpret")
)
def binary_qmm(
    a: jax.Array,
    w_packed: jax.Array,
    *,
    k: int,
    block=DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Integer MM ``a @ unpack(w_packed)`` with binary packed weights.

    Args:
      a: int8 ``(M, K)`` quantized activation mantissas.
      w_packed: uint32 ``(K/32, N)`` bit-packed binary weight mantissas.
      k: logical K (must equal ``a.shape[1]``; multiple of 32 and of
        ``block[2]`` — callers pad via ``ops.binary_qmm_int``).
      block: (bm, bn, bk) VMEM tile sizes.
      interpret: run the kernel body in Python (CPU validation mode).

    Returns:
      int32 ``(M, N)``.
    """
    m, ak = a.shape
    kw, n = w_packed.shape
    bm, bn, bk = block
    if ak != k or kw * _LANES_PER_WORD != k:
        raise ValueError(f"K mismatch: a {a.shape}, w_packed {w_packed.shape}, k={k}")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shapes ({m},{k},{n}) not multiples of block {block}")

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // _LANES_PER_WORD, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(a, w_packed)


# ---------------------------------------------------------------------------
# Decode: a few rows against the whole packed weight
# ---------------------------------------------------------------------------

#: Words of K in one block; longer K is cut into lane-aligned slabs of this.
_DECODE_MAX_BKW = 512
#: Bytes of packed weight in one block (double-buffered by the pipeline).
_DECODE_W_BLOCK_BYTES = 1 << 20
_DECODE_BN = (1024, 512, 256)
_BITS_PER_BYTE = 8


def decode_block(m: int, kw: int, n: int):
    """``(bm, bn, bkw)`` for :func:`decode_qmm` at ``(m, kw, n)``; the caller
    pads M, Kw and N to multiples.

    ``bm`` is M rounded up to the int8 sublane tile (32).  ``bkw`` is the
    whole packed K up to 512 words (K = 16384), else 512.  ``bn`` is the
    widest of 1024/512/256 that divides N (rounded up to 128) and keeps a
    weight block within 1 MiB, else 128.  granite-8b's ``ffn.gate`` (Kw 128,
    N 14336) gets 14 steps of (128, 1024) words; ``ffn.down`` (Kw 448, N
    4096) 8 of (448, 512).
    """
    bm = -(-m // 32) * 32
    bkw = min(kw, _DECODE_MAX_BKW)
    n128 = -(-n // 128) * 128
    fits = [c for c in _DECODE_BN if n128 % c == 0 and 4 * bkw * c <= _DECODE_W_BLOCK_BYTES]
    return bm, (fits[0] if fits else 128), bkw


def _decode_kernel(a_ref, wp_ref, o_ref):
    """One (bm, bn) output tile x one bkw-word slice of K.

    Shifting the packed words right by ``b`` and masking with 0x01010101
    leaves bit ``b`` of each of their four bytes in that byte's low bit;
    read as int8 (``pltpu.bitcast``, byte ``beta`` of word row ``r`` to row
    ``4r + beta``), that is a (4*bkw, bn) slab of {0,1} weights whose row
    ``i`` is ``k = 8i + b``.  Eight slabs cover the slice, each one shift
    and one mask per word, so every op unpacks four weights; ``a_ref[b]``
    holds the activation columns ``8i + b`` to meet slab ``b`` on the MXU.
    No slab outlives its dot, so the u32 temporary is one block of words.
    """

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def plane(b, acc):
        bits = (wp_ref[...] >> b.astype(jnp.uint32)) & jnp.uint32(0x01010101)
        return acc + jax.lax.dot_general(
            a_ref[b],
            pltpu.bitcast(bits, jnp.int8),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    o_ref[...] += jax.lax.fori_loop(
        0, _BITS_PER_BYTE, plane, jnp.zeros(o_ref.shape, jnp.int32), unroll=True
    )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def decode_qmm(
    a_planes: jax.Array,
    w_packed: jax.Array,
    *,
    block,
    interpret: bool = False,
) -> jax.Array:
    """Integer MM ``a @ unpack(w_packed)`` for decode's few rows.

    Args:
      a_planes: int8 ``(8, M, 4*Kw)``, ``a_planes[b, m, i] = a[m, 8i + b]``
        (``ops.decode_qmm_int`` lays the activation out so).
      w_packed: uint32 ``(Kw, N)`` bit-packed binary weight mantissas.
      block: (bm, bn, bkw) from :func:`decode_block`; M, N, Kw multiples.
      interpret: run the kernel body in Python (CPU validation mode).

    Returns:
      int32 ``(M, N)``.
    """
    _, m, k4 = a_planes.shape
    kw, n = w_packed.shape
    bm, bn, bkw = block
    if k4 != 4 * kw:
        raise ValueError(f"packed-K mismatch: {a_planes.shape} vs {w_packed.shape}")
    if m != bm or n % bn or kw % bkw:
        raise ValueError(f"shapes (M {m}, Kw {kw}, N {n}) do not fit block {block}")
    return pl.pallas_call(
        _decode_kernel,
        grid=(n // bn, kw // bkw),
        in_specs=[
            pl.BlockSpec((_BITS_PER_BYTE, bm, 4 * bkw), lambda j, kk: (0, 0, kk)),
            pl.BlockSpec((bkw, bn), lambda j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
    )(a_planes, w_packed)


# ---------------------------------------------------------------------------
# Routed experts: tiles of rows, each against its expert's packed words
# ---------------------------------------------------------------------------
#
# ``expert_decode_qmm`` is the grouped sibling of ``decode_qmm`` for routed
# experts: rows come in tiles of ``EXPERT_TILE_ROWS``, each tile routed to
# one expert of a stacked ``(E, Kw, N)`` packed weight (every layer's
# experts at once, ``(L * E, Kw, N)``, where a scan over layers reads its
# layer's in place: ``ops.expert_decode_qmm_int``).  The tiles' expert ids
# and the number of tiles in use arrive as scalar prefetch, so each grid
# step's ``index_map`` fetches only its tile's expert; consecutive tiles of
# one expert, and the unused tail, keep the block already in VMEM.

#: Rows of one tile of :func:`expert_decode_qmm`.  Decode routes a token's few
#: rows to each of its experts, so a tile of 8 wastes the least padding.
EXPERT_TILE_ROWS = 8


def expert_block(kw: int, n: int):
    """``(bn, bkw)`` for :func:`expert_decode_qmm` over one expert's
    ``(Kw, N)`` words; the caller pads N and Kw to multiples.

    ``bkw`` as in :func:`decode_block`.  ``bn`` is the whole N (rounded up
    to 128) where a block of words stays within 1 MiB, so that one DMA
    brings a whole expert: deepseek-v2-lite's gate and up (Kw 64, N 1408)
    and down (Kw 48, padded from 44, N 2048) take one step per tile.  Else
    the widest of 1024/512/256 that divides N and fits, else 128.
    """
    bkw = min(kw, _DECODE_MAX_BKW)
    n128 = -(-n // 128) * 128
    if 4 * bkw * n128 <= _DECODE_W_BLOCK_BYTES:
        return n128, bkw
    fits = [c for c in _DECODE_BN if n128 % c == 0 and 4 * bkw * c <= _DECODE_W_BLOCK_BYTES]
    return (fits[0] if fits else 128), bkw


def _expert_kernel(tile_expert_ref, n_tiles_ref, a_ref, wp_ref, o_ref):
    """One tile of rows x one (bkw, bn) block of its expert's words, unpacked
    a byte plane at a time as in ``_decode_kernel``; a tile past ``n_tiles``
    (unused) is left zero."""
    del tile_expert_ref  # read by the index maps

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    def plane(b, acc):
        bits = (wp_ref[...] >> b.astype(jnp.uint32)) & jnp.uint32(0x01010101)
        return acc + jax.lax.dot_general(
            a_ref[b],
            pltpu.bitcast(bits, jnp.int8),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )

    @pl.when(pl.program_id(0) < n_tiles_ref[0])
    def _mac():
        o_ref[...] += jax.lax.fori_loop(
            0, _BITS_PER_BYTE, plane, jnp.zeros(o_ref.shape, jnp.int32), unroll=True
        )


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def expert_decode_qmm(
    tile_expert: jax.Array,
    n_tiles: jax.Array,
    a_planes: jax.Array,
    w_packed: jax.Array,
    *,
    block,
    interpret: bool = False,
) -> jax.Array:
    """Integer MM of each tile of rows with its expert's unpacked words.

    Args:
      tile_expert: int32 ``(n_max,)``, the expert of each tile; tiles past
        ``n_tiles`` repeat the last expert in use (no new fetch).
      n_tiles: int32 ``(1,)``, the tiles in use.
      a_planes: int8 ``(8, n_max * bm, 4*Kw)`` in ``decode_qmm``'s layout,
        ``bm`` = :data:`EXPERT_TILE_ROWS` rows a tile.
      w_packed: uint32 ``(E, Kw, N)`` bit-packed binary weight mantissas,
        ``tile_expert`` indexing the leading axis.
      block: (bn, bkw) from :func:`expert_block`; N and Kw multiples.
      interpret: run the kernel body in Python (CPU validation mode).

    Returns:
      int32 ``(n_max * bm, N)``; rows of unused tiles are zero.
    """
    _, rows, k4 = a_planes.shape
    _, kw, n = w_packed.shape
    bn, bkw = block
    bm = EXPERT_TILE_ROWS
    n_max = tile_expert.shape[0]
    if k4 != 4 * kw or rows != n_max * bm:
        raise ValueError(f"shapes {a_planes.shape}, {w_packed.shape} do not fit {n_max} tiles")
    if n % bn or kw % bkw:
        raise ValueError(f"shapes (Kw {kw}, N {n}) do not fit block {block}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_max, n // bn, kw // bkw),
        in_specs=[
            pl.BlockSpec((_BITS_PER_BYTE, bm, 4 * bkw), lambda t, j, kk, te, nt: (0, t, kk)),
            pl.BlockSpec((pl.Squeezed(), bkw, bn), lambda t, j, kk, te, nt: (te[t], kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda t, j, kk, te, nt: (t, j)),
    )
    return pl.pallas_call(
        _expert_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.int32),
        interpret=interpret,
    )(tile_expert, n_tiles, a_planes, w_packed)

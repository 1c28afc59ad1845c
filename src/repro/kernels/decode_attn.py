"""Pallas TPU kernel: single-token decode attention over the int8 GQA cache.

The decode branch of ``models.attention._attention_core`` scores one query
token per slot against that slot's cache.  The XLA chain (``_scores_int`` ->
mask -> softmax -> ``_pv_int``) scores and reduces every one of the cache's
``max_len`` rows in every layer of every tick, and sums K's rows again each
time.  This kernel reads each slot's K and V rows once, and only up to the
slot's last live row; everything else happens in VMEM, with the same
arithmetic:

* scores: the int8 x int8 dot with int32 accumulation, then the affine
  epilogue of ``_scores_int`` (q's per-slot grid, the cache's per-slot
  ``(k_scale, k_offset + 128 k_scale)``, q's and K's row sums), ``/ sqrt(dh)``
  and rows past the slot's position masked to ``-1e30``;
* an exact float32 softmax over the slot's live rows (the scores of every
  live block are held in VMEM until the last one);
* the probabilities on ``_pv_int``'s grid, ``pm = clip(round(255 p), 0, 255)``
  and ``x = pm - 128`` as int8, then the int8 x int8 PV dot with int32
  accumulation and ``_pv_int``'s epilogue.

Rows past a slot's position have ``p = 0`` exactly, and in ``_pv_int``'s
epilogue their terms cancel exactly; so the sums run over the live rows only
(the row count ``t`` of that epilogue is the live count).  Only float32
rounding differs from the XLA chain.

Layout.  The cache ``(B, T, kvH, dh)`` is read as it is stored: one grid step
takes a ``(bt, kvH, dh)`` block of a slot's rows, all kv heads at once, and
views it as ``(bt * kvH, dh)`` (row ``t * kvH + h``).  Every query head is
scored against every (row, kv head) pair on the MXU, and the pairs of other
kv heads are masked like dead rows; the MXU has the room, and no head is
sliced out of the block (on a v5e, reading each head out of the block with
strided loads took about twice as long).  The row sums of K come from the
same dot, through a row of ones appended to q (``q_aug``); those of V from
the PV dot, through the live-row indicator appended to the quantized
probabilities.

Grid: ``(B, T / bt)``, the slot's last live block given by its position
(scalar prefetch).  The index maps clamp each slot's block index to that
block, so blocks past it are never copied, and ``pl.when`` skips their
steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attn", "block_rows", "fits", "vmem_bytes", "VMEM_BUDGET"]

_NEG_INF = -1e30
#: Rows of K and V one grid step reads (fewer where the cache is shorter).
_BLOCK_ROWS = 512
#: int8 sublane tile: q's and the probabilities' row counts are padded to it.
_I8_ROWS = 32
#: VMEM the kernel may hold (``vmem_bytes``): granite-8b at 4096 rows takes 11 MiB.
VMEM_BUDGET = 24 << 20


def block_rows(t: int) -> int:
    """Rows of a slot's cache one grid step reads: 512, or the whole ``t``."""
    return min(t, _BLOCK_ROWS)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def vmem_bytes(t: int, kvh: int, dh: int, h: int) -> int:
    """VMEM the kernel holds at a cache of ``t`` rows: the live blocks' scores
    (float32) and V rows (int8), and the double-buffered K and V blocks and
    head mask (int32)."""
    hp = _round_up(h, _I8_ROWS)
    bt = block_rows(t)
    return t * kvh * (4 * hp + dh) + 4 * bt * kvh * dh + 8 * hp * bt * kvh


def fits(t: int, kvh: int, dh: int, h: int) -> bool:
    """Does a cache of ``t`` rows fit the kernel: whole blocks of rows whose
    score columns fill lanes, within :data:`VMEM_BUDGET`?"""
    bt = block_rows(t)
    return t % bt == 0 and (bt * kvh) % 128 == 0 and vmem_bytes(t, kvh, dh, h) <= VMEM_BUDGET


def _kernel(
    pos_ref, aff_ref, q_ref, own_ref, k_ref, v_ref, o_ref, s_scr, v_scr, *, n_heads, bt, scale
):
    b = pl.program_id(0)
    j = pl.program_id(1)
    _, kvh, dh = k_ref.shape
    hp, n = own_ref.shape
    pos = jnp.minimum(pos_ref[b], s_scr.shape[0] * bt - 1)
    last = pos // bt

    def aff(i):
        return aff_ref[b, i]

    def own_live(blk):
        """(hp, n): query head i meets column c = t * kvH + h of block ``blk``
        where h is i's kv head and row t holds a token."""
        col = jax.lax.broadcasted_iota(jnp.int32, (hp, n), 1)
        return (own_ref[...] != 0) & (col < (pos - blk * bt + 1) * kvh)

    @pl.when(j <= last)
    def _scores():
        k2 = k_ref[...].reshape(n, dh)
        s = jax.lax.dot_general(
            q_ref[...], k2, (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32
        )  # (hq, n): q rows, then K's row sums (row n_heads)
        xy = s[:hp].astype(jnp.float32)
        ksum = s[n_heads : n_heads + 1].astype(jnp.float32)
        qsum = jnp.sum(q_ref[:hp].astype(jnp.int32), axis=1, keepdims=True).astype(jnp.float32)
        sc = xy * aff(0) + aff(1) * qsum + aff(2) * ksum + aff(3) * jnp.float32(dh)
        sc = sc / scale
        s_scr[j] = jnp.where(own_live(j), sc, _NEG_INF)
        v_scr[j] = v_ref[...]

    @pl.when(j == last)
    def _softmax_pv():
        m = jax.lax.fori_loop(
            0, last + 1,
            lambda i, m: jnp.maximum(m, jnp.max(s_scr[i], axis=1, keepdims=True)),
            jnp.full((hp, 1), _NEG_INF, jnp.float32),
        )
        den = jax.lax.fori_loop(
            0, last + 1,
            lambda i, d: d + jnp.sum(jnp.exp(s_scr[i] - m), axis=1, keepdims=True),
            jnp.zeros((hp, 1), jnp.float32),
        )

        def pv(i, carry):
            acc, xsum = carry
            p = jnp.exp(s_scr[i] - m) / den
            pm = jnp.clip(jnp.round(p * 255.0), 0.0, 255.0)
            live = own_live(i)
            x = jnp.where(live, pm.astype(jnp.int32) - 128, 0)
            xa = jnp.concatenate([x, live.astype(jnp.int32)]).astype(jnp.int8)
            acc = acc + jax.lax.dot_general(
                xa, v_scr[i].reshape(n, dh), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            return acc, xsum + jnp.sum(x, axis=1, keepdims=True)

        acc, xsum = jax.lax.fori_loop(
            0, last + 1, pv,
            (jnp.zeros((2 * hp, dh), jnp.int32), jnp.zeros((hp, 1), jnp.int32)),
        )
        xy = acc[:hp].astype(jnp.float32)
        vsum = acc[hp:].astype(jnp.float32)
        t_live = (pos + 1).astype(jnp.float32)
        out = xy * aff(4) + aff(5) * xsum.astype(jnp.float32) + aff(6) * vsum + aff(7) * t_live
        o_ref[...] = out


@functools.partial(jax.jit, static_argnames=("n_heads", "block_t", "interpret"))
def decode_attn(
    pos: jax.Array,
    aff: jax.Array,
    q_aug: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    n_heads: int,
    block_t: int,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention of one query token per slot over its int8 cache.

    Args:
      pos: int32 ``(B,)``, each slot's last live row (the row just written).
      aff: float32 ``(B, 8)``, each slot's epilogue constants: for QK^T
        ``a1*a2, a1*g2, g1*a2, g1*g2`` with q's grid ``(a1, g1)`` and the K
        cache's ``(a2, g2)``, then the same four for PV with the
        probabilities' grid ``(1/255, 128/255)`` and the V cache's.
      q_aug: int8 ``(B, HQ, dh)``: rows ``0..n_heads-1`` q's re-centered
        mantissas, row ``n_heads`` ones, the rest zero; ``HQ`` a multiple of 32
        above ``n_heads``.
      k, v: int8 ``(B, T, kvH, dh)`` re-centered cache mantissas.
      n_heads: query heads, a multiple of ``kvH``.
      block_t: rows of a slot's K and V one grid step reads (``block_rows``);
        ``T`` a multiple.
      interpret: run the kernel body in Python (CPU validation mode).

    Returns:
      float32 ``(B, HP, dh)``, the context of each query head in rows
      ``0..n_heads-1`` (``HP`` is ``n_heads`` rounded up to 32).
    """
    bsz, t, kvh, dh = k.shape
    hq = q_aug.shape[1]
    hp = _round_up(n_heads, _I8_ROWS)
    bt = block_t
    if t % bt or (bt * kvh) % 128 or hq % _I8_ROWS or hq <= n_heads or n_heads % kvh:
        raise ValueError(f"shapes q {q_aug.shape}, cache {k.shape} do not fit {n_heads} heads")

    # query head i reads kv head i // (n_heads / kvH): column t * kvH + h
    col = np.arange(bt * kvh)[None, :]
    head = np.arange(hp)[:, None]
    own = (col % kvh == head // (n_heads // kvh)) & (head < n_heads)

    def kv_block(b, j, pos_ref, aff_ref):
        del aff_ref
        last = jnp.minimum(pos_ref[b], t - 1) // bt
        return b, jnp.minimum(j, last), 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, t // bt),
        in_specs=[
            pl.BlockSpec((None, hq, dh), lambda b, j, p, a: (b, 0, 0)),
            pl.BlockSpec((hp, bt * kvh), lambda b, j, p, a: (0, 0)),
            pl.BlockSpec((None, bt, kvh, dh), kv_block),
            pl.BlockSpec((None, bt, kvh, dh), kv_block),
        ],
        out_specs=pl.BlockSpec((None, hp, dh), lambda b, j, p, a: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((t // bt, hp, bt * kvh), jnp.float32),
            pltpu.VMEM((t // bt, bt, kvh, dh), jnp.int8),
        ],
    )
    scale = float(np.sqrt(np.float32(dh)))
    return pl.pallas_call(
        functools.partial(_kernel, n_heads=n_heads, bt=bt, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, hp, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(t, kvh, dh, n_heads) + (32 << 20),
        ),
        name="decode_attn",
        interpret=interpret,
    )(pos, aff, q_aug, jnp.asarray(own, jnp.int32), k, v)

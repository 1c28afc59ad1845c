"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Each kernel is swept over shapes (block-aligned and ragged via the ops
wrappers) and operand widths, asserting bit-exact integer agreement with
ref.py, plus end-to-end QuantTensor dispatch against the dequantized oracle.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import flow_abstraction as FA
from repro.core import packing
from repro.core import quantization as Q
from repro.kernels import binary_qmm as BK
from repro.kernels import bitserial_qmm as BS
from repro.kernels import popcount_qmm as PK
from repro.kernels import ops, ref


RNG = np.random.default_rng(1234)


# ---------------------------------------------------------------------------
# binary_qmm: fused unpack -> MXU int8 dot
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "m,k,n",
    [(128, 512, 128), (256, 512, 128), (128, 1024, 256)],
)
def test_binary_qmm_block_aligned(m, k, n):
    a = RNG.integers(-128, 128, size=(m, k)).astype(np.int8)
    w = RNG.integers(0, 2, size=(k, n)).astype(np.int32)
    wp = packing.pack_bits(jnp.asarray(w), 1, axis=0)
    out = BK.binary_qmm(jnp.asarray(a), wp, k=k, interpret=True)
    expect = ref.binary_qmm_ref(jnp.asarray(a), wp, k)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    np.testing.assert_array_equal(np.asarray(out), a.astype(np.int64) @ w)


@pytest.mark.parametrize("m,k,n", [(1, 32, 1), (37, 300, 45), (130, 513, 129)])
def test_binary_qmm_ragged_via_ops(m, k, n):
    """ops wrapper pads ragged shapes; zero-padding must be exact."""
    a = RNG.integers(-8, 8, size=(m, k)).astype(np.int8)
    w = RNG.integers(0, 2, size=(k, n)).astype(np.int32)
    wp = packing.pack_bits(jnp.asarray(w), 1, axis=0)
    out = ops.binary_qmm_int(jnp.asarray(a), wp, k, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), a.astype(np.int64) @ w)


def test_binary_qmm_rejects_bad_shapes():
    a = jnp.zeros((64, 512), jnp.int8)
    wp = jnp.zeros((16, 128), jnp.uint32)
    with pytest.raises(ValueError):
        BK.binary_qmm(a, wp, k=512, interpret=True)  # 64 % 128 != 0


# ---------------------------------------------------------------------------
# popcount_qmm: the DPU analogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(64, 2048, 128), (128, 4096, 256)])
def test_popcount_qmm_block_aligned(m, k, n):
    a = RNG.integers(0, 2, size=(m, k)).astype(np.int32)
    b = RNG.integers(0, 2, size=(k, n)).astype(np.int32)
    ap = packing.pack_bits(jnp.asarray(a), 1, axis=-1)
    bp = packing.pack_bits(jnp.asarray(b), 1, axis=0)
    out = PK.popcount_qmm(ap, bp, interpret=True)
    expect = ref.popcount_qmm_ref(ap, bp, k)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    np.testing.assert_array_equal(np.asarray(out), a @ b)


@pytest.mark.parametrize("m,k,n", [(5, 64, 3), (70, 1000, 140)])
def test_popcount_qmm_ragged_via_ops(m, k, n):
    a = RNG.integers(0, 2, size=(m, k)).astype(np.int32)
    b = RNG.integers(0, 2, size=(k, n)).astype(np.int32)
    ap = packing.pack_bits(jnp.asarray(a), 1, axis=-1)
    bp = packing.pack_bits(jnp.asarray(b), 1, axis=0)
    out = ops.popcount_qmm_int(ap, bp, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), a @ b)


def test_popcount_qmm_several_k_blocks_via_ops():
    """Kw > 128 words: padded to 128-word lane-aligned blocks, accumulated
    across the K grid (the granite-8b ffn.down regime, K=14336)."""
    m, k, n = 9, 8320, 40  # 260 words -> 3 blocks of 128
    a = RNG.integers(0, 2, size=(m, k)).astype(np.int32)
    b = RNG.integers(0, 2, size=(k, n)).astype(np.int32)
    ap = packing.pack_bits(jnp.asarray(a), 1, axis=-1)
    bp = packing.pack_bits(jnp.asarray(b), 1, axis=0)
    out = ops.popcount_qmm_int(ap, bp, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), a @ b)


@pytest.mark.parametrize(
    "shape,block",
    [((8, 256, 128), (8, 128, 64)),  # bkw neither 128-aligned nor whole Kw
     ((16, 64, 128), (12, 128, 64)),  # bm not a sublane multiple
     ((8, 64, 256), (8, 64, 64))],  # bn not a lane multiple
)
def test_popcount_qmm_rejects_blocks_mosaic_cannot_tile(shape, block):
    m, kw, n = shape
    a = jnp.zeros((m, kw), jnp.uint32)
    b = jnp.zeros((kw, n), jnp.uint32)
    with pytest.raises(ValueError):
        PK.popcount_qmm(a, b, block=block, interpret=True)


# ---------------------------------------------------------------------------
# bitserial_qmm: multi-bit act x act
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("a_bits,b_bits", [(2, 2), (4, 4), (4, 8), (8, 8)])
def test_bitserial_qmm_block_aligned(a_bits, b_bits):
    m, k, n = 64, 1024, 128
    a = RNG.integers(0, 2**a_bits, size=(m, k)).astype(np.int32)
    b = RNG.integers(0, 2**b_bits, size=(k, n)).astype(np.int32)
    apl = packing.pack_bitplanes(jnp.asarray(a), a_bits, axis=-1)
    bpl = packing.pack_bitplanes(jnp.asarray(b), b_bits, axis=-2)
    out = BS.bitserial_qmm(apl, bpl, interpret=True)
    expect = ref.bitserial_qmm_ref(apl, bpl, k)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))
    np.testing.assert_array_equal(np.asarray(out), a @ b)


def test_bitserial_qmm_several_k_blocks_via_ops():
    m, k, n = 8, 4500, 24  # 141 words -> 2 blocks of 128
    a = RNG.integers(0, 16, size=(m, k)).astype(np.int32)
    b = RNG.integers(0, 4, size=(k, n)).astype(np.int32)
    apl = packing.pack_bitplanes(jnp.asarray(a), 4, axis=-1)
    bpl = packing.pack_bitplanes(jnp.asarray(b), 2, axis=-2)
    out = ops.bitserial_qmm_int(apl, bpl, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), a @ b)


def test_bitserial_qmm_ragged_via_ops():
    m, k, n = 33, 190, 77
    a = RNG.integers(0, 16, size=(m, k)).astype(np.int32)
    b = RNG.integers(0, 16, size=(k, n)).astype(np.int32)
    apl = packing.pack_bitplanes(jnp.asarray(a), 4, axis=-1)
    bpl = packing.pack_bitplanes(jnp.asarray(b), 4, axis=-2)
    out = ops.bitserial_qmm_int(apl, bpl, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), a @ b)


# ---------------------------------------------------------------------------
# end-to-end dispatch: QuantTensor in, flow-abstraction epilogue out
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("act_bits", [1, 2, 4, 8])
def test_qmm_pallas_act_weight_matches_oracle(act_bits):
    x = jnp.asarray(RNG.normal(size=(37, 300)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(300, 45)).astype(np.float32))
    xq = Q.quantize_activation(x, act_bits)
    wq = Q.binarize_weight(w)
    expect = FA.qmm_dequant_reference(xq, wq)
    out = ops.qmm_pallas(xq, wq, interpret=True)
    tol = 3e-5 * max(1.0, float(jnp.max(jnp.abs(expect))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=tol)


@pytest.mark.parametrize("act_bits", [2, 4, 8])
def test_qmm_pallas_act_act_matches_oracle(act_bits):
    a = jnp.asarray(RNG.normal(size=(20, 75)).astype(np.float32))
    b = jnp.asarray(RNG.normal(size=(75, 30)).astype(np.float32))
    aq = Q.quantize_activation(a, act_bits)
    bq = Q.quantize_activation(b, act_bits)
    expect = FA.qmm_dequant_reference(aq, bq)
    out = ops.qmm_pallas(aq, bq, interpret=True)
    tol = 3e-4 * max(1.0, float(jnp.max(jnp.abs(expect))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=tol)


def test_qmm_pallas_packed_weights():
    """Serving layout: weights arrive packed from the checkpoint."""
    x = jnp.asarray(RNG.normal(size=(16, 256)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(256, 64)).astype(np.float32))
    xq = Q.quantize_activation(x, 4)
    wq = Q.binarize_weight(w).pack(axis=0)
    expect = FA.qmm_dequant_reference(Q.quantize_activation(x, 4), Q.binarize_weight(w))
    out = ops.qmm_pallas(xq, wq, interpret=True)
    tol = 3e-5 * max(1.0, float(jnp.max(jnp.abs(expect))))
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=tol)


def test_qmm_pallas_agrees_with_mxu_backend():
    from repro.core import qmm as QE

    x = jnp.asarray(RNG.normal(size=(24, 200)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(200, 40)).astype(np.float32))
    xq = Q.quantize_activation(x, 4)
    wq = Q.binarize_weight(w)
    a = QE.qmm(xq, wq, backend="mxu")
    b = ops.qmm_pallas(xq, wq, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4)

"""Fused bit-serial QMM kernel: exact parity vs the ref oracle.

The exactness contract (see ``kernels/fused_qmm.py``): the integer core is
bit-exact always; the fp32 epilogue is bit-exact whenever its arithmetic is
exact, which the *dyadic* fixtures guarantee — power-of-two scales with
offsets that are dyadic multiples of them (``offset = -scale * 2**(bits-1)``,
the symmetric-quantizer shape).  Under those coefficients every epilogue term
is exactly representable, so fma-vs-mul/add compilation differences cannot
appear and ``assert_array_equal`` is the right assertion.  Real quantizer
scales are checked separately to float tolerance.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import flow_abstraction as FA
from repro.core import packing
from repro.core import qmm as QE
from repro.core import quantization as Q
from repro.core.quantization import QuantTensor
from repro.kernels import ops, ref

RNG = np.random.default_rng(17)

# tile-aligned, ragged-everything, tiny, mid-size K-ragged, and several
# 128-word K blocks (Kw = 141 words padded to 256)
SHAPES = [(64, 512, 128), (37, 300, 45), (5, 64, 3), (16, 96, 24), (8, 4500, 16)]
# W1A1, W1A8, W1A4, A8xA8, A4xA4
PRECISIONS = [(1, 1), (8, 1), (4, 1), (8, 8), (4, 4)]


def _dyadic_qt(shape, bits, scale_shape):
    """QuantTensor with dyadic coefficients: the bit-exact fixture."""
    mant = RNG.integers(0, 2**bits, size=shape).astype(
        np.uint8 if bits <= 8 else np.int32
    )
    exps = RNG.integers(-4, 3, size=scale_shape)
    scale = (2.0**exps).astype(np.float32)
    offset = (-scale * (2 ** (bits - 1))).astype(np.float32)
    return QuantTensor(
        mantissa=jnp.asarray(mant),
        scale=jnp.asarray(scale),
        offset=jnp.asarray(offset),
        bits=bits,
    )


def _oracle(x, w, m, k, n):
    """ref.fused_qmm_ref over the same planes/coefficients ops.qmm_fused uses."""
    a_planes = packing.pack_bitplanes(
        x.unpack(dtype=jnp.int32).mantissa.astype(jnp.uint32), x.bits, axis=-1
    )
    b_planes = packing.pack_bitplanes(
        w.unpack(dtype=jnp.int32).mantissa.astype(jnp.uint32), w.bits, axis=-2
    )
    f32 = jnp.float32
    return ref.fused_qmm_ref(
        a_planes,
        b_planes,
        jnp.broadcast_to(jnp.asarray(x.scale, f32), (m, 1)),
        jnp.broadcast_to(jnp.asarray(x.offset, f32), (m, 1)),
        jnp.broadcast_to(jnp.asarray(w.scale, f32), (1, n)),
        jnp.broadcast_to(jnp.asarray(w.offset, f32), (1, n)),
        k,
    )


# ---------------------------------------------------------------------------
# exact parity vs the oracle (dyadic coefficients -> bit-exact, all modes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", SHAPES)
@pytest.mark.parametrize("act_bits,weight_bits", PRECISIONS)
def test_fused_matches_oracle_bit_exact(m, k, n, act_bits, weight_bits):
    x = _dyadic_qt((m, k), act_bits, (m, 1))  # per-token dyadic scales
    w = _dyadic_qt((k, n), weight_bits, (1, n))  # per-channel dyadic scales
    got = ops.qmm_fused(x, w)
    want = _oracle(x, w, m, k, n)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_fused_packed_weight_serving_path_bit_exact():
    """1-bit weights arrive pre-packed (with a precomputed colsum) from
    ``pack_linear_for_serving``; the kernel consumes the packed planes
    directly and ignores the colsum — still bit-exact vs the oracle."""
    m, k, n = 37, 300, 45
    x = _dyadic_qt((m, k), 8, (m, 1))
    w = _dyadic_qt((k, n), 1, (1, n))
    want = _oracle(x, w, m, k, n)
    colsum = FA.weight_corrections(w)
    wp = w.pack(axis=0)
    got = ops.qmm_fused(x, wp, w_colsum=colsum)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a *wrong* colsum must not change anything: it is computed in-kernel
    got2 = ops.qmm_fused(x, wp, w_colsum=colsum + 999)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(want))


def test_fused_integer_core_is_exact_mantissa_matmul():
    """With scale=1, offset=0 the output *is* the integer MM — exact."""
    m, k, n, bits = 16, 200, 24, 8
    a = RNG.integers(0, 2**bits, size=(m, k)).astype(np.int64)
    b = RNG.integers(0, 2**bits, size=(k, n)).astype(np.int64)
    one = lambda s: jnp.ones(s, jnp.float32)  # noqa: E731
    x = QuantTensor(
        mantissa=jnp.asarray(a.astype(np.uint8)),
        scale=one((m, 1)),
        offset=jnp.zeros((m, 1), jnp.float32),
        bits=bits,
    )
    w = QuantTensor(
        mantissa=jnp.asarray(b.astype(np.uint8)),
        scale=one((1, n)),
        offset=jnp.zeros((1, n), jnp.float32),
        bits=bits,
    )
    got = ops.qmm_fused(x, w)
    np.testing.assert_array_equal(
        np.asarray(got), (a @ b).astype(np.float32)
    )


# ---------------------------------------------------------------------------
# registry wiring + real quantizer scales
# ---------------------------------------------------------------------------


def test_fused_dispatches_through_qmm_backend_kwarg():
    m, k, n = 16, 96, 24
    x = _dyadic_qt((m, k), 4, (m, 1))
    w = _dyadic_qt((k, n), 1, (1, n))
    np.testing.assert_array_equal(
        np.asarray(QE.qmm(x, w, backend="fused")),
        np.asarray(ops.qmm_fused(x, w)),
    )


@pytest.mark.parametrize("act_bits,weight_bits", [(1, 1), (8, 1), (8, 8)])
def test_fused_real_quantizer_scales_match_mxu(act_bits, weight_bits):
    """Arbitrary (non-dyadic) scales: agreement to fp32 tolerance — the
    integer core is still exact; only the epilogue rounding may differ."""
    m, k, n = 24, 160, 20
    xf = jnp.asarray(RNG.standard_normal((m, k)).astype(np.float32))
    wf = jnp.asarray(RNG.standard_normal((k, n)).astype(np.float32))
    xq = Q.quantize_activation(xf, act_bits)
    wq = (
        Q.quantize_weight(wf, weight_bits)
        if weight_bits == 1
        else Q.quantize_activation(wf, weight_bits)
    )
    got = QE.qmm(xq, wq, backend="fused")
    want = QE.qmm(xq, wq, backend="mxu")
    tol = 3e-5 * max(1.0, float(jnp.max(jnp.abs(want))))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol)


def test_fused_out_dtype_cast():
    m, k, n = 8, 64, 16
    x = _dyadic_qt((m, k), 4, (m, 1))
    w = _dyadic_qt((k, n), 1, (1, n))
    out = ops.qmm_fused(x, w, out_dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16


def test_fused_rejects_non_rank2():
    x3 = QuantTensor(
        mantissa=jnp.zeros((2, 8, 64), jnp.uint8),
        scale=jnp.float32(1.0),
        offset=jnp.float32(0.0),
        bits=8,
    )
    w = _dyadic_qt((64, 16), 1, (1, 16))
    with pytest.raises(ValueError, match="rank-2"):
        ops.qmm_fused(x3, w)

"""The ``mxu`` backend's decode core: packed 1-bit weights unpacked in VMEM.

At decode's few rows on a TPU the ``mxu`` backend hands the packed weight
words to ``binary_qmm.decode_qmm`` instead of unpacking them to K x N int8
through HBM.  The integer product is exact, so it must equal the XLA path's
bit for bit, and the shared flow epilogue must then give the same output.
On the CPU the kernel runs interpreted; the tests force the platform check
(``ops.on_tpu``) where they need the TPU's choice.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config
from repro.configs.base import QuantConfig
from repro.configs.smoke import smoke_variant
from repro.core import flow_abstraction as FA
from repro.core import packing, site_log
from repro.core import qmm as QE
from repro.kernels import binary_qmm as BK
from repro.kernels import ops
from repro.models import layers as L
from repro.models import model_zoo as Z

RNG = np.random.default_rng(1414)


@pytest.fixture
def tpu_choice(monkeypatch):
    """Make the backends choose as on a TPU (kernels still interpreted)."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        yield


def _operands(m, k, n):
    a = jnp.asarray(RNG.integers(-128, 128, size=(m, k)), jnp.int8)
    w = jnp.asarray(RNG.integers(0, 2, size=(k, n)), jnp.int32)
    return a, packing.pack_bits(w, 1, axis=0)


@pytest.mark.parametrize("m", [1, 8, 16])
@pytest.mark.parametrize("k,n", [(256, 384), (1000, 130), (4096, 256)])
def test_decode_core_equals_xla_mxu_product(m, k, n):
    """int32 product through the kernel == today's jnp ``mxu`` product."""
    a, wp = _operands(m, k, n)
    out = ops.decode_qmm_int(a, wp, interpret=True)
    w8 = packing.unpack_bits(wp, 1, k, axis=0, dtype=jnp.int8)
    expect = FA.default_int_matmul(a, w8, 8, 1)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize(
    "m,kw,n,block",
    [
        (8, 128, 14336, (32, 1024, 128)),  # granite q/gate/up: 14 steps
        (16, 448, 4096, (32, 512, 448)),  # ffn.down: a 917 KiB weight block
        (8, 160, 1024, (32, 1024, 160)),  # nemo k/v
        (64, 1024, 130, (64, 256, 512)),  # K cut into slabs, N padded
    ],
)
def test_decode_block(m, kw, n, block):
    assert BK.decode_block(m, kw, n) == block


def _serve_linear(k, n):
    quant = QuantConfig()
    p = L.pack_linear_for_serving(
        L.init_linear(jax.random.PRNGKey(k + n), k, n), quant
    )
    return p, quant


@pytest.mark.parametrize("m", [1, 8, 16])
def test_qlinear_same_through_both_cores(m, monkeypatch):
    p, quant = _serve_linear(256, 130)
    x = jax.random.normal(jax.random.PRNGKey(m), (m, 256), jnp.float32)
    with site_log.recording() as xla_sites:
        xla = L.qlinear(p, x, quant, "serve", name="ffn.up")
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode(), site_log.recording() as kernel_sites:
        kernel = L.qlinear(p, x, quant, "serve", name="ffn.up")
    assert [s["int_core"] for s in xla_sites] == ["unpacked"]
    assert [s["int_core"] for s in kernel_sites] == ["packed"]
    np.testing.assert_array_equal(np.asarray(kernel), np.asarray(xla))


def _has_pallas_call(closed) -> bool:
    return "pallas_call" in str(closed)


@pytest.mark.parametrize(
    "m,core", [(QE.PACKED_CORE_MAX_ROWS, "packed"), (QE.PACKED_CORE_MAX_ROWS + 1, "unpacked")]
)
def test_rows_above_threshold_take_unpacked_path(m, core, tpu_choice):
    p, quant = _serve_linear(256, 128)
    x = jax.ShapeDtypeStruct((m, 256), jnp.float32)
    with site_log.recording() as sites:
        closed = jax.make_jaxpr(lambda x: L.qlinear(p, x, quant, "serve", name="ffn.up"))(x)
    assert [s["int_core"] for s in sites] == [core]
    assert _has_pallas_call(closed) == (core == "packed")


def test_off_tpu_every_shape_stays_unpacked():
    p, quant = _serve_linear(256, 128)
    x = jax.ShapeDtypeStruct((8, 256), jnp.float32)
    with site_log.recording() as sites:
        closed = jax.make_jaxpr(lambda x: L.qlinear(p, x, quant, "serve", name="ffn.up"))(x)
    assert [s["int_core"] for s in sites] == ["unpacked"]
    assert not _has_pallas_call(closed)


def _site_cores(trace, cfg, sp, cache, tokens):
    with site_log.recording() as sites:
        jax.eval_shape(lambda p, t, c: trace(p, t, cfg, c), sp, tokens, cache)
    return [s for s in sites if s["kind"] == "qlinear"]


@pytest.mark.parametrize("arch", ["granite-8b", "mistral-nemo-12b"])
def test_site_log_names_the_core_of_each_phase(arch, tpu_choice):
    """Every 1-bit qlinear site of a decode trace reads packed words; a
    prefill of more rows than the threshold takes the unpacked path."""
    cfg = smoke_variant(get_config(arch))
    sds = jax.ShapeDtypeStruct
    sp = jax.eval_shape(lambda k: Z.init_serving_params(k, cfg), sds((2,), jnp.uint32))
    cache = jax.eval_shape(lambda: Z.init_cache(8, 128, cfg))
    decode = _site_cores(Z.decode_step, cfg, sp, cache, sds((8,), jnp.int32))
    prefill = _site_cores(Z.prefill, cfg, sp, cache, sds((8, 16), jnp.int32))
    names = {s["site"] for s in decode}
    assert {"attn.q", "attn.k", "attn.v", "attn.o", "ffn.gate", "ffn.up", "ffn.down"} <= names
    assert {s["int_core"] for s in decode} == {"packed"}
    assert {s["int_core"] for s in prefill} == {"unpacked"}

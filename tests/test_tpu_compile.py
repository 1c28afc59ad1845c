"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed even where no TPU is attached, and it compiles
for a described topology.  These tests compile the main serving path at
granite-8b's published widths: each Pallas kernel at the granite-8b FFN
shapes (it must lower to Mosaic — a ``tpu_custom_call`` in the program —
not merely pass in interpret mode), the one-layer-at-a-time weight setup,
and one decode step, whose memory must fit the chip's HBM.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around the compiles (an
entry compiled for a described chip cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.quantization import QuantTensor
from repro.kernels import ops
from repro.models import model_zoo as Z

HBM_BYTES = 15.75e9  # one v5e chip as its compiler reports it
# granite-8b FFN at decode M: ffn.up/gate (K=4096 -> N=14336) and ffn.down
FFN = {"up": (8, 4096, 14336), "down": (8, 14336, 4096)}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(kernel, sharding, m, k, n):
    """(fn, args) for one Pallas kernel at (m, k, n), real (not interpret)."""
    kw = k // 32
    s = lambda shape, dtype: _sds(sharding, shape, dtype)  # noqa: E731
    if kernel == "binary_qmm":  # W1A8 staged: int8 acts x packed weights
        return (
            lambda a, w: ops.binary_qmm_int(a, w, k, interpret=False),
            (s((m, k), jnp.int8), s((kw, n), jnp.uint32)),
        )
    if kernel == "popcount_qmm":  # W1A1
        return (
            lambda a, b: ops.popcount_qmm_int(a, b, interpret=False),
            (s((m, kw), jnp.uint32), s((kw, n), jnp.uint32)),
        )
    if kernel == "bitserial_qmm":  # A8xA8
        return (
            lambda a, b: ops.bitserial_qmm_int(a, b, interpret=False),
            (s((8, m, kw), jnp.uint32), s((8, kw, n), jnp.uint32)),
        )
    # fused_qmm, W1A8 serving layout: packed weights straight from the cache
    x = QuantTensor(s((m, k), jnp.uint8), s((m, 1), jnp.float32),
                    s((m, 1), jnp.float32), bits=8)
    w = QuantTensor(s((kw, n), jnp.uint32), s((1, n), jnp.float32),
                    s((1, n), jnp.float32), bits=1, packed=True, packed_axis=0,
                    length=k)
    return (lambda x, w: ops.qmm_fused(x, w, interpret=False), (x, w))


@pytest.mark.parametrize("site", sorted(FFN))
@pytest.mark.parametrize(
    "kernel", ["binary_qmm", "popcount_qmm", "bitserial_qmm", "fused_qmm"]
)
def test_kernel_lowers_to_mosaic(one_chip, kernel, site):
    fn, args = _kernel_args(kernel, one_chip, *FFN[site])
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        + m.temp_size_in_bytes
        - m.alias_size_in_bytes
    )


def test_granite_serving_params_fit_one_chip(one_chip):
    """The entry point's weight setup: packed model + one latent layer."""
    cfg = get_config("granite-8b")
    key = _sds(one_chip, (2,), jnp.uint32)
    compiled = Z.init_serving_params.lower(key, cfg).compile()
    assert _bytes(compiled) < 0.25 * HBM_BYTES  # the fp32 latent is 32 GB


def test_granite_decode_step_fits_one_chip(one_chip):
    cfg = get_config("granite-8b")
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _sds(one_chip, a.shape, a.dtype), t
    )
    params = place(
        jax.eval_shape(lambda k: Z.init_serving_params(k, cfg), jax.random.PRNGKey(0))
    )
    cache = place(jax.eval_shape(lambda: Z.init_cache(4, 2048, cfg)))
    tokens = _sds(one_chip, (4,), jnp.int32)
    compiled = (
        jax.jit(lambda p, t, c: Z.decode_step(p, t, cfg, c))
        .lower(params, tokens, cache)
        .compile()
    )
    assert _bytes(compiled) < HBM_BYTES

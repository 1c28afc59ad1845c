"""Compile rehearsals for one TPU v5e chip, without the chip.

The TPU compiler is installed even where no TPU is attached, and it compiles
for a described topology.  These tests compile the main serving path at
granite-8b's published widths: each Pallas kernel at the granite-8b FFN
shapes (it must lower to Mosaic — a ``tpu_custom_call`` in the program —
not merely pass in interpret mode), the one-layer-at-a-time weight setup,
and one decode step, whose memory must fit the chip's HBM, whose QMM
sites must read the packed weights in the decode kernel, and whose decode
attention must read the int8 cache as stored, in the ``decode_attn`` kernel.

The routed-expert path gets the same at deepseek-v2-lite's widths: the
grouped expert kernel, and a decode step that holds no unpacked expert
weights.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around the compiles (an
entry compiled for a described chip cannot be read back without one).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
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
    if kernel == "decode_qmm":  # W1A8 at decode's rows, unpacked in VMEM
        return (
            lambda a, w: ops.decode_qmm_int(a, w, interpret=False),
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
    "kernel", ["binary_qmm", "decode_qmm", "popcount_qmm", "bitserial_qmm", "fused_qmm"]
)
def test_kernel_lowers_to_mosaic(one_chip, kernel, site):
    fn, args = _kernel_args(kernel, one_chip, *FFN[site])
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# deepseek-v2-lite's experts at 16 slots x top-6 rows: gate/up (2048 -> 1408) and down
EXPERTS = {"gate": (96, 64, 2048, 1408), "down": (96, 64, 1408, 2048)}


@pytest.mark.parametrize("site", sorted(EXPERTS))
def test_expert_kernel_lowers_to_mosaic(one_chip, site):
    r, e, k, n = EXPERTS[site]

    def grouped(a, row_expert, w):
        return ops.expert_decode_qmm_int(a, ops.expert_tiles(row_expert, e), w, interpret=False)

    args = (
        _sds(one_chip, (r, k), jnp.int8),
        _sds(one_chip, (r,), jnp.int32),
        _sds(one_chip, (e, k // 32, n), jnp.uint32),
    )
    compiled = jax.jit(grouped).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# granite-8b's decode attention: 8 slots over 4096 cache rows, 32/8 heads of 128
ATTN = {"slots": 8, "rows": 4096, "heads": 32, "kv_heads": 8, "dh": 128}


def test_decode_attn_lowers_to_mosaic(one_chip):
    b, t, h, kvh, dh = (ATTN[k] for k in ("slots", "rows", "heads", "kv_heads", "dh"))

    def attend(qm, qs, qo, k, v, ks, ko, vs, vo, pos):
        return ops.decode_attn(qm, qs, qo, k, v, ks, ko, vs, vo, pos, interpret=False)

    f32 = _sds(one_chip, (b,), jnp.float32)
    cache = _sds(one_chip, (b, t, kvh, dh), jnp.int8)
    args = (_sds(one_chip, (b, h, dh), jnp.int8), f32, f32, cache, cache, f32, f32, f32, f32,
            _sds(one_chip, (b,), jnp.int32))
    compiled = jax.jit(attend).lower(*args).compile()
    assert re.search(r"%decode_attn[.\d]* = .*tpu_custom_call", compiled.as_text())


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


@pytest.fixture(scope="module")
def granite_decode(one_chip):
    """granite-8b's decode step at 8 slots x 4096 rows, compiled for the v5e
    with the TPU's choices (``ops.on_tpu`` patched), and its weight shapes."""
    cfg = get_config("granite-8b")
    shapes = jax.eval_shape(
        lambda k: Z.init_serving_params(k, cfg), jax.random.PRNGKey(0)
    )
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _sds(one_chip, a.shape, a.dtype), t
    )
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "on_tpu", lambda: True)  # lowered for the v5e
        cache = place(jax.eval_shape(lambda: Z.init_cache(ATTN["slots"], ATTN["rows"], cfg)))
        compiled = (
            jax.jit(lambda p, t, c: Z.decode_step(p, t, cfg, c))
            .lower(place(shapes), _sds(one_chip, (ATTN["slots"],), jnp.int32), cache)
            .compile()
        )
    return cfg, shapes, compiled


def test_granite_decode_step_unpacks_weights_in_vmem(granite_decode):
    """At 8 slots every QMM site runs the decode kernel on the packed words:
    no u32 broadcast or int8 copy of a site's K x N weight reaches HBM (the
    XLA unpack path's temporaries are 539 MB, its ffn u32 broadcast alone
    235 MB)."""
    cfg, shapes, compiled = granite_decode
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 7  # q k v o gate up down
    d, kv, f = cfg.d_model, cfg.n_kv_heads * cfg.d_head, cfg.d_ff
    smallest_site = min(d * kv, d * f)  # attn.k/v
    stacked = {
        tuple(a.shape) for a in jax.tree.leaves(shapes) if a.dtype == jnp.uint32
    }
    for dims in re.findall(r"u32\[([0-9,]+)\]", text):
        shape = tuple(int(n) for n in dims.split(","))
        if shape not in stacked:  # the packed weights themselves
            assert np.prod(shape) < smallest_site, shape
    assert compiled.memory_analysis().temp_size_in_bytes < 235e6


#: temporaries of the same compile with decode attention in the XLA chain
#: (``_scores_int`` -> mask -> softmax -> ``_pv_int``)
XLA_CHAIN_TEMP_BYTES = 32256


def test_granite_decode_step_reads_cache_in_attention_kernel(granite_decode):
    """Decode attention runs in ``decode_attn`` on the cache as it is stored:
    no K row sums over the whole cache (``s32[8,4096,8]``, the XLA chain's
    ``convert_reduce_fusion``), no copy of the K or V cache relaid out of
    its row-major ``(B, T, kvH, dh)`` layout (the chain laid both out kv
    head first), and no more temporaries than the chain's."""
    _, _, compiled = granite_decode
    text = compiled.as_text()
    b, t, kvh, dh = (ATTN[k] for k in ("slots", "rows", "kv_heads", "dh"))
    assert re.search(r"%decode_attn[.\d]* = .*tpu_custom_call", text)
    assert f"s32[{b},{t},{kvh}]" not in text
    cache = re.escape(f"s8[{b},{t},{kvh},{dh}]")
    layouts = set(re.findall(cache + r"\{([0-9,]+)", text))
    assert layouts == {"3,2,1,0"}, layouts
    assert compiled.memory_analysis().temp_size_in_bytes <= XLA_CHAIN_TEMP_BYTES


def test_deepseek_decode_step_reads_only_packed_experts(one_chip, monkeypatch):
    """deepseek-v2-lite at 16 slots: the routed experts run in the grouped
    kernel, and no array of the compiled step is an unpacked (E, K, N)
    expert weight, int8 or u32, nor a copy of the packed words, one layer's
    (E, K/32, N) sliced out of the stack or the stack relaid whole: the
    kernel reads the stack in place.  The step fits the chip."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)  # lowered for the v5e
    cfg = get_config("deepseek-v2-lite-16b")
    shapes = jax.eval_shape(
        lambda k: Z.init_serving_params(k, cfg), jax.random.PRNGKey(0)
    )
    place = lambda t: jax.tree.map(  # noqa: E731
        lambda a: _sds(one_chip, a.shape, a.dtype), t
    )
    cache = place(jax.eval_shape(lambda: Z.init_cache(16, 4096, cfg)))
    compiled = (
        jax.jit(lambda p, t, c: Z.decode_step(p, t, cfg, c))
        .lower(place(shapes), _sds(one_chip, (16,), jnp.int32), cache)
        .compile()
    )
    text = compiled.as_text()
    assert re.search(r"%expert_decode_qmm[.\d]* = .*tpu_custom_call", text)
    e, d, f = cfg.moe.n_routed, cfg.d_model, cfg.moe.d_expert_ff
    one_expert = d * f
    for dtype, dims in re.findall(r"(u32|s8|s32)\[([0-9,]+)\]", text):
        shape = tuple(int(n) for n in dims.split(","))
        # the packed words of all 26 layers, (26, 64, K/32, N), hold fewer
        # elements than one layer's (64, K, N) weights unpacked
        assert not (e in shape and np.prod(shape) >= e * one_expert), (dtype, shape)
    layer_words = e * (f // 32) * d  # the fewest of a layer's gate, up or down
    for dims, op in re.findall(r"= u32\[([0-9,]+)\]\{[^}]*\} ([\w-]+)\(", text):
        if op not in ("parameter", "bitcast", "get-tuple-element"):
            assert np.prod([int(n) for n in dims.split(",")]) < layer_words, (dims, op)
    assert _bytes(compiled) < HBM_BYTES

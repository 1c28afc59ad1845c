"""Attention representations (``models/attention.py``).

Two properties of the one place that decides them:

* every integer scores and PV function returns the real product of the
  operands its mantissas and affines stand for: the shared epilogue
  (``_affine``) over the integer product equals the float64 dot of the
  operands dequantized from the same mantissas and affines the path uses;
* ``representation`` names the cache that ``init_cache`` allocates, both
  from the config alone and read back off the allocated leaves, for every
  registered configuration's smoke variant.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs
from repro.configs.smoke import smoke_variant
from repro.core import packing
from repro.models import attention as A
from repro.models import model_zoo as Z

B, S, H, KVH, T = 2, 3, 4, 2, 7
DH = 40  # two packed words, the second with a zero tail


def _grid(key, lo, hi):
    """A per-slot (B,) affine: scales in [lo, hi], offsets around -1."""
    ka, kg = jax.random.split(key)
    return (
        jax.random.uniform(ka, (B,), minval=lo, maxval=hi),
        jax.random.uniform(kg, (B,), minval=-1.5, maxval=-0.5),
    )


def _f64(x):
    return np.asarray(x, np.float64)


def _q_deq(q, bits):
    """q dequantized from the mantissas and per-slot affine the path puts it on."""
    qr = A._quantize_q(q, bits, "attn.qk", "auto")
    return _f64(qr.scale) * _f64(qr.mantissa) + _f64(qr.offset)


def _cache_deq(m, scale, offset):
    """An int8 cache row dequantized (mantissas re-centered by 128)."""
    b = (-1,) + (1,) * (m.ndim - 1)
    return (_f64(m) + 128.0) * _f64(scale).reshape(b) + _f64(offset).reshape(b)


def _p_deq(p):
    """Probabilities on the PV's 1/255 grid."""
    return np.clip(np.round(_f64(p) * 255.0), 0.0, 255.0) / 255.0


def _int8(key, shape):
    return jax.random.randint(key, shape, -128, 128).astype(jnp.int8)


def _probs(key, shape):
    return jax.nn.softmax(2.0 * jax.random.normal(key, shape), axis=-1)


def _grouped(k):
    """(B,T,kvH,d) -> (B,T,H,d): kv head h // group serves query head h."""
    return np.repeat(k, H // k.shape[2], axis=2)


def _scores_int(ks):
    q = jax.random.normal(ks[0], (B, S, H, DH))
    k_m = _int8(ks[1], (B, T, KVH, DH))
    sc, off = _grid(ks[2], 0.005, 0.02)
    out = A._scores_int(q, k_m, sc, off, 8)
    return out, "bshd,bthd->bhst", _q_deq(q, 8), _grouped(_cache_deq(k_m, sc, off))


def _pv_int(ks):
    p = _probs(ks[0], (B, H, S, T))
    v_m = _int8(ks[1], (B, T, KVH, DH))
    sc, off = _grid(ks[2], 0.005, 0.02)
    out = A._pv_int(p, v_m, sc, off)
    return out, "bhst,bthd->bshd", _p_deq(p), _grouped(_cache_deq(v_m, sc, off))


def _scores_int_latent(ks):
    q_abs = jax.random.normal(ks[0], (B, S, H, DH))
    ckv_m = _int8(ks[1], (B, T, DH))
    sc, off = _grid(ks[2], 0.005, 0.02)
    out = A._scores_int_latent(q_abs, ckv_m, sc, off, 8)
    return out, "bshr,btr->bhst", _q_deq(q_abs, 8), _cache_deq(ckv_m, sc, off)


def _pv_int_latent(ks):
    p = _probs(ks[0], (B, H, S, T))
    ckv_m = _int8(ks[1], (B, T, DH))
    sc, off = _grid(ks[2], 0.005, 0.02)
    out = A._pv_int_latent(p, ckv_m, sc, off)
    return out, "bhst,btr->bshr", _p_deq(p), _cache_deq(ckv_m, sc, off)


def _scores_binary(ks):
    q = jax.random.normal(ks[0], (B, S, H, DH))
    bits = jax.random.bernoulli(ks[1], 0.5, (B, T, KVH, DH)).astype(jnp.uint32)
    sc, off = _grid(ks[2], 0.5, 2.0)
    planes = packing.pack_bits(bits, 1, axis=-1).transpose(0, 2, 1, 3)  # (B,kvH,T,dw)
    out = A._scores_binary(q, planes, sc, off, "attn.qk", "float")
    k = _f64(bits) * _f64(sc)[:, None, None, None] + _f64(off)[:, None, None, None]
    return out, "bshd,bthd->bhst", _q_deq(q, 1), _grouped(k)


def _scores_binary_latent(ks):
    """The key bits are ``m >= 0`` of the int8 latent, on the affine they
    induce: ``128 sc`` per bit, ``off + 64 sc`` at zero."""
    q_abs = jax.random.normal(ks[0], (B, S, H, DH))
    ckv_m = _int8(ks[1], (B, T, DH))
    sc, off = _grid(ks[2], 0.005, 0.02)
    out = A._scores_binary_latent(q_abs, ckv_m, sc, off, "float")
    bit = _f64(np.asarray(ckv_m) >= 0)
    k = bit * (128.0 * _f64(sc))[:, None, None] + (_f64(off) + 64.0 * _f64(sc))[:, None, None]
    return out, "bshr,btr->bhst", _q_deq(q_abs, 1), k


@pytest.mark.parametrize(
    "path",
    [_scores_int, _pv_int, _scores_int_latent, _pv_int_latent, _scores_binary,
     _scores_binary_latent],
    ids=lambda f: f.__name__.lstrip("_"),
)
def test_epilogue_is_the_dot_of_the_dequantized_operands(path):
    """Tolerance: the epilogue sums four float32 terms, each at most the
    contraction length times the largest |operand| of each side, so float32
    rounding stays within a few 1e-7 of that; 2e-6 of it leaves a margin
    yet fails on any wrong affine (a missing 128 shift or a dropped term
    moves the result by a whole operand row)."""
    out, spec, x, y = path(jax.random.split(jax.random.PRNGKey(17), 3))
    ref = np.einsum(spec, x, y)
    out = _f64(out)
    assert out.shape == ref.shape
    n = x.shape[-1]  # the contracted axis is last on the left in every spec
    scale = n * np.abs(x).max() * np.abs(y).max()
    err = np.abs(out - ref).max()
    assert err <= 2e-6 * scale, (err, scale)


# --- the decision agrees with the cache init_cache allocates ---------------


def _quant_variants(cfg):
    """The config, its bitwise-scores variant and its float-cache variant."""
    q = cfg.quant
    site = "attn.qk_latent" if cfg.mla is not None else "attn.qk"
    return [
        cfg,
        dataclasses.replace(
            cfg, quant=dataclasses.replace(q, backend_overrides=((site, "binary"),))
        ),
        dataclasses.replace(cfg, quant=dataclasses.replace(q, kv_cache_bits=16)),
    ]


def _attention_caches(cache):
    """(cache, leading batch axis) of every attention layer of a stack cache."""
    stack = cache["stack"]
    found = [(c, 0) for c in stack["prefix"]] + [(c, 1) for c in stack["period"]]
    return [(c, ax) for c, ax in found if "k" in c or "ckv" in c]


def _attention_kinds(cfg):
    return [k for k in cfg.prefix_layers + cfg.pattern_period if k in ("g", "l", "Md", "Mm")]


@pytest.mark.parametrize("name", list_configs())
def test_representation_names_the_allocated_cache(name):
    batch = 3
    for cfg in _quant_variants(smoke_variant(get_config(name))):
        cache = jax.eval_shape(lambda: Z.init_cache(batch, 16, cfg))  # noqa: B023
        layers = _attention_caches(cache)
        assert len(layers) == len(_attention_kinds(cfg))
        for layer, ax in layers:
            latent = "ckv" in layer
            store = A.representation(cfg.quant, latent=latent).store
            assert A.representation(cfg.quant, latent=latent, cache=layer).store == store
            main = layer["ckv"] if latent else layer["k"]
            scales = {n for n in layer if n.endswith(("_scale", "_offset"))}
            if store == A.FLOAT:
                assert main.dtype == jnp.bfloat16 and not scales
                continue
            want = {"ckv_scale", "ckv_offset"} if latent else {
                "k_scale", "k_offset", "v_scale", "v_offset"}
            assert scales == want
            assert all(layer[n].shape[ax] == batch for n in scales)
            if store == A.BITS:
                assert not latent and main.dtype == jnp.uint32
                assert main.shape[-1] == packing.packed_len(cfg.d_head, 1)
                assert layer["v"].dtype == jnp.int8
            else:
                assert main.dtype == jnp.int8
                if not latent:
                    assert layer["v"].dtype == jnp.int8

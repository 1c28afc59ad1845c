"""Quantization-aware building blocks shared by every architecture.

Three execution modes thread through all layers (``mode``):

* ``"train"`` — QAT: latent fp32 weights fake-binarized with STE, activations
  fake-quantized; matmuls stay float so gradients flow.  This is how the
  paper's benchmark models (BiT et al.) are produced.
* ``"serve"`` — the BETA datapath: weights live bit-packed (uint32) with
  per-channel scale/offset + precomputed colsum; activations are quantized to
  the engine's mode and the product runs through the flow abstraction on an
  integer core.  What the accelerator executes.
* ``"float"`` — full-precision baseline (the paper's FP-32/FIX-16 rows).

Params are plain nested dicts of jnp arrays (pjit-friendly); serving params
are produced from train params by ``prepare_serving_params`` (model_zoo).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, QuantConfig, RopeScaling
from repro.core import flow_abstraction as FA
from repro.core import packing
from repro.core import qmm as QE
from repro.core import quantization as Q
from repro.core import site_log

__all__ = [
    "qlinear",
    "init_linear",
    "pack_linear_for_serving",
    "rmsnorm",
    "layernorm",
    "rope",
    "rope_inv_freq",
    "yarn_mscale",
    "attention_scale",
    "ffn",
    "init_ffn",
    "embed",
    "unembed",
]

# ---------------------------------------------------------------------------
# quant-aware linear
# ---------------------------------------------------------------------------


def init_linear(key, d_in: int, d_out: int, dtype=jnp.float32, scale: float = 1.0):
    std = scale / (d_in**0.5)
    return {"w": jax.random.normal(key, (d_in, d_out), dtype) * std}


def pack_linear_for_serving(p: dict, quant: QuantConfig) -> dict:
    """Offline weight pipeline (the paper's 'performed offline' step):
    binarize -> bit-pack along K -> precompute colsum corrections."""
    if not quant.enabled:
        return {"w": p["w"].astype(jnp.bfloat16)}
    wq = Q.quantize_weight(p["w"], quant.weight_bits, per_channel_axis=-1)
    colsum = FA.weight_corrections(wq)
    packed = wq.pack(axis=0)
    return {
        "w_packed": packed.mantissa,  # uint32 (K/32, N)
        "w_scale": packed.scale.astype(jnp.float32),  # (1, N)
        "w_offset": packed.offset.astype(jnp.float32),
        "w_colsum": colsum.astype(jnp.int32),  # (N,)
    }


def _serving_weight(p: dict, k: int, quant: QuantConfig) -> Q.QuantTensor:
    return Q.QuantTensor(
        mantissa=p["w_packed"],
        scale=p["w_scale"],
        offset=p["w_offset"],
        bits=quant.weight_bits,
        packed=True,
        packed_axis=0,
        length=k,
    )


def qlinear(
    p: dict,
    x: jax.Array,
    quant: QuantConfig,
    mode: str,
    *,
    act_bits: Optional[int] = None,
    name: str = "",
) -> jax.Array:
    """``x (..., K) @ W (K, N)`` in the configured execution mode.

    ``name`` identifies the layer site (e.g. "ffn.up") for per-layer backend
    overrides (``QuantConfig.backend_overrides``); unnamed sites use the
    config's default backend.  A named site runs under
    ``jax.named_scope(name)``, so its device ops (weight unpack, activation
    quantization, QMM, epilogue) carry the site name in a profiler trace.
    """
    if not name:
        return _qlinear(p, x, quant, mode, act_bits, name)
    with jax.named_scope(name):
        return _qlinear(p, x, quant, mode, act_bits, name)


def _qlinear(p, x, quant: QuantConfig, mode: str, act_bits: Optional[int], name: str):
    if mode == "float" or not quant.enabled:
        w = p["w"] if "w" in p else None
        if w is None:
            raise ValueError("float mode needs latent weights")
        return jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))

    bits = act_bits or quant.act_bits

    if mode == "train":
        if quant.prebinarize_gather:
            # weights arrive pre-binarized (packed-gather STE upstream)
            w_hat = p["w"]
        else:
            w_hat = Q.fake_binarize_weight(p["w"], per_channel_axis=-1)
        x_hat = Q.fake_quant(x, bits)
        return jnp.einsum("...k,kn->...n", x_hat, w_hat.astype(x.dtype))

    if mode == "serve":
        k = x.shape[-1]
        wq = _serving_weight(p, k, quant)
        lead = x.shape[:-1]
        # per-token calibration on the flattened (M, K) view: each row gets
        # its own grid, so co-batched serving slots stay numerically
        # independent (batch invariance) — the epilogue broadcasts (M, 1)
        xq = Q.quantize_activation(
            x.astype(jnp.float32).reshape(-1, k), bits, per_channel_axis=0
        )
        x2 = Q.QuantTensor(
            mantissa=xq.mantissa,
            scale=xq.scale,
            offset=xq.offset,
            bits=bits,
        )
        if site_log.is_recording():
            site_log.record(
                kind="qlinear",
                site=name,
                bits=bits,
                cfg_bits=quant.act_bits,
                mantissa_dtype=str(xq.mantissa.dtype),
                backend=quant.backend_for(name),
                int_core=QE.int_core(x2, wq, quant.backend_for(name)),
            )
        out = QE.qmm(
            x2, wq, backend=quant.backend_for(name), w_colsum=p.get("w_colsum")
        )
        return out.reshape(*lead, -1).astype(x.dtype)

    raise ValueError(f"unknown mode {mode!r}")


# ---------------------------------------------------------------------------
# norms / positions / activations
# ---------------------------------------------------------------------------


def rmsnorm(g: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))).astype(
        x.dtype
    )


def layernorm(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)


def yarn_mscale(scale: float, mscale: float) -> float:
    """YaRN's attention-temperature term as DeepSeek publishes it:
    ``0.1 * mscale * ln(scale) + 1`` (1 when nothing is scaled)."""
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def rope_inv_freq(d: int, theta: float, scaling: RopeScaling) -> np.ndarray:
    """YaRN's ``d/2`` inverse frequencies (float32).

    ``extra_i = theta^(-2i/d)`` and ``inter_i = extra_i / factor`` are joined
    by ``ramp_i = clip((i - low) / (high - low), 0, 1)``, where ``low`` and
    ``high`` are the dims at which a wavelength fits ``beta_fast`` and
    ``beta_slow`` times into the original context:
    ``inv_freq_i = inter_i * ramp_i + extra_i * (1 - ramp_i)``.
    """
    i = np.arange(d // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / d)

    def dim_of(rotations: float) -> float:
        n = scaling.original_max_position_embeddings
        return d * math.log(n / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(scaling.beta_fast)), 0)
    high = min(math.ceil(dim_of(scaling.beta_slow)), d - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (extra / scaling.factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def attention_scale(head_dim: int, scaling: Optional[RopeScaling]) -> float:
    """Softmax scale ``head_dim^-0.5``, times ``yarn_mscale(factor,
    mscale_all_dim)^2`` under YaRN."""
    scale = head_dim**-0.5
    if scaling is not None and scaling.mscale_all_dim:
        scale *= yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2
    return scale


def rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    dtype=jnp.float32,
    scaling: Optional[RopeScaling] = None,
) -> jax.Array:
    """Rotary embedding. x: (..., S, H, D) or (..., S, D); positions (..., S).

    ``scaling`` (YaRN) sets the frequencies by :func:`rope_inv_freq` and
    multiplies cos and sin by ``yarn_mscale(factor, mscale) /
    yarn_mscale(factor, mscale_all_dim)``."""
    d = x.shape[-1]
    half = d // 2
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs = jnp.asarray(rope_inv_freq(d, theta, scaling))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    if x.ndim == angles.ndim + 1:  # head axis present
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim
        )
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def _act(name: str, x: jax.Array) -> jax.Array:
    if name.startswith("gelu"):
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------


def init_ffn(key, cfg_ffn_type: str, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "up": init_linear(k1, d_model, d_ff),
        "down": init_linear(k2, d_ff, d_model, scale=0.5),
    }
    if cfg_ffn_type.endswith("glu"):
        p["gate"] = init_linear(k3, d_model, d_ff)
    return p


def ffn(
    p: dict,
    x: jax.Array,
    ffn_type: str,
    quant: QuantConfig,
    mode: str,
    name: str = "ffn",
):
    up = qlinear(p["up"], x, quant, mode, name=f"{name}.up")
    if ffn_type.endswith("glu"):
        gate = qlinear(p["gate"], x, quant, mode, name=f"{name}.gate")
        h = _act(ffn_type, gate) * up
    else:
        h = _act(ffn_type, up)
    return qlinear(p["down"], h, quant, mode, name=f"{name}.down")


# ---------------------------------------------------------------------------
# embeddings (kept full-precision, as the paper's benchmark models do)
# ---------------------------------------------------------------------------


def embed(p: dict, tokens: jax.Array, d_model: int, dtype=jnp.bfloat16) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0).astype(dtype) * jnp.asarray(
        d_model**0.5, dtype
    )


def unembed(p: dict, x: jax.Array, tied: bool, dtype=jnp.float32) -> jax.Array:
    table = p["embedding"] if tied else p["unembedding"]
    return jnp.einsum("...d,vd->...v", x.astype(dtype), table.astype(dtype))

"""Block assembly: (mixer + FFN) blocks, scanned period stacks, enc-dec.

Stack layout (configs/base.py): ``prefix_layers`` are unrolled with their own
params; the repeating ``pattern_period`` is lowered as ONE ``lax.scan`` over
``n_periods`` with params (and caches) stacked on the leading axis per
period position.  HLO size therefore scales with ``len(period)``, not
``n_layers`` — essential for the 512-way SPMD dry-run compiles of 60+-layer
models on this 1-core container, and for real-world compile latency.

Pre-norm residual blocks throughout (RMSNorm; BERT-family's post-LN is
mapped to pre-norm — systems-equivalent, noted in DESIGN.md).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S

__all__ = [
    "init_block",
    "block_apply",
    "init_block_cache",
    "init_stack",
    "stack_apply",
    "init_stack_cache",
]


def _needs_cross(cfg: ArchConfig) -> bool:
    return cfg.encoder is not None and cfg.encoder.n_layers > 0


# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------


def init_block(key, cfg: ArchConfig, kind: str, cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: dict = {"ln1": jnp.zeros((d,), jnp.float32)}
    if kind in ("g", "l"):
        p["attn"] = A.init_attention(ks[0], cfg)
    elif kind in ("Md", "Mm"):
        p["attn"] = A.init_mla(ks[0], cfg)
    elif kind == "r":
        p["rglru"] = S.init_rglru(ks[0], cfg)
    elif kind == "s":
        p["ssd"] = S.init_ssd(ks[0], cfg)
        return p  # mamba2 block = norm + mixer only
    else:
        raise ValueError(f"unknown block kind {kind!r}")

    if cross:
        p["ln_cross"] = jnp.zeros((d,), jnp.float32)
        p["cross_attn"] = A.init_attention(ks[2], cfg)

    p["ln2"] = jnp.zeros((d,), jnp.float32)
    if kind == "Mm":
        p["moe"] = M.init_moe(ks[1], cfg)
    else:
        ff = cfg.d_ff
        p["ffn"] = L.init_ffn(ks[1], cfg.ffn_type, d, ff)
    return p


def init_block_cache(batch: int, max_len: int, cfg: ArchConfig, kind: str):
    if kind in ("g", "l"):
        return A.init_kv_cache(batch, max_len, cfg, kind)
    if kind in ("Md", "Mm"):
        return A.init_mla_cache(batch, max_len, cfg)
    if kind == "r":
        return S.init_rglru_state(batch, cfg)
    if kind == "s":
        return S.init_ssd_state(batch, cfg)
    raise ValueError(kind)


def block_apply(
    p: dict,
    x: jax.Array,
    cfg: ArchConfig,
    kind: str,
    mode: str,
    positions: jax.Array,
    cache=None,
    encoder_out: Optional[jax.Array] = None,
):
    """Pre-norm residual block. Returns (x, new_cache, aux)."""
    aux = jnp.float32(0.0)
    h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if kind in ("g", "l"):
        mix, cache = A.attention(p["attn"], h, cfg, kind, mode, positions, cache)
    elif kind in ("Md", "Mm"):
        mix, cache = A.mla_attention(p["attn"], h, cfg, mode, positions, cache)
    elif kind == "r":
        mix, cache = S.rglru_mixer(p["rglru"], h, cfg, mode, cache)
    elif kind == "s":
        mix, cache = S.ssd_mixer(p["ssd"], h, cfg, mode, cache)
        return x + mix, cache, aux
    x = x + mix

    if "cross_attn" in p and encoder_out is not None:
        h = L.rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        kvh, dh = cfg.n_kv_heads, cfg.d_head
        ck = L.qlinear(
            p["cross_attn"]["k"], encoder_out, cfg.quant, mode, name="cross_attn.k"
        )
        cv = L.qlinear(
            p["cross_attn"]["v"], encoder_out, cfg.quant, mode, name="cross_attn.v"
        )
        ck = ck.reshape(*encoder_out.shape[:-1], kvh, dh)
        cv = cv.reshape(*encoder_out.shape[:-1], kvh, dh)
        mix, _ = A.attention(
            p["cross_attn"], h, cfg, "g", mode, positions,
            kv_override=(ck, cv), causal=False,
        )
        x = x + mix

    h = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if kind == "Mm":
        out, aux = M.moe_ffn(p["moe"], h, cfg, mode)
    else:
        out = L.ffn(p["ffn"], h, cfg.ffn_type, cfg.quant, mode)
    return x + out, cache, aux


_compiled_block = jax.jit(block_apply, static_argnums=(2, 3, 4))


# ---------------------------------------------------------------------------
# the stack: prefix (unrolled) + period (scanned)
# ---------------------------------------------------------------------------


def init_stack(
    key, cfg: ArchConfig, cross: bool = False, block_fn: Optional[Callable] = None
) -> dict:
    """Params: {'prefix': [block...], 'period': [stacked-block...]}.

    Period params are stacked along axis 0 with length ``n_periods`` (one
    entry per scan step), independently for each position in the period.

    Period blocks are built one layer at a time under ``lax.map``.
    ``block_fn``, when given, maps every block as soon as it is initialised
    (e.g. to its packed serving form), so inside ``jit`` only one layer's
    latent weights exist at once.
    """
    keys = jax.random.split(key, len(cfg.prefix_layers) + 1)
    fn = block_fn or (lambda blk: blk)
    prefix = [
        fn(init_block(keys[i], cfg, kind, cross))
        for i, kind in enumerate(cfg.prefix_layers)
    ]
    period = []
    if cfg.n_periods:
        pkeys = jax.random.split(keys[-1], len(cfg.pattern_period))
        for j, kind in enumerate(cfg.pattern_period):
            reps = jax.random.split(pkeys[j], cfg.n_periods)

            def one(k, kind=kind):
                return fn(init_block(k, cfg, kind, cross))

            period.append(jax.lax.map(one, reps))
    return {"prefix": prefix, "period": period}


def init_stack_cache(batch: int, max_len: int, cfg: ArchConfig) -> dict:
    prefix = [
        init_block_cache(batch, max_len, cfg, kind) for kind in cfg.prefix_layers
    ]
    period = []
    for kind in cfg.pattern_period:
        one = init_block_cache(batch, max_len, cfg, kind)
        period.append(
            jax.tree.map(lambda a: jnp.broadcast_to(a, (cfg.n_periods,) + a.shape), one)
        )
    return {"prefix": prefix, "period": period}


def stack_apply(
    params: dict,
    x: jax.Array,
    cfg: ArchConfig,
    mode: str,
    positions: jax.Array,
    caches: Optional[dict] = None,
    encoder_out: Optional[jax.Array] = None,
    compiled_prefix: bool = False,
):
    """Apply prefix blocks then the scanned period stack.

    ``compiled_prefix``: run each unrolled prefix block as one compiled
    program, for a caller that runs the stack eagerly (the serving engine's
    prefill): dispatched op by op, each op at a new prompt length is a
    compile of its own (173 for deepseek-v2-lite's dense layer).

    Returns (x, new_caches, aux_total).
    """
    aux_total = jnp.float32(0.0)
    new_prefix = []
    prefix_block = _compiled_block if compiled_prefix else block_apply
    for i, kind in enumerate(cfg.prefix_layers):
        c = caches["prefix"][i] if caches is not None else None
        x, c, aux = prefix_block(
            params["prefix"][i], x, cfg, kind, mode, positions, c, encoder_out
        )
        new_prefix.append(c)
        aux_total += aux

    new_period = [None] * len(cfg.pattern_period)
    if cfg.n_periods:
        xs = {"params": params["period"]}
        # Serving: the routed experts' words stay whole, read in place by the
        # grouped kernel at the step's layer, not sliced (copied) per layer.
        held = [None] * len(cfg.pattern_period)
        if mode == "serve":
            scanned, held = M.hold_expert_words(params["period"])
            if any(h is not None for h in held):
                xs = {"params": scanned, "layer": jnp.arange(cfg.n_periods)}

        def body(carry, xs):
            xc, aux_c = carry
            p_stk = xs["params"]
            c_stk = xs.get("caches")
            new_cs = []
            for j, kind in enumerate(cfg.pattern_period):
                cj = c_stk[j] if c_stk is not None else None
                pj = p_stk[j]
                if held[j] is not None:
                    pj = M.lend_expert_words(pj, held[j], xs["layer"])
                xc, cj, aux = block_apply(
                    pj, xc, cfg, kind, mode, positions, cj, encoder_out
                )
                new_cs.append(cj if cj is not None else 0)
                aux_c = aux_c + aux
            ys = {"caches": new_cs} if c_stk is not None else {}
            return (xc, aux_c), ys

        if caches is not None:
            xs["caches"] = caches["period"]
        # Block-level remat for QAT training: recompute the period body on
        # the backward pass (activation memory ~ one period, not n_layers).
        scan_body = jax.checkpoint(body) if mode == "train" else body
        (x, aux_total), ys = jax.lax.scan(scan_body, (x, aux_total), xs)
        if caches is not None:
            new_period = ys["caches"]

    new_caches = None
    if caches is not None:
        new_caches = {"prefix": new_prefix, "period": new_period}
    return x, new_caches, aux_total

"""Public model API: build any assigned architecture from its ArchConfig.

Entry points (all pure functions of explicit params — pjit-ready):

* ``init_params(key, cfg)``            — training params (latent fp32)
* ``prepare_serving_params(params)``   — offline: binarize, bit-pack, colsums
* ``init_serving_params(key, cfg)``    — both of the above, one layer at a time
* ``loss_fn(params, batch, cfg, mode)``— LM loss (+ MoE aux, + MTP)
* ``forward_logits(...)``              — full-sequence logits
* ``init_cache(batch, max_len, cfg)``  — serving caches (quantized KV)
* ``prefill(...)`` / ``decode_step(...)``

Frontends per the assignment: ``[audio]``/``[vlm]`` entries stub the
modality encoder — ``input_specs`` (launch/dryrun.py) provides precomputed
frame/patch embeddings; the transformer backbone is the real deliverable.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import dispatch
from repro.models import layers as L
from repro.models import moe as M
from repro.models import transformer as T

__all__ = [
    "init_params",
    "prepare_serving_params",
    "init_serving_params",
    "loss_fn",
    "forward_logits",
    "init_cache",
    "init_slot_cache",
    "cache_insert",
    "cache_reset",
    "prefill",
    "decode_step",
]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ArchConfig, block_fn=None) -> dict:
    """Latent fp32 params.  ``block_fn`` maps each block as it is built
    (``transformer.init_stack``); ``init_serving_params`` packs with it."""
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    p: dict = {
        "embedding": jax.random.normal(ks[0], (cfg.vocab_size, d), jnp.float32)
        * 0.02,
        "final_norm": jnp.zeros((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        p["unembedding"] = (
            jax.random.normal(ks[1], (cfg.vocab_size, d), jnp.float32) * 0.02
        )
    cross = cfg.encoder is not None and cfg.encoder.n_layers > 0
    p["stack"] = T.init_stack(ks[2], cfg, cross=cross, block_fn=block_fn)

    if cfg.encoder is not None:
        enc: dict = {}
        d_in = cfg.encoder.d_input or d
        enc["stub_proj"] = L.init_linear(ks[3], d_in, d)
        if cfg.encoder.n_layers:
            # a small bidirectional transformer on top of the stub (whisper)
            enc_cfg = _encoder_cfg(cfg)
            enc["stack"] = T.init_stack(ks[4], enc_cfg, block_fn=block_fn)
            enc["final_norm"] = jnp.zeros((d,), jnp.float32)
        p["encoder"] = enc

    if cfg.pos_embedding == "learned":
        p["pos_embedding"] = (
            jax.random.normal(ks[5], (cfg.max_seq, d), jnp.float32) * 0.02
        )

    if cfg.mtp_depth:
        p["mtp"] = {"proj": L.init_linear(ks[6], 2 * d, d)}
    return p


def _encoder_cfg(cfg: ArchConfig) -> ArchConfig:
    import dataclasses

    return dataclasses.replace(
        cfg,
        name=cfg.name + "-encoder",
        n_layers=cfg.encoder.n_layers,
        prefix_layers=(),
        pattern_period=("g",),
        causal=False,
        pos_embedding="sinusoidal",
        encoder=None,
        mtp_depth=0,
    )


# ---------------------------------------------------------------------------
# serving weight pipeline (offline, like the paper's folded coefficients)
# ---------------------------------------------------------------------------

_FP_LEAF_PATHS = ("router", "stub_proj")  # accuracy-critical, kept FP


def prepare_serving_params(params: dict, cfg: ArchConfig):
    """Binarize + bit-pack every QMM weight; keep FP leaves (norms, router,
    embeddings, frontend stubs, recurrence gains) as bf16/fp32.

    Inside the scanned ``period`` subtree every weight carries an extra
    leading scan dim — packing is vmapped over it, so serving params keep
    the exact pytree structure ``stack_apply`` consumes.
    """

    def pack_leaf(node, stacked: bool):
        w = node["w"]
        base_ndim = w.ndim - (1 if stacked else 0)
        if base_ndim == 2:
            fn = lambda n: L.pack_linear_for_serving(n, cfg.quant)
        elif base_ndim == 3:
            fn = lambda n: M.pack_experts_for_serving(n, cfg.quant)
        else:
            raise ValueError(f"unexpected weight rank {w.ndim} (stacked={stacked})")
        return jax.vmap(fn)(node) if stacked else fn(node)

    def walk(node, path, stacked):
        if isinstance(node, dict):
            if "w" in node and len(node) == 1:
                if any(s in path for s in _FP_LEAF_PATHS):
                    return {"w": node["w"].astype(jnp.float32)}
                return pack_leaf(node, stacked)
            return {k: walk(v, path + (k,), stacked or k == "period") for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, path + (str(i),), stacked) for i, v in enumerate(node))
        if hasattr(node, "dtype") and jnp.issubdtype(node.dtype, jnp.floating):
            if path and path[-1] in ("embedding", "unembedding", "pos_embedding"):
                return node.astype(jnp.bfloat16)
            return node
        return node

    return walk(params, (), False)


@functools.partial(jax.jit, static_argnums=1)
def init_serving_params(key, cfg: ArchConfig) -> dict:
    """``prepare_serving_params(init_params(key, cfg), cfg)`` without the fp32
    latent model ever being whole on the device.

    Every block is packed as soon as it is initialised (one scanned layer
    per ``lax.map`` step), so device memory holds the packed model plus one
    layer's latent weights — for granite-8b 1.4 GB instead of 32 GB.
    Packing is idempotent on packed blocks, so the outer
    ``prepare_serving_params`` only converts the embeddings.  Bit-identical
    to the two-step path under ``jit``.
    """
    pack = functools.partial(prepare_serving_params, cfg=cfg)
    return prepare_serving_params(init_params(key, cfg, block_fn=pack), cfg)


# ---------------------------------------------------------------------------
# forward passes
# ---------------------------------------------------------------------------


def _sinusoidal(positions: jax.Array, d: int) -> jax.Array:
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (jnp.log(10000.0) / (half - 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _embed_inputs(params, tokens, cfg: ArchConfig, positions, frontend=None, mode="train"):
    x = L.embed(params, tokens, cfg.d_model)
    if cfg.pos_embedding == "learned":
        pe = jnp.take(params["pos_embedding"], positions, axis=0)
        x = x + pe.astype(x.dtype)
    if (
        cfg.encoder is not None
        and cfg.encoder.kind == "patch_stub"
        and frontend is not None
    ):
        # VLM: splice projected patch embeddings over the first positions.
        patches = L.qlinear(
            params["encoder"]["stub_proj"], frontend.astype(x.dtype), cfg.quant, "float"
        )
        n = patches.shape[1]
        x = jnp.concatenate([patches.astype(x.dtype), x[:, n:]], axis=1)
    return x


def _run_encoder(params, frontend, cfg: ArchConfig, mode: str):
    """Whisper-style encoder over stub frame embeddings. Returns (B, T, D)."""
    enc = params["encoder"]
    x = L.qlinear(enc["stub_proj"], frontend, cfg.quant, "float")
    t = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(t), x.shape[:2])
    x = x + _sinusoidal(pos, cfg.d_model).astype(x.dtype)
    enc_cfg = _encoder_cfg(cfg)
    x, _, _ = T.stack_apply(enc["stack"], x, enc_cfg, mode, pos)
    return L.rmsnorm(enc["final_norm"], x, cfg.norm_eps)


def _forward_hidden(
    params: dict,
    tokens: jax.Array,
    cfg: ArchConfig,
    mode: str = "train",
    frontend: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward to the final (normed) hidden states."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    encoder_out = None
    if cfg.encoder is not None and cfg.encoder.n_layers and frontend is not None:
        encoder_out = _run_encoder(params, frontend, cfg, mode)
    x = _embed_inputs(params, tokens, cfg, positions, frontend, mode)
    x = x.astype(jnp.bfloat16)
    x, _, aux = T.stack_apply(
        params["stack"], x, cfg, mode, positions, None, encoder_out
    )
    return L.rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def forward_logits(
    params: dict,
    tokens: jax.Array,
    cfg: ArchConfig,
    mode: str = "train",
    frontend: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits (B,S,V), aux_loss)."""
    x, aux = _forward_hidden(params, tokens, cfg, mode, frontend)
    logits = L.unembed(params, x, cfg.tie_embeddings)
    return logits, aux


def loss_fn(
    params: dict,
    batch: dict,
    cfg: ArchConfig,
    mode: str = "train",
    aux_weight: float = 0.01,
):
    """Next-token LM loss (+ MoE balance aux + MTP head for deepseek-v3).

    batch: {"tokens": (B,S) int32, optional "frontend": stub embeddings}.
    Encoder-only archs (BERT family) use the denoising-copy objective —
    systems-equivalent supervision (DESIGN.md).
    """
    tokens = batch["tokens"]
    hidden, aux = _forward_hidden(params, tokens, cfg, mode, batch.get("frontend"))
    logits = L.unembed(params, hidden, cfg.tie_embeddings)
    if cfg.causal:
        pred, tgt = logits[:, :-1], tokens[:, 1:]
    else:
        pred, tgt = logits, tokens
    logp = jax.nn.log_softmax(pred, axis=-1)
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    loss = jnp.mean(nll.astype(jnp.float32))

    if cfg.mtp_depth and "mtp" in params and cfg.causal:
        # depth-1 MTP (deepseek-v3): predict t+2 from [h_t ; emb(t+1)],
        # sharing the unembedding (training-loss only; serving ignores it).
        h_t = hidden[:, :-2].astype(jnp.float32)
        emb_next = L.embed(params, tokens[:, 1:-1], cfg.d_model).astype(jnp.float32)
        mtp_in = jnp.concatenate([h_t, emb_next], axis=-1)
        h_mtp = L.qlinear(params["mtp"]["proj"], mtp_in, cfg.quant, mode)
        mtp_logits = L.unembed(params, h_mtp, cfg.tie_embeddings)
        mlogp = jax.nn.log_softmax(mtp_logits.astype(jnp.float32), axis=-1)
        mtp_nll = -jnp.take_along_axis(
            mlogp, tokens[:, 2:][..., None], axis=-1
        )[..., 0]
        loss = loss + 0.3 * jnp.mean(mtp_nll)

    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux": aux, "nll": loss}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def init_cache(batch: int, max_len: int, cfg: ArchConfig) -> dict:
    cache = {"stack": T.init_stack_cache(batch, max_len, cfg)}
    if cfg.encoder is not None and cfg.encoder.n_layers:
        cache["encoder_out"] = jnp.zeros(
            (batch, cfg.encoder.n_positions, cfg.d_model), jnp.bfloat16
        )
    return cache


def init_slot_cache(max_len: int, cfg: ArchConfig) -> dict:
    """A batch-1 cache suitable for ``cache_insert`` into a packed batch.

    Continuous-batching serving prefills each admitted request into one of
    these (exact prompt length, no padding) and then splices it into its
    decode slot — the slot cache MUST share ``max_len`` with the packed
    cache so every leaf lines up except the batch axis.
    """
    return init_cache(1, max_len, cfg)


def _insert_leaf(dst, src, slot, axis: int):
    return jax.lax.dynamic_update_slice_in_dim(dst, src.astype(dst.dtype), slot, axis)


def cache_insert(cache: dict, slot_cache: dict, slot) -> dict:
    """Splice a batch-1 ``slot_cache`` into row ``slot`` of a packed cache.

    Every per-row cache leaf carries the batch axis at 0 (prefix layers,
    encoder_out) or 1 (scanned ``period`` layers, whose leading axis is the
    scan dim) — including the per-row ``pos`` cursors and quantization
    affines, so the inserted request resumes at its own position with its
    own calibration while other slots keep decoding.  ``slot`` may be a
    Python int or a traced scalar (jit-safe).
    """
    stack, s_stack = cache["stack"], slot_cache["stack"]
    prefix = jax.tree.map(
        lambda d, s: _insert_leaf(d, s, slot, 0), stack["prefix"], s_stack["prefix"]
    )
    period = jax.tree.map(
        lambda d, s: _insert_leaf(d, s, slot, 1), stack["period"], s_stack["period"]
    )
    out = dict(cache, stack=dict(stack, prefix=prefix, period=period))
    if "encoder_out" in cache:
        out["encoder_out"] = _insert_leaf(
            cache["encoder_out"], slot_cache["encoder_out"], slot, 0
        )
    return out


def cache_reset(cache: dict, slot, cfg: ArchConfig, max_len: int) -> dict:
    """Zero row ``slot`` of a packed cache (freed when a request finishes):
    position cursor back to 0, calibration affines back to identity."""
    return cache_insert(cache, init_slot_cache(max_len, cfg), slot)


def prefill(
    params: dict,
    tokens: jax.Array,
    cfg: ArchConfig,
    cache: dict,
    frontend: Optional[jax.Array] = None,
    length: Optional[jax.Array] = None,
    compiled_prefix: bool = False,
) -> Tuple[jax.Array, dict]:
    """Process the prompt; returns (last-position logits (B,V), cache).

    Runs under the "prefill" autotune phase: its QMMs see M = batch x
    prompt, orders of magnitude larger than decode's M = batch, so the
    measured backend choice is tuned (and cached) independently.

    ``length``: optional (B,) actual prompt lengths for RIGHT-padded
    batches (bucketed prefill).  Logits are taken at ``length - 1`` per
    row and cache cursors are rewound to ``length`` so decode overwrites
    the pad region.  Pads are causally invisible to real tokens, but this
    is exact only for float full-attention caches: quantized-KV
    calibration sees the pads, windowed rings evict real tokens once the
    padded length reaches the window, and SSM recurrences integrate pad
    steps.  Exact-length prefill (``length=None``, no padding) is the
    default and what the continuous-batching engine uses.

    ``compiled_prefix``: an eager caller's unrolled prefix blocks each run as
    one compiled program (``transformer.stack_apply``).
    """
    with dispatch.tuning_phase("prefill"):
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        encoder_out = None
        if cfg.encoder is not None and cfg.encoder.n_layers and frontend is not None:
            encoder_out = _run_encoder(params, frontend, cfg, "serve")
            # cache-slot dtype derives from the init leaf (never a literal)
            cache = dict(
                cache, encoder_out=encoder_out.astype(cache["encoder_out"].dtype)
            )
        x = _embed_inputs(params, tokens, cfg, positions, frontend, "serve")
        x = x.astype(jnp.bfloat16)
        x, new_stack, _ = T.stack_apply(
            params["stack"], x, cfg, "serve", positions, cache["stack"], encoder_out,
            compiled_prefix=compiled_prefix,
        )
        if length is None:
            x_last = x[:, -1:]
        else:
            rows = jnp.asarray(length, jnp.int32).reshape(-1)
            idx = jnp.broadcast_to((rows - 1)[:, None, None], (b, 1, x.shape[-1]))
            x_last = jnp.take_along_axis(x, idx, axis=1)
            new_stack = _set_stack_pos(new_stack, rows)
        with jax.named_scope("head"):
            x = L.rmsnorm(params["final_norm"], x_last, cfg.norm_eps)
            logits = L.unembed(params, x, cfg.tie_embeddings)[:, 0]
        return logits, dict(cache, stack=new_stack)


def decode_step(
    params: dict,
    tokens: jax.Array,
    cfg: ArchConfig,
    cache: dict,
) -> Tuple[jax.Array, dict]:
    """One decode step. tokens (B,) int32 -> logits (B, V) + updated cache.

    Runs under the "decode" autotune phase (see ``prefill``).  The final
    norm and the unembedding run under ``jax.named_scope("head")``, as in
    ``prefill``."""
    with dispatch.tuning_phase("decode"):
        b = tokens.shape[0]
        pos_rows = jnp.reshape(_cache_pos(cache["stack"], cfg), (-1,))
        positions = jnp.broadcast_to(pos_rows[:, None], (b, 1))
        x = L.embed(params, tokens[:, None], cfg.d_model)
        if cfg.pos_embedding == "learned":
            pe = jnp.take(params["pos_embedding"], positions, axis=0)
            x = x + pe.astype(x.dtype)
        x = x.astype(jnp.bfloat16)
        encoder_out = cache.get("encoder_out")
        x, new_stack, _ = T.stack_apply(
            params["stack"], x, cfg, "serve", positions, cache["stack"], encoder_out
        )
        with jax.named_scope("head"):
            x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
            logits = L.unembed(params, x, cfg.tie_embeddings)[:, 0]
        return logits, dict(cache, stack=new_stack)


def _cache_pos(stack_cache: dict, cfg: ArchConfig):
    """Per-row (B,) position cursors of the first layer's cache."""
    if stack_cache["prefix"]:
        return stack_cache["prefix"][0]["pos"]
    return stack_cache["period"][0]["pos"][0]


def _set_stack_pos(stack_cache: dict, rows: jax.Array) -> dict:
    """Overwrite every layer's ``pos`` cursor with per-row values (B,)."""

    def fix(c):
        if isinstance(c, dict) and "pos" in c:
            pos = jnp.broadcast_to(rows, c["pos"].shape).astype(c["pos"].dtype)
            return dict(c, pos=pos)
        return c

    return dict(
        stack_cache,
        prefix=[fix(c) for c in stack_cache["prefix"]],
        period=[fix(c) for c in stack_cache["period"]],
    )

"""Distributed QAT training step builder (pjit) + microbatching + pod sync.

``make_train_step`` returns a jitted SPMD step:

    (params, opt_state, batch) -> (params, opt_state, metrics)

* **Parallelism**: params/opt-state sharded by runtime.sharding (TP/EP over
  ``model``); batch over ``(pod, data)``; XLA SPMD inserts the gradient
  all-reduces.  This is the function the dry-run lowers for every
  ``train_4k`` cell.
* **Microbatching**: ``accum_steps`` splits the per-step batch along B and
  accumulates grads in a ``lax.scan`` — activation memory scales with the
  microbatch, which is what lets deepseek-v3-671b's 1M-token steps compile
  within a 16 GB/chip budget (EXPERIMENTS.md §Dry-run).
* **Compressed pod sync** (beyond-paper, see optim.compression): an
  explicit int8 error-feedback all-reduce variant, exposed as
  ``make_compressed_dp_step`` over an explicit shard_map for DP-only
  configs, plus analytic byte accounting used in §Perf.  The default pjit
  path keeps XLA-managed fp32 reductions (control arm).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import model_zoo as Z
from repro.optim import adamw, compression
from repro.runtime import sharding as SH

__all__ = ["TrainConfig", "make_train_step", "make_compressed_dp_step", "init_train_state"]


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: adamw.AdamWConfig = adamw.AdamWConfig()
    accum_steps: int = 1
    remat: bool = True
    aux_weight: float = 0.01


def init_train_state(key, cfg: ArchConfig):
    params = Z.init_params(key, cfg)
    return params, adamw.init_state(params)


def _loss(params, batch, cfg: ArchConfig, aux_weight: float):
    return Z.loss_fn(params, batch, cfg, mode="train", aux_weight=aux_weight)


# ---------------------------------------------------------------------------
# packed FSDP gather: binarize + bit-pack BEFORE the weight all-gather
# ---------------------------------------------------------------------------
#
# FSDP keeps fp32 latents sharded over `data`; every layer use all-gathers
# them — for deepseek-v3 that is ~45 GB of fp32 per MoE layer to EVERY chip
# (the dominant memory+collective term of the train_4k baseline, §Perf).
# But the QAT forward only consumes alpha * sign(w): sign bits pack 32-to-a-
# word, so we binarize and pack ON THE SHARD, constrain the PACKED tensor to
# the TP-only sharding (that constraint is where the gather happens — 32x
# fewer wire bytes, measured 31.8x in the probe), unpack post-gather, and
# route gradients back to the latents with the standard STE (custom_vjp:
# the fp32 latent never appears in the forward graph, so XLA cannot
# "helpfully" gather it).


def _ste_packed_binarize(mesh: Mesh, packed_spec, k_dim: int):
    from repro.core import packing

    @jax.custom_vjp
    def f(w):
        return _value(w)

    def _value(w):
        alpha = jnp.mean(jnp.abs(w), axis=-2, keepdims=True)
        bits = (w >= 0).astype(jnp.uint32)
        packed = packing.pack_bits(bits, 1, axis=-2)
        packed = jax.lax.with_sharding_constraint(
            packed, NamedSharding(mesh, packed_spec)
        )
        pm1 = packing.unpack_bits(packed, 1, k_dim, axis=-2, dtype=jnp.int8)
        pm1 = pm1.astype(jnp.bfloat16) * 2.0 - 1.0
        return pm1 * alpha.astype(jnp.bfloat16)

    def fwd(w):
        alpha = jnp.mean(jnp.abs(w), axis=-2, keepdims=True)
        return _value(w), alpha

    def bwd(alpha, g):
        return ((g.astype(jnp.float32) * alpha),)  # STE through sign

    f.defvjp(fwd, bwd)
    return f


_QMM_OWNERS = SH._COL_PARALLEL | SH._ROW_PARALLEL | {"up", "gate", "down"}


def prebinarize_params(params, cfg: ArchConfig, mesh: Mesh):
    """Replace every QMM latent 'w' with its packed-gather STE binarization.

    Norms/routers/embeddings/frontends pass through untouched; the returned
    tree is what the model consumes with ``quant.prebinarize_gather`` set.
    """

    def walk(node, path):
        if isinstance(node, dict):
            if "w" in node and len(node) == 1 and not any(
                s in path for s in ("router", "stub_proj")
            ):
                parent = path[-1] if path else ""
                if parent in _QMM_OWNERS:
                    w = node["w"]
                    packed_shape = list(w.shape)
                    packed_shape[-2] = -(-w.shape[-2] // 32)
                    spec = SH.param_pspec(
                        path + ("w_packed",), tuple(packed_shape), mesh
                    )
                    fn = _ste_packed_binarize(mesh, spec, w.shape[-2])
                    return {"w": fn(w)}
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, path + (str(i),)) for i, v in enumerate(node))
        return node

    return walk(params, ())


def make_train_step(
    cfg: ArchConfig,
    tcfg: TrainConfig,
    mesh: Mesh,
    batch_shape: dict,
):
    """Build the pjit'd train step for (arch, mesh, global batch shape).

    batch_shape: {"tokens": (B, S)[, "frontend": (B, T, Din)]} — concrete
    shapes so shardings can be resolved ahead of time (AOT-lowerable).
    """
    # Remat policy: block-level remat lives inside models.transformer
    # (scan body checkpointed in train mode); tcfg.remat kept for ablation.
    base_loss = functools.partial(_loss, cfg=cfg, aux_weight=tcfg.aux_weight)
    if cfg.quant.enabled and cfg.quant.prebinarize_gather:

        def loss_fn(params, batch):
            return base_loss(prebinarize_params(params, cfg, mesh), batch)

    else:
        loss_fn = base_loss

    accum = tcfg.accum_steps

    def step(params, opt_state, batch):
        if accum == 1:
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
        else:
            b = batch["tokens"].shape[0]
            micro = b // accum
            sliced = jax.tree.map(
                lambda a: a[: micro * accum].reshape(accum, micro, *a.shape[1:]),
                batch,
            )

            def acc_body(carry, mb):
                g_acc, m_acc = carry
                (_, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                m_acc = jax.tree.map(jnp.add, m_acc, m)
                return (g_acc, m_acc), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            m0 = {"loss": jnp.float32(0), "aux": jnp.float32(0), "nll": jnp.float32(0)}
            (grads, msum), _ = jax.lax.scan(acc_body, (g0, m0), sliced)
            grads = jax.tree.map(lambda g: g / accum, grads)
            metrics = jax.tree.map(lambda m: m / accum, msum)

        params2, opt2, opt_metrics = adamw.apply_updates(
            params, grads, opt_state, tcfg.optimizer
        )
        metrics = dict(metrics, **opt_metrics)
        return params2, opt2, metrics

    # resolve shardings (FSDP over `data` for latent weights + Adam moments)
    p_leaves = jax.eval_shape(lambda k: Z.init_params(k, cfg), jax.random.PRNGKey(0))
    p_sh = SH.params_shardings(p_leaves, mesh, fsdp=True)
    opt_sh = adamw.OptState(
        mu=p_sh, nu=p_sh, step=NamedSharding(mesh, P())
    )
    b_sh = SH.batch_shardings(batch_shape, mesh)

    return jax.jit(
        step,
        in_shardings=(p_sh, opt_sh, b_sh),
        out_shardings=(p_sh, opt_sh, None),
        donate_argnums=(0, 1),
    )


# ---------------------------------------------------------------------------
# explicit compressed-DP step (shard_map) — the distributed-optimization trick
# ---------------------------------------------------------------------------


def make_compressed_dp_step(
    cfg: ArchConfig,
    tcfg: TrainConfig,
    mesh: Mesh,
    compress: bool = True,
):
    """Pure-DP train step with explicit int8 error-feedback gradient
    all-reduce across every data axis (pod + data).  Params replicated —
    the cross-pod regime where wire bytes, not FLOPs, bound step time.
    Wire traffic: 4x fewer gradient bytes than fp32 psum (see
    benchmarks/compression_bench.py for the measured payload accounting).
    """
    axes = SH.data_axes(mesh)
    loss_fn = functools.partial(_loss, cfg=cfg, aux_weight=tcfg.aux_weight)

    def step(params, opt_state, err_state, batch):
        def shard_fn(params, opt_state, err_state, batch):
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
            for ax in axes:
                grads, err_state = compression.compressed_psum(
                    grads, err_state, ax, enabled=compress
                )
            params2, opt2, om = adamw.apply_updates(
                params, grads, opt_state, tcfg.optimizer
            )
            metrics = {
                k: jax.lax.pmean(v, axes) for k, v in dict(metrics, **om).items()
            }
            return params2, opt2, err_state, metrics

        batch_spec = jax.tree.map(lambda _: P(axes, *([None])), batch)
        return jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(), P(), P(), batch_spec),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )(params, opt_state, err_state, batch)

    return jax.jit(step, donate_argnums=(0, 1, 2))

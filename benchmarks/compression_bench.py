"""int8 error-feedback gradient compression: payload + fidelity accounting.

Reproduces: no paper table — a systems extension (the BETA storage insight
applied to the cross-pod gradient fabric; EXPERIMENTS.md §Dist).
Run:        PYTHONPATH=src python benchmarks/compression_bench.py

The distributed-optimization trick for cross-pod DP (optim.compression):
measures (a) wire-byte reduction of the compressed all-reduce vs fp32, and
(b) gradient fidelity (cosine similarity + error-feedback residual decay)
on a real QAT gradient from the smoke BERT model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.configs.smoke import smoke_variant
from repro.models import model_zoo as Z
from repro.optim import compression as C


def run() -> list:
    cfg = smoke_variant(get_config("bit-bert-base"))
    params = Z.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    grads = jax.grad(lambda p: Z.loss_fn(p, {"tokens": tokens}, cfg, "train")[0])(
        params
    )

    leaves = jax.tree.leaves(grads)
    fp32_bytes = sum(g.size * 4 for g in leaves)
    int8_bytes = sum(g.size * 1 + 4 for g in leaves)  # payload + scale

    err = C.init_error_state(grads)
    cos_list = []
    resid_norms = []
    g_flat = jnp.concatenate([g.ravel() for g in leaves]).astype(jnp.float32)
    for step in range(3):
        qs, scales, resids = [], [], []
        new_err = []
        for g, e in zip(leaves, jax.tree.leaves(err)):
            q, s, r = C.compress(g, e)
            qs.append(C.decompress(q, s).ravel())
            new_err.append(r)
        deq = jnp.concatenate(qs)
        cos = float(
            jnp.dot(deq, g_flat)
            / (jnp.linalg.norm(deq) * jnp.linalg.norm(g_flat) + 1e-12)
        )
        cos_list.append(cos)
        resid_norms.append(
            float(jnp.sqrt(sum(jnp.sum(r * r) for r in new_err)))
        )
        err = jax.tree.unflatten(jax.tree.structure(grads), new_err)

    return [
        {
            "name": "compression/wire_bytes",
            "us_per_call": 0.0,
            "derived": f"fp32={fp32_bytes} int8={int8_bytes} "
            f"reduction={fp32_bytes/int8_bytes:.2f}x",
        },
        {
            "name": "compression/fidelity",
            "us_per_call": 0.0,
            "derived": f"cosine_step0={cos_list[0]:.4f} "
            f"residual_norms={[f'{r:.3e}' for r in resid_norms]}",
        },
    ]


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']}")

"""QMM engine micro-benchmarks (measured on this container's CPU).

Reproduces the engine-level evidence behind the paper's §III-C claims
(Table II / Fig. 5 use the calibrated hardware model; this file measures
the *software* engine).  Run directly::

    PYTHONPATH=src python benchmarks/qmm_micro.py

Times the three integer backends and the naive dequantized-FP flow the
paper replaces, over BERT-base QMM shapes.  On CPU the absolute numbers
reflect this host, but three claims are checked *structurally*:

1. the abstracted flow (integer MM + rank-1 epilogue) beats the naive
   dequantize-then-FP32-matmul flow it replaces,
2. both QMM types (act x weight, act x act) run through one engine at
   every activation precision, and
3. the autotuned dispatcher (core.dispatch) picks a backend whose measured
   time matches the best candidate (chosen-vs-best parity rows): parity =
   t_chosen / t_best, 1.00 meaning the cache picked the true winner.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core import flow_abstraction as FA
from repro.core import qmm as QE
from repro.core import quantization as Q


def _time(fn, *args, reps: int = 5) -> float:
    jax.block_until_ready(fn(*args))  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6  # us


@jax.jit
def _naive(xq, wq):
    return FA.qmm_dequant_reference(xq, wq)


@functools.partial(jax.jit, static_argnames=("backend",))
def _flow(xq, wq, colsum, backend="mxu"):
    return QE.qmm(xq, wq, backend=backend, w_colsum=colsum)


@functools.partial(jax.jit, static_argnames=("backend",))
def _flow_nocs(xq, wq, backend="mxu"):
    return QE.qmm(xq, wq, backend=backend)


def run() -> list:
    rows = []
    rng = np.random.default_rng(0)
    m, k, n = 128, 768, 3072  # BERT-base FFN-up QMM
    x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))

    for act_bits in (1, 8):
        xq = Q.quantize_activation(x, act_bits)
        wq = Q.binarize_weight(w)
        colsum = FA.weight_corrections(wq)
        t_naive = _time(_naive, xq, wq)
        t_flow = _time(_flow, xq, wq, colsum)
        rows.append(
            {
                "name": f"qmm_micro/act_weight/W1A{act_bits}",
                "us_per_call": t_flow,
                "derived": f"naive_fp={t_naive:.0f}us flow_int={t_flow:.0f}us "
                f"speedup={t_naive/max(t_flow,1e-9):.2f}x",
            }
        )

    # act x act (the QMM type prior accelerators lack): Q @ K^T shape
    a = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32))
    for act_bits in (4, 8):
        aq = Q.quantize_activation(a, act_bits)
        bq = Q.quantize_activation(b, act_bits)
        t_naive = _time(_naive, aq, bq)
        t_flow = _time(_flow_nocs, aq, bq)
        rows.append(
            {
                "name": f"qmm_micro/act_act/A{act_bits}xA{act_bits}",
                "us_per_call": t_flow,
                "derived": f"naive_fp={t_naive:.0f}us flow_int={t_flow:.0f}us",
            }
        )

    # popcount (DPU analogue) vs unpack->int8 dot, 1-bit x 1-bit
    xb = Q.quantize_activation(x, 1)
    wq = Q.binarize_weight(w)
    t_pop = _time(functools.partial(_flow_nocs, backend="popcount"), xb, wq)
    t_mxu = _time(functools.partial(_flow_nocs, backend="mxu"), xb, wq)
    rows.append(
        {
            "name": "qmm_micro/backends/popcount_vs_mxu",
            "us_per_call": t_pop,
            "derived": f"popcount={t_pop:.0f}us mxu={t_mxu:.0f}us",
        }
    )

    rows.extend(_dispatch_parity_rows(rng))
    return rows


def _dispatch_parity_rows(rng) -> list:
    """Chosen-vs-best parity of the autotuned dispatcher.

    For a grid of (M, precision) cells, let the autotune cache pick a
    backend, then independently re-time every candidate; report
    ``parity = t_chosen / t_best`` (1.00 = the cache picked the true
    winner; small noise-driven excursions above 1 are expected).
    """
    rows = []
    cache = dispatch.AutotuneCache()
    k, n = 768, 768  # BERT-base attention-out QMM column
    for m, act_bits in ((8, 1), (8, 8), (256, 1), (256, 8)):
        x = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
        xq = Q.quantize_activation(x, act_bits)
        wq = Q.binarize_weight(w)
        # same conditions the tuner timed under: packed weights, colsum folded
        colsum = FA.weight_corrections(wq)
        wq = wq.pack(axis=0)
        chosen = cache.choose(m, k, n, act_bits, 1)
        timings = {
            b: _time(functools.partial(_flow, backend=b), xq, wq, colsum)
            for b in dispatch.candidate_backends(m, k, n, act_bits, 1)
        }
        best = min(timings, key=timings.get)
        parity = timings[chosen] / timings[best]
        rows.append(
            {
                "name": f"qmm_micro/dispatch/M{m}_W1A{act_bits}",
                "us_per_call": timings[chosen],
                "derived": (
                    f"chosen={chosen} best={best} parity={parity:.2f} "
                    + " ".join(f"{b}={t:.0f}us" for b, t in sorted(timings.items()))
                ),
            }
        )
    return rows


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']}")

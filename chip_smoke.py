"""Chip smoke test: granite-8b served on one TPU, every QMM backend pinned.

  python chip_smoke.py [--seed N]

Both phases run in this one process, because a chip belongs to one process
at a time.

1. serve: granite-8b at its published widths and full depth (36 layers).
   The weights are random, made from ``--seed`` by
   ``model_zoo.init_serving_params``, which packs one layer at a time.
   ``ServeEngine`` (4 slots, max_len 2048) serves 8 seeded greedy requests
   with prompts of 64-512 tokens and 16-64 new tokens.  Every request must
   end "ok" with finite logits, and the engine may record no fault, retry
   or demotion event.  Two requests must give the same tokens as
   ``serve_sequential``.
2. kernels: each registered QMM backend (mxu, popcount, pallas, fused) is
   pinned at a granite-8b FFN shape (decode M=8, K=4096, N=14336) in W1A1,
   W1A8 and A8xA8.  Integer cores must be bit-exact against
   ``kernels/ref.py``.  mxu must agree with a float64 dequantized
   reference, and every fp32 result with mxu, to fp32 rounding.  Every Pallas program must hold a Mosaic kernel
   (``tpu_custom_call``), so none ran in interpret mode.  One
   ``backend="auto"`` choice goes through the autotuner, which raises on the
   chip if any candidate fails.

The times printed are smoke timings of one cold run, not benchmark numbers.
The last line of stdout is ``{"ok": true, "device": {...}}``.  A failed check
exits 1.  Without a TPU, or without the repository's ``src/`` beside this
file, the script exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "granite-8b"
SLOTS, MAX_LEN, N_REQUESTS = 4, 2048, 8
PROMPT_LEN, NEW_TOKENS = (64, 512), (16, 64)
QMM_SHAPE = (8, 4096, 14336)  # decode M x granite-8b ffn.up K x N
MODES = (("W1A1", 1, 1), ("W1A8", 8, 1), ("A8xA8", 8, 8))
BAD_EVENTS = frozenset({
    "prefill_fault", "step_fault", "retry_tick", "backend_fault", "demote",
    "nan_logits", "requeue", "request_failed", "deadline_miss",
})


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class CompileClock:
    """Seconds JAX spent in backend compilation, from its monitoring events."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.total += secs


def serve_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import model_zoo as Z
    from repro.runtime.serve_loop import (
        STATE_OK, Request, ServeEngine, serve_sequential,
    )

    cfg = get_config(ARCH)
    print(f"[smoke] {ARCH}: {cfg.n_layers} layers (full depth), d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}, qmm backend {cfg.quant.backend}", flush=True)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        Z.init_serving_params(jax.random.PRNGKey(seed), cfg)
    )
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"[smoke] packed weights {nbytes / 1e9:.3f} GB made in "
          f"{time.perf_counter() - t0:.2f} s", flush=True)

    rng = np.random.default_rng(seed)

    def request(prompt, new):
        return Request(prompt=prompt, max_new_tokens=new, temperature=0.0)

    reqs = [
        request(
            rng.integers(0, cfg.vocab_size, int(rng.integers(*PROMPT_LEN, endpoint=True))
                         ).astype(np.int32),
            int(rng.integers(*NEW_TOKENS, endpoint=True)),
        )
        for _ in range(N_REQUESTS)
    ]
    engine = ServeEngine(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN, seed=seed)
    t0 = time.perf_counter()
    done = engine.run(reqs)
    dt = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in done)
    kinds = [e["kind"] for e in engine.last_events]
    print(f"[smoke] served {len(done)} requests, {tokens} tokens, "
          f"{kinds.count('decode_tick')} decode ticks in {dt:.2f} s "
          f"incl. compile; failed: {len(engine.last_failed)}", flush=True)
    for r in done:
        check(r.state == STATE_OK, f"request {r.rid} ended {r.state!r}")
        check(len(r.output) == r.max_new_tokens,
              f"request {r.rid}: {len(r.output)} of {r.max_new_tokens} tokens")
    bad = sorted(BAD_EVENTS.intersection(kinds))
    check(not bad, f"engine recorded {bad}: "
          f"{[e for e in engine.last_events if e['kind'] in BAD_EVENTS][:3]}")
    # every emitted token passed the engine's finite-logit check (a
    # non-finite row is a nan_logits event, refused above)

    pick = [done[0], done[-1]]
    oracle = serve_sequential(
        cfg, params,
        [request(r.prompt, r.max_new_tokens) for r in pick],
        max_len=MAX_LEN, seed=seed,
    )
    for got, want in zip(pick, oracle):
        check(got.output == want.output,
              f"request {got.rid} differs from serve_sequential: "
              f"{got.output[:8]}... vs {want.output[:8]}...")
    print(f"[smoke] requests {[r.rid for r in pick]} match serve_sequential "
          f"token for token ({[len(r.output) for r in pick]} tokens)", flush=True)


def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import backend_registry, dispatch, packing
    from repro.core import flow_abstraction as FA
    from repro.core import qmm as QE
    from repro.core import quantization as Q
    from repro.core.quantization import QuantTensor
    from repro.kernels import ops, ref

    m, k, n = QMM_SHAPE
    kx, kw = jax.random.split(jax.random.PRNGKey(seed + 1))
    x = jax.random.normal(kx, (m, k), jnp.float32)
    w = jax.random.normal(kw, (k, n), jnp.float32)

    def compiled(fn, *args, mosaic: bool):
        c = jax.jit(fn).lower(*args).compile()
        has_kernel = "tpu_custom_call" in c.as_text()
        check(has_kernel == mosaic,
              f"{fn.__name__}: Mosaic kernel in program = {has_kernel}, "
              f"expected {mosaic}")
        return jax.block_until_ready(c(*args))

    def same(got, want, what):
        got, want = np.asarray(got), np.asarray(want)
        check(got.shape == want.shape and np.array_equal(got, want),
              f"{what}: not bit-exact ({int(np.sum(got != want))} elements differ)")

    def rounding_bound(xq, wq, a_mant, w_mant, mm):
        """Elementwise fp32 rounding bound of the affine epilogue
        ``mm*a1*a2 + a1*g2*rowsum + g1*a2*colsum + g1*g2*K`` in the unsigned
        form (the larger of the two forms the backends use): a few ulps of
        the sum of its terms' magnitudes, for each of the two results."""
        f = lambda v: np.asarray(v, np.float64)  # noqa: E731
        a1, g1, a2, g2 = f(xq.scale), f(xq.offset), f(wq.scale), f(wq.offset)
        row = f(a_mant).sum(axis=1, keepdims=True)
        col = f(w_mant).sum(axis=0, keepdims=True)
        terms = (np.abs(f(mm) * a1 * a2) + np.abs(a1 * g2 * row)
                 + np.abs(g1 * a2 * col) + np.abs(g1 * g2 * k))
        return 8 * np.finfo(np.float32).eps * terms

    def close(got, want, bound, what):
        got, want = np.asarray(got), np.asarray(want)
        check(np.all(np.isfinite(got)), f"{what}: non-finite output")
        excess = np.abs(got - want) - bound
        check(np.all(excess <= 0),
              f"{what}: |diff| exceeds the fp32 rounding bound by up to "
              f"{float(np.max(excess))}")

    names = backend_registry.backend_names(family="qmm")
    check(set(names) == {"mxu", "popcount", "pallas", "fused"},
          f"unexpected qmm backends {names}")
    for mode, ab, wb in MODES:
        t0 = time.perf_counter()
        xq = Q.quantize_activation(x, ab, per_channel_axis=0)
        if wb == 1:
            wq = Q.quantize_weight(w, 1, per_channel_axis=-1)
            colsum = FA.weight_corrections(wq)
            wq = wq.pack(axis=0)
        else:
            wq, colsum = Q.quantize_activation(w, wb), None

        # integer cores: unsigned mantissa planes against the ref oracles
        a_mant = xq.unpack(jnp.int32).mantissa.astype(jnp.uint32)
        w_mant = wq.unpack(jnp.int32).mantissa.astype(jnp.uint32)
        a_pl = packing.pack_bitplanes(a_mant, ab, axis=-1)
        w_pl = packing.pack_bitplanes(w_mant, wb, axis=-2)
        want = ref.bitserial_qmm_ref(a_pl, w_pl, k)

        def popcount_core(a, b):
            return QE.popcount_int_matmul(a, b, ab, wb)

        same(compiled(popcount_core, a_mant, w_mant, mosaic=False), want,
             f"{mode} popcount core")
        if ab == wb == 1:
            same(compiled(ops.popcount_qmm_int, a_pl[0], w_pl[0], mosaic=True),
                 ref.popcount_qmm_ref(a_pl[0], w_pl[0], k), f"{mode} popcount_qmm")
        elif wb == 1:
            a8 = Q.recenter(xq).unpack(jnp.int8).mantissa

            def binary_core(a, wp):
                return ops.binary_qmm_int(a, wp, k)

            same(compiled(binary_core, a8, wq.mantissa, mosaic=True),
                 ref.binary_qmm_ref(a8, wq.mantissa, k), f"{mode} binary_qmm")
        else:
            same(compiled(ops.bitserial_qmm_int, a_pl, w_pl, mosaic=True), want,
                 f"{mode} bitserial_qmm")

        # fused: unit scales and zero offsets make its output the integer MM
        def fused_core(a, b):
            one = QuantTensor(a, jnp.ones((m, 1)), jnp.zeros((m, 1)), bits=ab)
            other = QuantTensor(b, jnp.ones((1, n)), jnp.zeros((1, n)), bits=wb)
            return ops.qmm_fused(one, other)

        same(compiled(fused_core, a_mant, w_mant, mosaic=True),
             want.astype(jnp.float32), f"{mode} fused core")

        # fp32 results: mxu against the dequantized reference, the rest vs mxu
        def pinned(b):
            def run(xq, wq, colsum):
                return QE.qmm(xq, wq, backend=b, w_colsum=colsum)

            run.__name__ = f"qmm[{b}]"
            return run

        out = {
            b: compiled(pinned(b), xq, wq, colsum, mosaic=b in ("pallas", "fused"))
            for b in names
        }
        bound = rounding_bound(xq, wq, a_mant, w_mant, want)
        # float64 on the host: an f32 matmul on the TPU defaults to bf16 passes
        deq = (np.asarray(xq.dequantize(), np.float64)
               @ np.asarray(wq.dequantize(), np.float64))
        close(out["mxu"], deq, bound, f"{mode} mxu vs dequant")
        for b in names:
            close(out[b], out["mxu"], bound, f"{mode} {b} vs mxu")
        print(f"[smoke] {mode} M={m} K={k} N={n}: {', '.join(names)} pass "
              f"({time.perf_counter() - t0:.2f} s)", flush=True)

    cache = dispatch.AutotuneCache()
    chosen = cache.choose(m, k, n, 8, 1, tag="smoke")
    (rec,) = cache.entries.values()
    check(rec.timed and set(rec.timings_us) == set(names),
          f"autotuner timed {sorted(rec.timings_us)}, not every backend")
    print(f"[smoke] autotune W1A8 M={m}: chose {chosen}; best-of-3 us "
          f"{ {b: round(t, 1) for b, t in rec.timings_us.items()} } "
          "(smoke timings, not benchmark numbers)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, SRC)
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repro package from {SRC}: {e}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    print(f"[smoke] device {dev.platform} / {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache_dir}", flush=True)

    clock = CompileClock(jax)
    try:
        for name, run in (("serve", serve_phase), ("kernels", kernel_phase)):
            c0, t0 = clock.total, time.perf_counter()
            run(args.seed)
            print(f"[smoke] phase {name}: wall {time.perf_counter() - t0:.2f} s, "
                  f"compile {clock.total - c0:.2f} s (smoke timings, not "
                  "benchmark numbers)", flush=True)
    except Exception:  # noqa: BLE001 — any failure fails the smoke, loudly
        traceback.print_exc()
        return 1
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"[smoke] peak device memory {stats['peak_bytes_in_use'] / 1e9:.3f} GB",
              flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Attention-scores micro-benchmark: the scores family on this host.

Measures every registered scores-family core (binary AND-popcount,
unpack->int8 MXU, unpack->f32) over attention-shaped problems and reports
chosen-vs-best parity of the autotuned dispatcher — the engine-level
evidence that "attn.qk -> binary" autotunes binary-vs-int-vs-float per
shape without ever changing numerics (all cores are bit-exact; parity is
pure speed).  Run directly::

    PYTHONPATH=src python benchmarks/attn_micro.py
    PYTHONPATH=src python benchmarks/attn_micro.py --smoke --out BENCH_attn.json
    PYTHONPATH=src python benchmarks/attn_micro.py --validate BENCH_attn.json

On CPU the absolute numbers reflect this host; the artifact records the
platform so readers can tell which regime the measured column holds in.
"""

from __future__ import annotations

import functools

import jax

from repro.core import attn_bench as AB
from repro.core import backend_registry, dispatch


def _dispatch_parity_rows() -> list:
    """Chosen-vs-best parity of the scores dispatcher.

    For a grid of attention shapes, let a fresh autotune cache pick a core,
    then independently re-time every candidate; parity = t_chosen / t_best
    (1.00 = the cache picked the true winner; small noise-driven excursions
    above 1 are expected).
    """
    rows = []
    cache = dispatch.AutotuneCache()
    for b, h, g, s, t, dh in AB.SMOKE_SHAPES + ((1, 8, 2, 1, 128, 64),):
        chosen = dispatch.choose_scores_backend(b, h, s, t, dh, cache=cache)
        q_planes = AB.make_planes(b, h, s, dh, seed=1)
        k_planes = AB.make_planes(b, g, t, dh, seed=2)
        timings = {}
        for name in backend_registry.backend_names(family="scores"):
            spec = backend_registry.get_backend(name)
            call = jax.jit(functools.partial(spec.run_scores, dh=dh))
            timings[name] = (
                dispatch._wallclock_timer(lambda: call(q_planes, k_planes))
                * 1e6
            )
        best = min(timings, key=timings.get)
        parity = timings[chosen] / timings[best]
        rows.append(
            {
                "name": f"attn_micro/dispatch/B{b}H{h}G{g}S{s}T{t}d{dh}",
                "us_per_call": timings[chosen],
                "derived": (
                    f"chosen={chosen} best={best} parity={parity:.2f} "
                    + " ".join(
                        f"{n}={v:.0f}us" for n, v in sorted(timings.items())
                    )
                ),
            }
        )
    return rows


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--smoke", action="store_true",
        help="one small shape (the CI cell) instead of the default grid",
    )
    p.add_argument(
        "--out", default="", help="write the BENCH_attn.json artifact here"
    )
    p.add_argument(
        "--validate",
        default="",
        help="validate an existing BENCH_attn.json against the schema and exit",
    )
    args = p.parse_args(argv)

    if args.validate:
        doc = AB.load_attn_bench(args.validate)
        print(
            f"{args.validate}: ok — {len(doc['cells'])} cells, "
            f"backends {doc['backends']}"
        )
        return 0

    shapes = AB.SMOKE_SHAPES if args.smoke else AB.DEFAULT_SHAPES
    doc = AB.run_attn_bench(shapes)
    print(AB.format_table(doc))
    if args.out:
        AB.save_attn_bench(args.out, doc)
        print(f"wrote {args.out}")
    for r in _dispatch_parity_rows():
        print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']}")
    return 0


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())

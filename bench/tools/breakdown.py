"""Break a cell's traced window down by the program's named scopes and spans.

    python3 bench/tools/breakdown.py --workload <name> --seconds <s> \\
        --seeds 11,12 [--untraced]

One process; for each seed one traced run of the cell through the
benchmark's own harness, with the profiler's trace kept and read again by
``bench/spans.py``, and the engine's spans (``ServeEngine.last_events``) of
the window.  With ``--untraced`` each seed first runs once without the
profiler, so that its ``itl_p50_ms`` sets the traced one against it: the
cost of tracing.  One JSON line per seed:

* ``result``: the harness's result line of the traced run (per-layer
  metrics, breakdown, ``correct`` and the checks);
* ``scopes``: ``decode_qmm_ms``, ``decode_attn_ms``, ``decode_head_ms``
  (device ms per ``jit__decode`` execution in the seven QMM sites, the
  attention core, the head), ``tick_host_ms`` and ``admit_dispatch_s``
  (see ``bench/spans.py``);
* ``parts``: the same device time per site and per scope of the core;
* ``itl_p50_ms``: traced, and untraced with ``--untraced``;
* ``idle_gaps``: the ten longest, each labelled by the innermost
  ``serve.*`` span over its middle, or else by the harness's reconstruction
  from request stamps (marked ``guessed``).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def breakdown(cell, seed: int, seconds: float, untraced: bool, **run_kw) -> dict:
    """One seed's line (see the module's docstring)."""
    from bench import harness
    from bench import spans as S
    from repro.runtime import serve_loop

    line = {"workload": cell.name, "seed": seed}
    itl = cell.reader("itl_p50_ms")
    if untraced:
        details = {}
        harness.run(cell, seed, seconds, False, time.perf_counter(), details=details, **run_kw)
        line["itl_p50_ms_untraced"] = itl.read(details["run"])

    tracers, runs = [], []

    class KeptTracer(harness.Tracer):
        def cleanup(self):  # read again below, removed after
            tracers.append(self)

    def recorded(run):
        @functools.wraps(run)
        def wrapped(engine, requests):
            try:
                return run(engine, requests)
            finally:
                runs.append((engine.last_events, engine._t0))

        return wrapped

    details = {}
    saved = harness.Tracer, serve_loop.ServeEngine.run
    harness.Tracer, serve_loop.ServeEngine.run = KeptTracer, recorded(serve_loop.ServeEngine.run)
    try:
        result = harness.run(cell, seed, seconds, True, time.perf_counter(), details=details, **run_kw)
    finally:
        harness.Tracer, serve_loop.ServeEngine.run = saved
    run, (events, t_run) = details["run"], runs[-1]
    tracer = tracers[-1]
    try:
        (path,) = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"), recursive=True)
        trace = S.load(path)
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    t0 = trace.anchor_ns
    t1 = t0 + int((tracer.perf_stop - tracer.perf_anchor) * 1e9)
    groups = S.decode_scopes(trace, t0, t1)
    line["result"] = result
    line["scopes"] = {
        "decode_qmm_ms": groups.get("qmm"),
        "decode_attn_ms": groups.get("attn"),
        "decode_head_ms": groups.get("head"),
        "tick_host_ms": S.tick_host_ms(events, run.open, run.close),
        "admit_dispatch_s": S.admit_dispatch_s(events, run.open, run.close),
    }
    line["parts"] = S.decode_scopes(trace, t0, t1, S.part_of, S.PARTS)
    line["itl_p50_ms"] = itl.read(run)
    anchor_t = tracer.perf_anchor - t_run  # the anchor on the engine's clock
    gaps = run.trace.gaps[:10]
    names = S.label_gaps(gaps, trace.spans, lambda t: t0 + (t - anchor_t) * 1e9, run.requests, run.ticks)
    line["idle_gaps"] = [[name, length] for name, (_, length) in zip(names, gaps)]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--untraced", action="store_true")
    args = ap.parse_args(argv)

    from bench.spec import Cell
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = Cell(args.workload, ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(breakdown(cell, seed, args.seconds, args.untraced)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

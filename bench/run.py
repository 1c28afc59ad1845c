"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last the
``checks``: each number compared beside its limit); the last lines of
standard error repeat the checks.  Without a TPU, or with fewer chips than
the cell asks for, it exits 3 and prints no result.

Run from the root of a checkout: the program is imported from ``src/``, and
JAX's compilation cache is where ``JAX_COMPILATION_CACHE_DIR`` says, or else
in ``.jax_cache/`` of the checkout.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import Cell

    cell = Cell(args.workload, ROOT)
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # every program, small ones too, goes to the cache: later runs compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"[bench] check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

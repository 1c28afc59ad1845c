"""Find the knee of a Poisson cell: the highest offered rate at which the
queue of arrived, not yet admitted requests does not grow through the window.

    python3 bench/tools/sweep.py --workload <name> --seconds <s> --rates 0.2,0.3,0.4

One process; for each rate one run of the cell with that rate in place of
the mix's, without the correctness check.  One JSON line per rate: the
queue at the window's opening and close, requests due and answered, and
``ttft_p50_s``.  The knee goes into the mix file by hand; the benchmark
never searches for it.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def queue_at(run, t: float) -> int:
    return sum(1 for r in run.requests if r.arrival <= t and (r.admitted is None or r.admitted > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    from bench.spec import Cell
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    cell = Cell(args.workload, ROOT)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix["arrival"]["rate_rps"] = rate
        details = {}
        res = harness.run(cell, args.seed, args.seconds, False, time.perf_counter(),
                          verify=False, details=details)
        run = details["run"]
        due = [r for r in run.requests if r.due]
        print(json.dumps({
            "rate_rps": rate,
            "queue_at_open": queue_at(run, run.open),
            "queue_at_close": queue_at(run, run.close),
            "due": len(due),
            "answered_by_close": sum(1 for r in due if r.first is not None and r.first < run.close),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set the limit of ``correct``: the program over many seeds,
the control over a few, in one process (set-up is shared).

    python3 bench/tools/readings.py --workload <name> --seconds <s> \\
        --seeds 11,12,13 --control-seeds 11 [--control-bits 4]

For each seed one JSON line with the numbers ``bench/check.py`` reads
(``max_logit_err``, ``max_logit_gap``) for the program and, on control
seeds, for the control (the reference computed at ``--control-bits``
activations in the program's place) and for a served token altered to the
least likely one.  Each comes with the verdict of the same comparison that
decides ``correct``: the program's has to be true, the control's and the
altered token's false.  A number's lower reading is the largest the program
gives, its upper the smallest the control gives.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--control-bits", type=int, default=4)
    args = ap.parse_args(argv)

    from bench.spec import Cell
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import harness

    cell = Cell(args.workload, ROOT)
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        details = {}
        res = harness.run(
            cell, seed, args.seconds, False, t0,
            control_bits=args.control_bits if seed in control else None, details=details,
        )
        line = {
            "seed": seed,
            "correct": res["correct"],
            "program": details["verdict"].get("readings"),
            "control_correct": details["verdict"].get("control", {}).get("correct"),
            "control": details["verdict"].get("control", {}).get("checks"),
            "altered_token_correct": details["verdict"].get("altered_token", {}).get("correct"),
            "altered_token": details["verdict"].get("altered_token", {}).get("checks"),
            "tokens_compared": res["checks"]["tokens_compared"]["value"],
            "failed": res["failed"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "seconds": time.perf_counter() - t0,
        }
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

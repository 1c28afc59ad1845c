"""Benchmark orchestrator — one module per paper table/figure.

Run:        PYTHONPATH=src python benchmarks/run.py
Per-module invocations and an index: benchmarks/README.md.

Prints ``name,us_per_call,derived`` CSV:

  table1_resources   Table I   FPGA resource breakdown (structural model)
  table2_comparison  Table II  throughput / power / GOPS/W vs paper
  fig5_tradeoff      Fig. 5    precision <-> efficiency trade-off
  qmm_micro          (engine)  measured QMM backend micro-benchmarks
  compression_bench  (dist)    int8 error-feedback gradient all-reduce
  roofline           §Roofline 3-term analysis from dry-run artifacts
"""

from __future__ import annotations

import os
import sys
import traceback

# allow `python benchmarks/run.py` from the repo root (sys.path[0] is the
# benchmarks dir itself in that case, hiding the package)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    from benchmarks import (
        compression_bench,
        fig5_tradeoff,
        qmm_micro,
        roofline,
        table1_resources,
        table2_comparison,
    )

    modules = [
        ("table1", table1_resources),
        ("table2", table2_comparison),
        ("fig5", fig5_tradeoff),
        ("qmm_micro", qmm_micro),
        ("compression", compression_bench),
        ("roofline", roofline),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        try:
            for r in mod.run():
                print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']}")
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},0.00,ERROR", file=sys.stderr)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

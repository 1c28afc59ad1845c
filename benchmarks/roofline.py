"""Roofline analysis from dry-run artifacts (EXPERIMENTS.md §Roofline).

Reproduces: no paper table — the TPU-side roofline accounting for the
serving claims.  Needs dry-run artifacts first:
    PYTHONPATH=src python -m repro.launch.dryrun --all
Run:        PYTHONPATH=src python benchmarks/roofline.py

QMM-backend mode (``repro.core.qmm_roofline``): place every *registered*
QMM backend (mxu / popcount / pallas / fused, plus anything added later)
against the memory-bandwidth roof using its registry ``traffic_model``,
and record the ``BENCH_qmm.json`` artifact:

    PYTHONPATH=src python benchmarks/roofline.py --qmm-out BENCH_qmm.json
    PYTHONPATH=src python benchmarks/roofline.py --smoke \
        --qmm-out artifacts/BENCH_qmm_ci.json      # CI cell, tiny shapes
    PYTHONPATH=src python benchmarks/roofline.py --validate BENCH_qmm.json

Per (arch x shape x mesh) cell:

  compute term    = HLO_FLOPs_per_device / peak_FLOP/s        [s]
  memory term     = HLO_bytes_per_device / HBM_bw             [s]
  collective term = collective_bytes_per_device / link_bw     [s]

``cost_analysis()`` numbers are PER-DEVICE and partition-aware (calibrated:
a dp-sharded op reports global/dp, so TP-idle replication shows up as extra
per-device flops — exactly what a roofline should charge).  Scan bodies are
counted once by XLA; the dry-run's ``period_body`` record corrects this:
``corrected = raw + (n_periods - 1) * body``.

MODEL_FLOPS = 6*N_active*D tokens (train; includes backward) or
2*N_active*D (inference) + attention terms — the useful-work yardstick; the
ratio MODEL_FLOPS / (HLO_FLOPs * n_devices) exposes remat/replication waste.

Hardware constants (TPU v5e per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI (3D-torus links; cross-pod DCN is slower but the pod axis
is DP-only by design).
"""

from __future__ import annotations

import glob
import json
import os

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

ARTIFACT_DIR = os.environ.get("DRYRUN_DIR", "artifacts/dryrun")


def model_flops(rec: dict) -> float:
    """Analytical useful FLOPs for the cell (global, all devices)."""
    n = rec["active_params"]
    b, s = rec["global_batch"], rec["seq_len"]
    if rec["kind"] == "train":
        return 6.0 * n * b * s  # fwd 2ND + bwd 4ND
    if rec["kind"] == "prefill":
        return 2.0 * n * b * s
    return 2.0 * n * b  # decode: one token per sequence


def corrected(rec: dict, key: str) -> float:
    raw = rec["cost"]["flops"] if key == "flops" else rec["cost"]["bytes_accessed"]
    body = rec.get("period_body") or {}
    if "error" in body or not body:
        return float(raw or 0.0)
    nper = body.get("n_periods", 0)
    bval = body.get("flops" if key == "flops" else "bytes_accessed", 0.0)
    return float(raw or 0.0) + max(nper - 1, 0) * float(bval or 0.0)


def corrected_collective_bytes(rec: dict) -> float:
    raw = rec["collectives"]["total_bytes"]
    body = rec.get("period_body") or {}
    if "error" in body or not body or not isinstance(body.get("collectives"), dict):
        return float(raw)
    nper = body.get("n_periods", 0)
    return float(raw) + max(nper - 1, 0) * float(body["collectives"]["total_bytes"])


def analyze(rec: dict) -> dict:
    n_dev = 1
    for v in rec.get("mesh_shape", {}).values():
        n_dev *= v
    flops_dev = corrected(rec, "flops")
    bytes_dev = corrected(rec, "bytes_accessed")
    coll_dev = corrected_collective_bytes(rec)
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = coll_dev / LINK_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec)
    ratio = mf / (flops_dev * n_dev) if flops_dev else 0.0
    bound = max(terms.values())
    # roofline fraction: useful work per device-second at the binding limit
    useful_frac = (mf / n_dev / PEAK_FLOPS) / bound if bound else 0.0
    return {
        "cell": f"{rec['arch']}/{rec['shape']}/{rec['mesh']}",
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": mf,
        "hlo_flops_global": flops_dev * n_dev,
        "useful_ratio": ratio,
        "roofline_fraction": useful_frac,
        "n_devices": n_dev,
    }


def load_records(mesh: str = "single"):
    recs = []
    for path in sorted(glob.glob(os.path.join(ARTIFACT_DIR, f"*__{mesh}.json"))):
        with open(path) as f:
            rec = json.load(f)
        recs.append(rec)
    return recs


def run() -> list:
    rows = []
    for mesh in ("single", "multi"):
        for rec in load_records(mesh):
            if rec.get("status") == "skip":
                rows.append(
                    {
                        "name": f"roofline/{rec['arch']}/{rec['shape']}/{mesh}",
                        "us_per_call": 0.0,
                        "derived": f"SKIP ({rec['reason'][:60]})",
                    }
                )
                continue
            if rec.get("status") != "ok":
                rows.append(
                    {
                        "name": f"roofline/{rec['arch']}/{rec['shape']}/{mesh}",
                        "us_per_call": 0.0,
                        "derived": f"status={rec.get('status')}",
                    }
                )
                continue
            a = analyze(rec)
            dom_t = max(a["t_compute_s"], a["t_memory_s"], a["t_collective_s"])
            rows.append(
                {
                    "name": f"roofline/{a['cell']}",
                    "us_per_call": dom_t * 1e6,
                    "derived": (
                        f"compute={a['t_compute_s']:.3e}s"
                        f" memory={a['t_memory_s']:.3e}s"
                        f" coll={a['t_collective_s']:.3e}s"
                        f" dom={a['dominant']}"
                        f" useful={a['useful_ratio']:.2f}"
                        f" roofline_frac={a['roofline_fraction']:.2f}"
                    ),
                }
            )
    return rows


def run_qmm(smoke: bool = False, out: str | None = None) -> dict:
    """QMM-backend roofline over every registered backend; optional artifact."""
    from repro.core import qmm_roofline as R

    if smoke:
        doc = R.run_qmm_roofline(R.SMOKE_SHAPES, R.SMOKE_PRECISIONS, warmup=1, reps=1)
    else:
        doc = R.run_qmm_roofline()
    if out:
        R.save_qmm_bench(out, doc)
    return doc


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "--smoke",
        action="store_true",
        help="QMM mode with tiny shapes / single rep (the CI cell)",
    )
    p.add_argument(
        "--qmm-out",
        metavar="PATH",
        help="run the QMM-backend roofline and write the BENCH_qmm.json artifact",
    )
    p.add_argument(
        "--validate",
        metavar="PATH",
        help="validate an existing BENCH_qmm.json against the schema and exit",
    )
    args = p.parse_args(argv)

    if args.validate:
        from repro.core import qmm_roofline as R

        doc = R.load_qmm_bench(args.validate)
        print(
            f"{args.validate}: ok — {len(doc['cells'])} cells, "
            f"backends {sorted({c['backend'] for c in doc['cells']})}"
        )
        return 0
    if args.smoke or args.qmm_out:
        from repro.core import qmm_roofline as R

        doc = run_qmm(smoke=args.smoke, out=args.qmm_out)
        print(R.format_table(doc))
        if args.qmm_out:
            print(f"wrote {args.qmm_out}")
        return 0
    # legacy mode: the dry-run-artifact roofline table
    for r in run():
        print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']}")
    return 0


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())

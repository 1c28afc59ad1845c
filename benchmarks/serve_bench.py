"""Open-loop serving benchmark -> BENCH_serve.json (perf trajectory).

    PYTHONPATH=src python benchmarks/serve_bench.py \
        --arch granite-8b --smoke --n-requests 16 --rate 8 \
        --out BENCH_serve.json

Drives the continuous-batching ``ServeEngine`` with the seeded Poisson
traffic generator (runtime.traffic) and persists requests/sec plus p50/p99
token latency.  The workload is fully determined by the CLI config, so the
committed ``BENCH_serve.json`` is a trajectory artifact: any PR touching
the serving hot path reruns the same command and diffs the numbers
(absolute values are host-dependent; the trajectory is what matters).

Latency accounting: token latency = time-to-first-token measured from the
request's *arrival* (queueing delay included — this is an open-loop bench)
plus every inter-token gap; TTFT percentiles are reported separately.
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro.configs import get_config, list_configs
from repro.configs.smoke import smoke_variant
from repro.models import model_zoo as Z
from repro.runtime.faults import parse_fault_plan
from repro.runtime.serve_loop import ServeEngine
from repro.runtime.traffic import TrafficConfig, generate_requests, save_bench, summarize_bench


def run_bench(args) -> dict:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)
    plan = parse_fault_plan(args.fault_plan)
    serving = Z.init_serving_params(jax.random.PRNGKey(args.seed), cfg)
    engine = ServeEngine(
        cfg,
        serving,
        batch_slots=args.slots,
        max_len=args.max_len,
        seed=args.seed,
        autotune_cache_path=args.autotune_cache,
        fault_plan=plan,
        snapshot_every=args.snapshot_every,
        snapshot_dir=args.snapshot_dir,
    )
    tc = TrafficConfig(
        n_requests=args.n_requests,
        rate_rps=args.rate,
        prompt_len=(args.prompt_min, args.prompt_max),
        new_tokens=(args.new_min, args.new_max),
        temperature=args.temperature,
        deadline_s=args.deadline_s,
        seed=args.seed,
    )
    requests = generate_requests(tc, cfg.vocab_size)

    if args.warmup:
        # compile prefill/decode outside the measured window — with faults
        # suspended, so the chaos (and any demotion it triggers) lands
        # entirely inside the measured run whose events feed availability
        warm = generate_requests(
            TrafficConfig(n_requests=1, rate_rps=0.0, prompt_len=tc.prompt_len,
                          new_tokens=(2, 2), seed=tc.seed + 1),
            cfg.vocab_size,
        )
        engine.fault_plan = parse_fault_plan(None)
        engine.run(warm)
        engine.fault_plan = plan

    t0 = time.perf_counter()
    done = engine.run(requests)
    wall = time.perf_counter() - t0

    config = {
        "arch": args.arch,
        "smoke": bool(args.smoke),
        "batch_slots": args.slots,
        "max_len": args.max_len,
        "quant_mode": cfg.quant.mode_name,
        "traffic": tc.to_dict(),
        "fault_plan": plan.to_dict() if not plan.is_noop() else None,
    }
    summary = summarize_bench(done, wall, config, events=engine.last_events)
    # zero LOST requests: every request reaches a terminal state, and every
    # successful one carries its full output (failures/deadline misses are
    # recorded in the availability block, never dropped silently)
    assert all(r.state in ("ok", "failed", "deadline") for r in done)
    assert all(
        len(r.output) == r.max_new_tokens for r in done if r.state == "ok"
    )
    return summary


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="granite-8b", choices=list(list_configs()))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=8.0, help="Poisson arrivals/s; <=0 = all at t0")
    ap.add_argument("--prompt-min", type=int, default=4)
    ap.add_argument("--prompt-max", type=int, default=12)
    ap.add_argument("--new-min", type=int, default=4)
    ap.add_argument("--new-max", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--warmup", action="store_true", default=True)
    ap.add_argument("--no-warmup", dest="warmup", action="store_false")
    ap.add_argument("--autotune-cache", default=None)
    ap.add_argument("--fault-plan", default=None,
                    help="JSON FaultPlan (runtime.faults) for a chaos run, e.g. "
                         "'{\"decode_fail_ticks\": [1]}'")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline (seconds from arrival)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot engine state every K decode ticks (0 = off)")
    ap.add_argument("--snapshot-dir", default=None)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    summary = run_bench(args)
    save_bench(args.out, summary)
    print(json.dumps(summary, indent=2, sort_keys=True))
    print(f"[serve_bench] wrote {args.out}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

"""Batched serving driver (the accelerator's role: binary-weight inference).

  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
      --requests 8 --max-new 16

Initializes a model from ``--seed`` and runs the offline weight pipeline
(binarize -> bit-pack -> colsum fold, the paper's 'performed offline' step)
one layer at a time (``model_zoo.init_serving_params``), so a full-size
model such as granite-8b fits one TPU v5e without ``--smoke``.  Then it
serves a queue of requests through the slot-managed continuous-batching
engine.  The process exits 1 if any request ends "failed" and no
``--fault-plan`` was given.

Two request sources:

* fixed queue (default): ``--requests`` identical-shape prompts, all
  arriving at t=0 — the quick eyeball run.
* open-loop traffic (``--traffic``): seeded Poisson arrivals with uniform
  prompt/output length ranges (runtime.traffic) — the serve_bench workload;
  add ``--bench-out`` to persist the BENCH_serve.json summary.

Robustness knobs (docs/serving-robustness.md):

* ``--fault-plan '{"decode_fail_ticks": [3]}'`` — inject a deterministic
  failure schedule (runtime.faults.FaultPlan JSON) into the run.
* ``--deadline-s 2.0`` — per-request deadline from arrival; expired
  requests terminate with state "deadline" instead of holding a slot.
* ``--snapshot-every 8 --snapshot-dir /tmp/serve-snap`` — checkpoint the
  full engine state (queue, slot caches/cursors/budgets, sampler states)
  every 8 decode ticks.

Crash recovery — a killed process finishes its in-flight requests
token-for-token identical to an uninterrupted run::

  # serving process (killed mid-batch: SIGKILL, OOM, preemption, ...)
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
      --requests 8 --max-new 24 --snapshot-every 4 --snapshot-dir /tmp/snap

  # replacement process: same arch/seed/slots, --resume instead of a queue
  PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
      --snapshot-dir /tmp/snap --resume
"""

import argparse
import sys
import time

import jax
import numpy as np

from repro.configs import get_config, list_configs
from repro.configs.smoke import smoke_variant
from repro.models import model_zoo as Z
from repro.runtime.faults import parse_fault_plan
from repro.runtime.serve_loop import Request, ServeEngine
from repro.runtime.traffic import (
    TrafficConfig,
    generate_requests,
    save_bench,
    summarize_bench,
)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=list(list_configs()))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as slots emit them (per-request callbacks)")
    ap.add_argument("--autotune-cache", default=None,
                    help="JSON path for persisted QMM autotune verdicts")
    # open-loop traffic mode
    ap.add_argument("--traffic", action="store_true",
                    help="Poisson open-loop workload instead of the fixed queue")
    ap.add_argument("--rate", type=float, default=8.0)
    ap.add_argument("--bench-out", default=None,
                    help="write the BENCH_serve.json summary here")
    # robustness knobs (docs/serving-robustness.md)
    ap.add_argument("--fault-plan", default=None,
                    help="JSON FaultPlan (runtime.faults) injected into the run")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline in seconds from arrival")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot engine state every K decode ticks (0 = off)")
    ap.add_argument("--snapshot-dir", default=None,
                    help="CheckpointManager directory for engine snapshots")
    ap.add_argument("--resume", action="store_true",
                    help="resume in-flight requests from --snapshot-dir instead "
                         "of serving a fresh queue")
    args = ap.parse_args()
    if args.resume and not args.snapshot_dir:
        ap.error("--resume requires --snapshot-dir")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_variant(cfg)

    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    serving = Z.init_serving_params(key, cfg)

    # packed-weight footprint accounting (the paper's compression headline);
    # the fp32 latent model is only ever a shape here
    def nbytes(t):
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(t))

    full = nbytes(jax.eval_shape(lambda k: Z.init_params(k, cfg), key))
    packed = nbytes(serving)
    print(
        f"[serve] weights: fp32 latent {full/1e6:.1f} MB -> packed {packed/1e6:.1f} MB"
        f" ({full/packed:.1f}x)"
    )

    engine = ServeEngine(
        cfg,
        serving,
        batch_slots=args.slots,
        max_len=args.max_len,
        seed=args.seed,
        autotune_cache_path=args.autotune_cache,
        fault_plan=parse_fault_plan(args.fault_plan),
        snapshot_every=args.snapshot_every,
        snapshot_dir=args.snapshot_dir,
    )
    if args.resume:
        t0 = time.perf_counter()
        done = engine.resume()
        dt = time.perf_counter() - t0
    else:
        if args.traffic:
            tc = TrafficConfig(
                n_requests=args.requests,
                rate_rps=args.rate,
                prompt_len=(max(1, args.prompt_len // 2), args.prompt_len),
                new_tokens=(max(1, args.max_new // 2), args.max_new),
                temperature=args.temperature,
                deadline_s=args.deadline_s,
                seed=args.seed,
            )
            reqs = generate_requests(tc, cfg.vocab_size)
        else:
            reqs = [
                Request(
                    prompt=rng.integers(
                        0, cfg.vocab_size, size=(args.prompt_len,)
                    ).astype(np.int32),
                    max_new_tokens=args.max_new,
                    temperature=args.temperature,
                    deadline_s=args.deadline_s,
                )
                for _ in range(args.requests)
            ]
        if args.stream:
            for i, r in enumerate(reqs):
                r.on_token = lambda tok, i=i: print(f"  [stream] req{i} -> {tok}")

        t0 = time.perf_counter()
        done = engine.run(reqs)
        dt = time.perf_counter() - t0
    total_tokens = sum(len(r.output) for r in done)
    print(f"[serve] {len(done)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s incl. compile)")
    for i, r in enumerate(done[:4]):
        print(f"  req{i}: prompt[:4]={np.asarray(r.prompt)[:4].tolist()} -> out[:8]={r.output[:8]}")
    print(f"[serve] failed: {len(engine.last_failed)} of {len(done)} requests "
          f"{engine.last_failed}")
    if args.bench_out:
        summary = summarize_bench(
            done, dt,
            {"arch": args.arch, "smoke": bool(args.smoke),
             "batch_slots": args.slots, "max_len": args.max_len,
             "traffic": args.traffic},
            events=engine.last_events,
        )
        save_bench(args.bench_out, summary)
        print(f"[serve] bench summary -> {args.bench_out} "
              f"(rps={summary['rps']:.2f}, p50={summary['p50_ms']:.1f}ms, "
              f"p99={summary['p99_ms']:.1f}ms)")
    if engine.last_failed and args.fault_plan is None:
        sys.exit(1)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()

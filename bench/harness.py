"""Run one cell once: set-up, the measured window, the check, the result.

Set-up (``setup_s``, from process start to the window's opening):

1. the weights, made on the device from the seed in one jitted call
   (``model_zoo.init_serving_params``);
2. a warm-up through the engine itself: one request at each pooled prompt
   length, so the eager prefill of every length and the decode step are in
   JAX's persistent compilation cache (the first run in a checkout compiles
   them, later runs load them);
3. a ramp of the cell's own traffic (``bench/window.py`` says when the
   window opens).

The window is one ``ServeEngine.run`` over the plan.  After it, the device's
peak memory is read, the program's state is freed, and the served tokens are
compared with the plain reference (``bench/check.py``).
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import glob
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from bench import check, traffic
from bench import trace as T
from bench import window as W
from bench.spec import Cell

#: configuration keys that must equal the program's registered ArchConfig
ARCH_KEYS = (
    "n_layers", "d_model", "n_heads", "n_kv_heads", "d_head", "d_ff", "vocab_size",
    "rope_theta", "norm_eps", "tie_embeddings", "ffn_type",
)
QUANT_KEYS = ("weight_bits", "act_bits", "attn_act_bits", "kv_cache_bits", "backend")
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def device_info(chips: int, require_tpu: bool = True) -> Dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(
            f"cell needs {chips} TPU chip(s); JAX found {len(devs)} {devs[0].platform} device(s)"
        )
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def program_config(config: Dict):
    """The program's ArchConfig for a configuration file, checked against it."""
    from repro.configs import get_config

    cfg = get_config(config["registry_name"])
    if config.get("overrides"):
        cfg = dataclasses.replace(cfg, **config["overrides"])
    for k in ARCH_KEYS:
        if getattr(cfg, k) != config[k]:
            raise ValueError(f"{config['registry_name']}: {k} is {getattr(cfg, k)!r}, file says {config[k]!r}")
    for k in QUANT_KEYS:
        if getattr(cfg.quant, k) != config[k]:
            raise ValueError(f"{config['registry_name']}: quant {k} is {getattr(cfg.quant, k)!r}, file says {config[k]!r}")
    return cfg


class CompileClock:
    """Tracing, lowering and compile (or cache load) seconds, as JAX reports them."""

    def __init__(self):
        import jax

        self.events = []  # (perf_counter at the report, seconds)
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name in COMPILE_EVENTS:
            self.events.append((time.perf_counter(), secs))

    def since(self, t0: float):
        """The events, timed from ``t0``."""
        return [(t - t0, secs) for t, secs in self.events]

    def close(self) -> None:
        self._jax.monitoring.unregister_event_duration_listener(self._on_event)


class Tracer:
    """The profiler over the window, with an anchor for the engine's clock."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.perf_anchor = self.perf_stop = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.perf_anchor = time.perf_counter()
        with jax.profiler.TraceAnnotation(T.ANCHOR):
            pass

    def stop(self) -> None:
        """Mark the window's close; the trace is written after the run, so
        that writing it stalls no request still waiting for its first token."""
        self.perf_stop = time.perf_counter()

    def finish(self) -> None:
        import jax

        if self.perf_anchor is not None:
            jax.profiler.stop_trace()
        if self.perf_stop is None:
            self.perf_stop = time.perf_counter()

    def summary(self, anchor_t: float) -> T.Summary:
        files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"), recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        devices, anchor_ns = T.load(files[0])
        if anchor_ns is None:
            raise RuntimeError(f"anchor {T.ANCHOR!r} not found in the trace")
        t1 = anchor_ns + int((self.perf_stop - self.perf_anchor) * 1e9)
        return T.summarize(devices, anchor_ns, t1, anchor_ns, anchor_t)

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def _warm(engine, mix: Dict, vocab: int, seed: int) -> None:
    """One request at every pooled prompt length, through the engine."""
    from repro.runtime.serve_loop import Request

    rng = np.random.default_rng([seed, 2])
    engine.run([
        Request(prompt=rng.integers(0, vocab, n).astype(np.int32), max_new_tokens=2)
        for n in mix["prompt_pool"]
    ])


def run(
    cell: Cell,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    *,
    require_tpu: bool = True,
    control_bits: Optional[int] = None,
    verify: bool = True,
    details: Optional[Dict] = None,
    log=sys.stderr,
) -> Dict:
    """One run of ``cell``; returns the result line's object.

    ``require_tpu=False`` lets a test drive a run on the CPU; ``control_bits``
    also reads the control (``bench/tools/readings.py``); ``verify=False``
    skips the reference (the knee sweep); ``details`` receives the
    :class:`~bench.window.Run` record, the requests compared, and the
    check's verdict with its readings (and the control's verdict).
    """
    import jax

    dev = device_info(cell.chips, require_tpu)
    from repro.models import model_zoo as Z
    from repro.runtime.serve_loop import STATE_FAILED, Request, ServeEngine

    mix, config = cell.mix, cell.config
    cfg = program_config(config)
    params = jax.block_until_ready(Z.init_serving_params(jax.random.PRNGKey(seed), cfg))
    engine = ServeEngine(cfg, params, batch_slots=mix["slots"], max_len=config["max_len"], seed=seed)
    _warm(engine, mix, cfg.vocab_size, seed)

    reqs = [
        Request(prompt=p.prompt, max_new_tokens=p.max_new_tokens,
                temperature=p.temperature, arrival_s=p.arrival_s)
        for p in traffic.plan(mix, seed, seconds, cfg.vocab_size)
    ]
    tracer = Tracer() if trace else None
    backlog = mix["arrival"]["kind"] == "backlog"
    win = W.Window(
        reqs, seconds, mix["grace_s"],
        open_at_s=None if backlog else mix["ramp_s"],
        open_after_first_tokens=mix["slots"] if backlog else None,
        on_open=tracer.start if tracer else None,
        on_close=tracer.stop if tracer else None,
    )
    tap = check.LogitTap(cfg.vocab_size, seed)
    for r in reqs:
        r.on_token = functools.partial(_tapped, tap, r, r.on_token)
    clock = CompileClock()
    try:
        with tap:
            engine.run(reqs)
    finally:
        clock.close()
        if tracer:
            tracer.finish()
    t_run = engine._t0  # the origin of every time the engine stamps
    if not win.opened:
        raise RuntimeError("no token was emitted after the ramp: the window never opened")
    memory_peak = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(memory_peak.get("peak_bytes_in_use", 0))

    records = W.records(reqs, win)
    ticks = W.ticks(engine.last_events, reqs)
    summary = None
    if tracer:
        t_trace = time.perf_counter()
        summary = tracer.summary(anchor_t=tracer.perf_anchor - t_run)
        print(f"[bench] trace read in {time.perf_counter() - t_trace:.1f} s", file=log, flush=True)
        tracer.cleanup()
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
    family = cell.family()
    record = W.Run(
        seconds=seconds, open=win.open, close=win.close, grace_s=mix["grace_s"],
        setup_s=t_run + win.open - t_start, requests=records, ticks=ticks,
        compile_events=clock.since(t_run),
        need=functools.partial(family.decode_need, config),
        peak=cell.peaks(dev["kind"]) if trace else {},
        trace=summary,
    )
    metrics = {}
    for m in cell.metrics("per_layer" if trace else "end_to_end"):
        value = cell.reader(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # a request failed anywhere in the run is a wrong answer of the timed path
    attempted = [r for r in records if r.due or r.state == STATE_FAILED or any(map(record.in_window, r.token_times))]
    failed = [r for r in attempted if r.state == STATE_FAILED or (r.due and not record.answered(r))]
    _log_window(log, record, attempted, failed)

    if details is not None:
        details["run"] = record
    picked = check.sample(reqs, win, mix["check_tokens"], seed)
    if details is not None:
        details["picked"] = picked
    del engine, params
    gc.collect()
    if verify:
        t_check = time.perf_counter()
        verdict = check.compare(cell.reference(), config, seed, picked, tap, control_bits)
        print(f"[bench] reference over {len(picked)} requests in {time.perf_counter() - t_check:.1f} s; "
              f"readings {verdict.get('readings')}, control {verdict.get('control')}", file=log, flush=True)
    else:
        verdict = {"correct": False, "checks": {}}

    # a request that failed, or went unanswered past the grace, is a wrong answer
    checks = dict(verdict["checks"], failed_requests={"value": len(failed), "limit": 0})
    result = {
        "correct": verdict["correct"] and not failed,
        "attempted": len(attempted),
        "failed": len(failed),
        "metrics": metrics,
        "device": dev,
    }
    if summary is not None:
        result["breakdown"] = {
            "device_ops": [[name, s] for name, s in summary.top_ops],
            "idle_gaps": [
                [T.label_gap(start, length, records, ticks), length]
                for start, length in summary.gaps[:10]
            ],
        }
    if details is not None:
        details["verdict"] = verdict
    result["checks"] = checks
    return result


def _tapped(tap, req, hook, token) -> None:
    tap.claim(req)
    hook(token)


def _log_window(log, run: W.Run, attempted, failed) -> None:
    """What the window held, for the reader of standard error."""
    in_win = [t for r in run.requests for t in r.token_times if run.in_window(t)]
    ticks = [t for t in run.ticks if run.in_window(t.t)]
    gaps = [
        b - a
        for r in run.requests
        for a, b in zip(r.token_times, r.token_times[1:])
        if run.open <= a and b <= run.close
    ]
    qs = np.percentile(gaps, [50, 90, 95, 99]).round(4).tolist() if gaps else []
    print(
        f"[bench] window {run.open:.2f}-{run.close:.2f} s: {len(attempted)} attempted, "
        f"{len(failed)} failed, {len(in_win)} tokens, {len(ticks)} ticks, "
        f"token gaps p50/p90/p95/p99 {qs} s, setup {run.setup_s:.2f} s",
        file=log, flush=True,
    )

"""Continuous-batching serving engine: slot-managed decode over the binary
Transformer datapath (what BETA does at the edge, scaled to a pod).

Components:

* ``make_prefill`` / ``make_decode_step`` — jitted SPMD steps over packed
  serving params + quantized KV caches (sharding per runtime.sharding).
  These are the functions the ``prefill_*`` / ``decode_*`` / ``long_*``
  dry-run cells lower.
* ``ServeEngine`` — host-side continuous-batching loop: an admission queue
  feeds a fixed-size packed decode batch.  Each slot carries its own request
  state (cache row with per-row position cursor + calibration affines,
  remaining-token budget, per-request RNG).  A newly admitted request is
  prefilled at its EXACT prompt length (batch 1, no padding) and spliced
  into a free slot with ``model_zoo.cache_insert`` while the other slots
  keep decoding; a finished slot is reset and immediately refilled from the
  queue — no wave ever stalls on its longest request.
* ``serve_sequential`` — the naive one-request-at-a-time oracle the
  differential tests compare against.

Numerical contract (what the differential test pins down): serve-mode
activation quantization is per-token and cache state is per-row, so a
request's tokens are bit-identical no matter which requests share the
batch — continuous batching is a pure scheduling optimization.

The decode step is the latency-critical path: one token per call against a
cache of ``max_len`` — its roofline is memory-bound, which is exactly where
the 1-bit packed weights + int8 KV cache pay off (EXPERIMENTS.md §Roofline).

With ``backend="auto"`` in the quant config, prefill and decode QMMs
(dense and attention projections; MoE expert MMs always use the MXU flow)
tune under separate autotune keys ("prefill" vs "decode" phases, set in
model_zoo) — their M dims differ by orders of magnitude, so the winning
backend can differ too.  Pass ``autotune_cache_path`` to ``ServeEngine`` to
persist/restore the measured verdicts across serving processes.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.configs.base import ArchConfig
from repro.core import dispatch
from repro.models import model_zoo as Z
from repro.runtime import sharding as SH

__all__ = [
    "make_prefill",
    "make_decode_step",
    "ServeEngine",
    "Request",
    "serve_sequential",
    "STATE_PENDING",
    "STATE_OK",
    "STATE_FAILED",
    "STATE_DEADLINE",
    "TERMINAL_STATES",
]


def serving_params_shardings(cfg: ArchConfig, mesh: Mesh):
    tmpl = jax.eval_shape(
        lambda k: Z.init_serving_params(k, cfg),
        jax.random.PRNGKey(0),
    )
    return SH.params_shardings(tmpl, mesh), tmpl


def make_prefill(cfg: ArchConfig, mesh: Mesh, batch: int, prompt_len: int, max_len: int):
    p_sh, _ = serving_params_shardings(cfg, mesh)
    cache_tmpl = jax.eval_shape(lambda: Z.init_cache(batch, max_len, cfg))
    c_sh = SH.cache_shardings(cache_tmpl, mesh, batch)
    tok_sh = NamedSharding(mesh, SH.logical_batch_spec(batch, prompt_len, mesh))
    has_frontend = cfg.encoder is not None

    if has_frontend:

        def fn(params, tokens, cache, frontend):
            return Z.prefill(params, tokens, cfg, cache, frontend)

        in_sh = (p_sh, tok_sh, c_sh, None)
    else:

        def fn(params, tokens, cache):
            return Z.prefill(params, tokens, cfg, cache)

        in_sh = (p_sh, tok_sh, c_sh)

    return jax.jit(
        fn,
        in_shardings=in_sh,
        out_shardings=(None, c_sh),
        donate_argnums=(2,),
    )


def make_decode_step(cfg: ArchConfig, mesh: Mesh, batch: int, max_len: int):
    p_sh, _ = serving_params_shardings(cfg, mesh)
    cache_tmpl = jax.eval_shape(lambda: Z.init_cache(batch, max_len, cfg))
    c_sh = SH.cache_shardings(cache_tmpl, mesh, batch)

    def fn(params, tokens, cache):
        return Z.decode_step(params, tokens, cfg, cache)

    return jax.jit(
        fn,
        in_shardings=(p_sh, None, c_sh),
        out_shardings=(None, c_sh),
        donate_argnums=(2,),
    )


# ---------------------------------------------------------------------------
# host-side engine
# ---------------------------------------------------------------------------


#: Terminal request states (``Request.state``).
STATE_PENDING = "pending"
STATE_OK = "ok"
STATE_FAILED = "failed"
STATE_DEADLINE = "deadline"
TERMINAL_STATES = (STATE_OK, STATE_FAILED, STATE_DEADLINE)


@dataclasses.dataclass
class Request:
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    # open-loop traffic: seconds (from run start) before the request exists
    arrival_s: float = 0.0
    # optional per-request deadline, seconds FROM ARRIVAL; None = no deadline.
    # A request past its deadline is terminated with state "deadline" —
    # whether still queued or mid-generation — instead of holding a slot.
    deadline_s: Optional[float] = None
    # optional per-request streaming callback: on_token(token_id).  On a
    # retry (re-admission after a failure) the replayed tokens stream again
    # — consumers that must not double-deliver should key on Request.retries.
    on_token: Optional[Callable[[int], None]] = None
    # filled by the engine:
    output: Optional[List[int]] = None
    rid: Optional[int] = None  # engine-assigned request id (RNG key)
    state: str = STATE_PENDING  # -> "ok" | "failed" | "deadline"
    retries: int = 0  # re-admissions after failures (NaN logits, step faults)
    t_admitted: Optional[float] = None  # seconds from run start
    t_first_token: Optional[float] = None
    t_finished: Optional[float] = None
    token_times: Optional[List[float]] = None  # one stamp per output token


def _sample(logits: np.ndarray, temperature: float, rng: np.random.Generator) -> int:
    """Shared by the engine and the sequential oracle: greedy at T<=0,
    softmax sampling otherwise, against the request's OWN rng stream."""
    if temperature <= 0:
        return int(np.argmax(logits))
    z = logits.astype(np.float64) / temperature
    z = z - z.max()
    p = np.exp(z)
    p = p / p.sum()
    return int(rng.choice(len(p), p=p))


def _request_rng(seed: int, rid: int) -> np.random.Generator:
    """Per-request stream keyed on (engine seed, request id): sampling is
    independent of which slot served the request and of its co-batch."""
    return np.random.default_rng([seed, rid])


@dataclasses.dataclass
class _Slot:
    req: Request
    remaining: int
    rng: np.random.Generator


@dataclasses.dataclass
class _EngineState:
    """Everything ``_serve`` advances — and exactly what a snapshot captures.

    ``requests`` is the full set in rid order; ``queue`` and ``slots`` hold
    references into it.  ``tick`` counts *successful* decode ticks (a retried
    tick does not advance it), ``snaps`` counts snapshot attempts.
    """

    requests: List[Request]
    queue: List[Request]
    slots: List[Optional[_Slot]]
    cache: dict
    cur: np.ndarray
    tick: int = 0
    snaps: int = 0


def _pack_rng_state(rng: np.random.Generator) -> Dict:
    """PCG64 state as msgpack-able strings (the 128-bit ints overflow)."""
    st = rng.bit_generator.state
    return {
        "bit_generator": st["bit_generator"],
        "state": str(st["state"]["state"]),
        "inc": str(st["state"]["inc"]),
        "has_uint32": int(st["has_uint32"]),
        "uinteger": int(st["uinteger"]),
    }


def _unpack_rng_state(d: Dict) -> np.random.Generator:
    rng = np.random.default_rng(0)
    rng.bit_generator.state = {
        "bit_generator": d["bit_generator"],
        "state": {"state": int(d["state"]), "inc": int(d["inc"])},
        "has_uint32": int(d["has_uint32"]),
        "uinteger": int(d["uinteger"]),
    }
    return rng


class ServeEngine:
    """Slot-managed continuous batching with a fault-tolerant control loop.

    Single-host driver; the jitted steps are SPMD so the same driver scales
    to a pod (per-slot prefill batches of 1 would be padded to the slot
    batch on real deployments).

    Scheduling loop per tick: (1) expire — queued or running requests past
    their ``deadline_s`` are terminated with state "deadline"; (2) admit —
    while a slot is free and the head of the arrival-ordered queue has
    arrived, prefill it exactly (batch 1, its own prompt length) and
    ``cache_insert`` it into the free slot; (3) decode — one packed
    ``decode_step`` over all slots; active slots sample/stream their token,
    slots whose budget hits zero are ``cache_reset`` and freed for the next
    admission; (4) snapshot — every ``snapshot_every`` ticks the whole
    engine state goes through ``CheckpointManager`` so :meth:`resume` can
    finish the run after a crash.

    Failure policy (the treat-failure-as-input contract):

    * A failed decode *tick* is retried in place with exponential backoff,
      up to ``max_retries`` attempts — the decode step is functional (the
      jitted fn does not donate its cache), so a retry recomputes the
      identical logits.
    * A :class:`~repro.runtime.faults.BackendFault` counts against the named
      backend; ``demote_after`` failures pin a process-wide dispatch
      demotion (``dispatch.pin_demotion``, e.g. fused -> mxu), rebuild the
      jitted decode fn, and keep serving — the demotion is visible in
      ``last_events`` as a ``demote`` event.
    * Non-finite logits fail the ONE request in that row, never the engine:
      the request is re-admitted from its prompt under the same
      ``(seed, rid)`` RNG key, so its replayed token sequence is bit-identical
      to an unfailed run.  ``max_retries`` re-admissions later it is
      terminally "failed".
    * A failed snapshot write is an event, not an outage: the engine keeps
      serving and tries again at the next boundary.

    The rids of the requests the last ``run``/``resume`` ended "failed" are
    kept on ``last_failed``; its event trace on ``last_events`` (kinds:
    admit/prefill/insert/decode_tick/finish/reset plus step_fault/
    retry_tick/backend_fault/demote/nan_logits/requeue/request_failed/
    prefill_fault/deadline_miss/snapshot/snapshot_failed/resume).
    """

    def __init__(
        self,
        cfg: ArchConfig,
        params,
        *,
        batch_slots: int = 4,
        max_len: int = 256,
        seed: int = 0,
        autotune_cache_path: Optional[str] = None,
        fault_plan=None,
        max_retries: int = 2,
        retry_backoff_s: float = 0.005,
        demote_after: int = 2,
        demote_to: str = dispatch.DEFAULT_BACKEND,
        snapshot_every: int = 0,
        snapshot_dir: Optional[str] = None,
    ):
        """``autotune_cache_path``: optional JSON file for the QMM autotune
        cache (core.dispatch).  Loaded at engine start (a warm serving
        process skips backend re-timing entirely) and written back after
        each ``run`` so the next process inherits fresh verdicts.  Only
        meaningful when the arch's quant config uses ``backend="auto"``.

        ``fault_plan``: a :class:`~repro.runtime.faults.FaultPlan` (or a
        JSON string/dict for one) of deterministic injected failures; None
        is the no-op default.  ``max_retries`` bounds both in-place tick
        retries and per-request re-admissions; ``retry_backoff_s`` is the
        base of the exponential backoff between tick retries.
        ``demote_after`` failures of one backend pin it to ``demote_to``.
        ``snapshot_every`` > 0 checkpoints engine state to ``snapshot_dir``
        at that tick cadence (required for :meth:`resume`)."""
        if cfg.encoder is not None and cfg.encoder.n_layers:
            raise NotImplementedError(
                "continuous batching drives decoder-only stacks; "
                "encoder-frontend archs go through make_prefill/make_decode_step"
            )
        from repro.runtime.faults import parse_fault_plan

        self.cfg = cfg
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.seed = seed
        self._next_rid = 0
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        self.mesh = mesh
        self.last_events: List[Dict] = []
        # rids of the requests the last run/resume terminally failed
        self.last_failed: List[int] = []
        self.autotune_cache_path = autotune_cache_path
        if autotune_cache_path and os.path.exists(autotune_cache_path):
            dispatch.get_cache().load(autotune_cache_path)
        self.fault_plan = parse_fault_plan(fault_plan)
        self.max_retries = max_retries
        self.retry_backoff_s = retry_backoff_s
        self.demote_after = demote_after
        self.demote_to = demote_to
        self.snapshot_every = snapshot_every
        self.snapshot_dir = snapshot_dir
        self._backend_failures: Dict[str, int] = {}
        self._demoted: Dict[str, str] = {}
        self._decode_fn = self._make_decode()

    def _make_decode(self):
        cfg_ = self.cfg

        def _decode(params, tokens, cache):
            return Z.decode_step(params, tokens, cfg_, cache)

        # fixed shapes: one compile per wrapper.  Rebuilt after a backend
        # demotion — the dispatch choice is baked in at trace time, so a
        # fresh jit wrapper is what makes the demotion take effect.
        return jax.jit(_decode)

    # -- internals ----------------------------------------------------------

    def _event(self, kind: str, t: float, **kw) -> None:
        self.last_events.append(dict(kind=kind, t=t, **kw))

    def _admit(self, req: Request, slot: int, cache: dict, now: float):
        """Exact-length batch-1 prefill + splice into ``slot``."""
        req.t_admitted = now
        self._event("admit", now, rid=req.rid, slot=slot, prompt_len=len(req.prompt))
        slot_cache = Z.init_slot_cache(self.max_len, self.cfg)
        tokens = jnp.asarray(np.asarray(req.prompt, np.int32)[None, :])
        logits, slot_cache = Z.prefill(self.params, tokens, self.cfg, slot_cache)
        self._event("prefill", time.perf_counter() - self._t0, rid=req.rid, slot=slot)
        cache = Z.cache_insert(cache, slot_cache, slot)
        self._event("insert", time.perf_counter() - self._t0, rid=req.rid, slot=slot)
        return np.asarray(logits)[0], cache

    def _emit(self, req: Request, token: int, now: float) -> None:
        req.output.append(token)
        req.token_times.append(now)
        if req.t_first_token is None:
            req.t_first_token = now
        if req.on_token is not None:
            req.on_token(token)

    def _clock(self) -> float:
        return time.perf_counter() - self._t0

    @staticmethod
    def _expired(req: Request, now: float) -> bool:
        return req.deadline_s is not None and now - req.arrival_s > req.deadline_s

    @staticmethod
    def _reset_progress(req: Request) -> None:
        """Rewind a request to its prompt (re-admission replays from here)."""
        req.output = []
        req.token_times = []
        req.t_admitted = req.t_first_token = req.t_finished = None

    def _requeue(self, st: _EngineState, req: Request, slot: Optional[int]) -> None:
        """Re-admit ``req`` after a failure — or terminally fail it.

        The slot (if held) is reset so co-batched requests are untouched.
        Replay is bit-identical to an unfailed run: progress rewinds to the
        prompt and the RNG is re-derived from the same ``(seed, rid)`` key
        at the next admission.
        """
        now = self._clock()
        if slot is not None and st.slots[slot] is not None:
            st.cache = Z.cache_reset(st.cache, slot, self.cfg, self.max_len)
            self._event("reset", self._clock(), rid=req.rid, slot=slot)
            st.slots[slot] = None
        req.retries += 1
        if req.retries > self.max_retries:
            req.state = STATE_FAILED
            req.t_finished = now
            self._event("request_failed", now, rid=req.rid, retries=req.retries)
            return
        self._reset_progress(req)
        st.queue.insert(0, req)
        self._event("requeue", now, rid=req.rid, retries=req.retries)

    def _finish(self, st: _EngineState, i: int, now: float, state: str = STATE_OK) -> None:
        slot = st.slots[i]
        slot.req.state = state
        slot.req.t_finished = now
        kind = "finish" if state == STATE_OK else "deadline_miss"
        self._event(kind, now, rid=slot.req.rid, slot=i)
        st.cache = Z.cache_reset(st.cache, i, self.cfg, self.max_len)
        self._event("reset", self._clock(), rid=slot.req.rid, slot=i)
        st.slots[i] = None

    def _note_backend_failure(self, backend: str, now: float) -> None:
        """Count a backend-attributed failure; demote the repeat offender."""
        n = self._backend_failures.get(backend, 0) + 1
        self._backend_failures[backend] = n
        self._event("backend_fault", now, backend=backend, count=n)
        if n < self.demote_after or backend in self._demoted:
            return
        target = self.demote_to if self.demote_to != backend else dispatch.DEFAULT_BACKEND
        dispatch.pin_demotion(backend, target)
        self._demoted[backend] = target
        # the demoted backend may be baked into the compiled decode step;
        # a fresh jit wrapper re-resolves dispatch at its next trace
        self._decode_fn = self._make_decode()
        self._event("demote", self._clock(), **{"from": backend, "to": target})

    # -- public API ---------------------------------------------------------

    def run(self, requests: List[Request]) -> List[Request]:
        """Serve a queue of requests; returns them in submission order.

        Requests with ``arrival_s > 0`` (open-loop traffic) are held back
        until their arrival time relative to the start of the call.  Every
        returned request carries a terminal ``state``: "ok" (full output),
        "deadline" (expired before completing), or "failed" (exceeded the
        retry budget after repeated faults).
        """
        for r in requests:
            prompt = np.asarray(r.prompt)
            if prompt.ndim != 1:
                raise ValueError(f"prompt must be rank-1, got shape {prompt.shape}")
            plen = len(prompt)
            if plen < 1 or r.max_new_tokens < 1:
                raise ValueError("request needs a non-empty prompt and >= 1 new token")
            if plen + r.max_new_tokens > self.max_len:
                raise ValueError(
                    f"prompt_len({plen}) + max_new_tokens({r.max_new_tokens}) "
                    f"exceeds engine max_len({self.max_len})"
                )
            if r.deadline_s is not None and r.deadline_s <= 0:
                raise ValueError(f"deadline_s must be positive, got {r.deadline_s}")
        for r in requests:
            r.rid = self._next_rid
            self._next_rid += 1
            r.state = STATE_PENDING
            r.retries = 0
            self._reset_progress(r)
        self.last_events = []
        self._t0 = time.perf_counter()

        state = _EngineState(
            requests=list(requests),
            queue=sorted(requests, key=lambda r: (r.arrival_s, r.rid)),
            slots=[None] * self.slots,
            cache=Z.init_cache(self.slots, self.max_len, self.cfg),
            cur=np.zeros((self.slots,), np.int32),
        )
        self._serve(state)
        return list(requests)

    def _serve(self, st: _EngineState) -> None:
        """Drive ``st`` to completion (shared by :meth:`run` and
        :meth:`resume`); every fault-policy decision lives here."""
        from repro.runtime.faults import BackendFault, FaultInjector

        inj = FaultInjector(self.fault_plan)
        clock = self._clock

        while st.queue or any(s is not None for s in st.slots):
            # ---- deadline sweep over the waiting queue -------------------
            now = clock()
            for req in [r for r in st.queue if self._expired(r, now)]:
                st.queue.remove(req)
                req.state = STATE_DEADLINE
                req.t_finished = now
                self._event("deadline_miss", now, rid=req.rid, slot=None)

            # ---- admission: fill free slots from arrived requests --------
            while st.queue and st.queue[0].arrival_s <= clock() and None in st.slots:
                req = st.queue.pop(0)
                i = st.slots.index(None)
                try:
                    inj.before_prefill(req.rid)
                    logits, st.cache = self._admit(req, i, st.cache, clock())
                except Exception as e:  # noqa: BLE001 — contained per-request
                    self._event(
                        "prefill_fault", clock(), rid=req.rid, error=repr(e)
                    )
                    self._requeue(st, req, slot=None)
                    continue
                if not np.all(np.isfinite(logits)):
                    self._event("nan_logits", clock(), rid=req.rid, slot=i)
                    self._requeue(st, req, slot=None)
                    continue
                slot = _Slot(req, req.max_new_tokens, _request_rng(self.seed, req.rid))
                tok = _sample(logits, req.temperature, slot.rng)
                self._emit(req, tok, clock())
                slot.remaining -= 1
                st.slots[i] = slot
                st.cur[i] = tok
                if slot.remaining == 0:
                    self._finish(st, i, clock())
            if all(s is None for s in st.slots):
                if st.queue:  # open-loop gap: idle until the next arrival
                    time.sleep(max(0.0, st.queue[0].arrival_s - clock()))
                continue

            # ---- one packed decode tick over every slot ------------------
            # Retried in place on failure: the jitted step does not donate
            # its cache, so a retry sees identical inputs -> identical
            # logits.  A BackendFault resets the attempt budget after a
            # demotion (the engine changed configuration; the next attempt
            # is a different program).
            logits = None
            attempt = 0
            while True:
                try:
                    inj.before_decode(st.tick, demoted=self._demoted)
                    out, new_cache = self._decode_fn(
                        self.params, jnp.asarray(st.cur), st.cache
                    )
                    logits = inj.corrupt_logits(st.tick, np.asarray(out))
                    break
                except BackendFault as e:
                    demoted_before = dict(self._demoted)
                    self._note_backend_failure(e.backend, clock())
                    if self._demoted != demoted_before:
                        attempt = 0
                        continue
                    attempt += 1
                except Exception as e:  # noqa: BLE001 — step faults retried
                    self._event(
                        "step_fault", clock(), tick=st.tick, error=repr(e)
                    )
                    attempt += 1
                if attempt > self.max_retries:
                    break
                backoff = self.retry_backoff_s * (2 ** (attempt - 1))
                self._event(
                    "retry_tick", clock(), tick=st.tick, attempt=attempt,
                    backoff_s=backoff,
                )
                if backoff > 0:
                    time.sleep(backoff)
            if logits is None:
                # tick retry budget exhausted: the batch is lost, the
                # requests are not — each replays from its prompt (or fails
                # terminally once ITS budget is gone).  The engine survives.
                for i in range(self.slots):
                    if st.slots[i] is not None:
                        self._requeue(st, st.slots[i].req, slot=i)
                continue
            st.cache = new_cache
            st.tick += 1
            now = clock()
            self._event(
                "decode_tick",
                now,
                rids=[s.req.rid if s else None for s in st.slots],
            )
            for i, slot in enumerate(st.slots):
                if slot is None:
                    continue
                row = logits[i]
                if not np.all(np.isfinite(row)):
                    # contain the numerics escape to this one request
                    self._event("nan_logits", now, rid=slot.req.rid, slot=i)
                    self._requeue(st, slot.req, slot=i)
                    continue
                tok = _sample(row, slot.req.temperature, slot.rng)
                self._emit(slot.req, tok, now)
                slot.remaining -= 1
                st.cur[i] = tok
                if slot.remaining == 0:
                    self._finish(st, i, clock())

            # ---- deadline sweep over running slots -----------------------
            now = clock()
            for i in range(self.slots):
                if st.slots[i] is not None and self._expired(st.slots[i].req, now):
                    self._finish(st, i, now, state=STATE_DEADLINE)

            # ---- periodic crash-recovery snapshot ------------------------
            if self.snapshot_every and st.tick % self.snapshot_every == 0:
                try:
                    inj.on_snapshot(st.snaps)
                    self._snapshot(st)
                    self._event("snapshot", clock(), tick=st.tick, ordinal=st.snaps)
                except Exception as e:  # noqa: BLE001 — snapshots are best-effort
                    self._event(
                        "snapshot_failed", clock(), tick=st.tick,
                        ordinal=st.snaps, error=repr(e),
                    )
                st.snaps += 1

        self.last_failed = [r.rid for r in st.requests if r.state == STATE_FAILED]
        if self.autotune_cache_path:
            dispatch.get_cache().save(self.autotune_cache_path)

    # -- crash-recoverable engine state -------------------------------------

    def _snapshot_manager(self):
        from repro.checkpoint import CheckpointManager

        if not self.snapshot_dir:
            raise ValueError("snapshot_dir is not configured on this engine")
        return CheckpointManager(self.snapshot_dir, keep=2)

    def _snapshot(self, st: _EngineState) -> None:
        """Persist the full engine state through ``CheckpointManager``.

        Arrays (the packed decode cache + per-slot next-token inputs) go in
        the checkpoint tree; the host-side scheduler state (queue order,
        per-slot budgets, per-request progress and PCG64 sampler states)
        rides in the manifest extras.  Committed atomically — a crash
        mid-write leaves the previous snapshot restorable.
        """
        mgr = self._snapshot_manager()
        tree = {"cache": st.cache, "cur": jnp.asarray(st.cur)}
        extras = {
            "serve": {
                "arch": self.cfg.name,
                "seed": int(self.seed),
                "batch_slots": int(self.slots),
                "max_len": int(self.max_len),
                "tick": int(st.tick),
                "snaps": int(st.snaps),
                "next_rid": int(self._next_rid),
                "elapsed_s": float(self._clock()),
                "queue_rids": [int(r.rid) for r in st.queue],
                "slots": [
                    None
                    if s is None
                    else {
                        "rid": int(s.req.rid),
                        "remaining": int(s.remaining),
                        "rng": _pack_rng_state(s.rng),
                    }
                    for s in st.slots
                ],
                "requests": [
                    {
                        "rid": int(r.rid),
                        "prompt": [int(t) for t in np.asarray(r.prompt)],
                        "max_new_tokens": int(r.max_new_tokens),
                        "temperature": float(r.temperature),
                        "arrival_s": float(r.arrival_s),
                        "deadline_s": None if r.deadline_s is None else float(r.deadline_s),
                        "state": r.state,
                        "retries": int(r.retries),
                        "output": [int(t) for t in (r.output or [])],
                        "token_times": [float(t) for t in (r.token_times or [])],
                    }
                    for r in st.requests
                ],
            }
        }
        mgr.save(st.tick, tree, extras)

    def resume(self) -> List[Request]:
        """Finish the run recorded in ``snapshot_dir``'s latest snapshot.

        Reconstructs the admission queue, per-slot caches/cursors/budgets
        and sampler states, then drives the normal serve loop to completion
        — the surviving requests' outputs are token-for-token identical to
        an uninterrupted run (the decode cache rows, next-token inputs, and
        PCG64 states are restored exactly).  Returns every request of the
        original run, in rid order, including those that had already
        finished before the snapshot.
        """
        from repro.checkpoint import manager as CM

        mgr = self._snapshot_manager()
        step = mgr.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed snapshot in {self.snapshot_dir}")
        # geometry check against the manifest BEFORE materializing arrays:
        # a mismatched engine gets the actionable error, not a shape trace
        manifest = CM._read_manifest(
            os.path.join(self.snapshot_dir, f"step_{step:09d}")
        )
        s = manifest["extras"]["serve"]
        if s["arch"] != self.cfg.name or s["batch_slots"] != self.slots or s[
            "max_len"
        ] != self.max_len:
            raise ValueError(
                f"snapshot geometry mismatch: snapshot is {s['arch']} "
                f"slots={s['batch_slots']} max_len={s['max_len']}, engine is "
                f"{self.cfg.name} slots={self.slots} max_len={self.max_len}"
            )
        like = {
            "cache": Z.init_cache(self.slots, self.max_len, self.cfg),
            "cur": jnp.zeros((self.slots,), jnp.int32),
        }
        step, tree, extras = mgr.restore(step, like=like)
        s = extras["serve"]

        by_rid: Dict[int, Request] = {}
        for rec in s["requests"]:
            req = Request(
                prompt=np.asarray(rec["prompt"], np.int32),
                max_new_tokens=rec["max_new_tokens"],
                temperature=rec["temperature"],
                arrival_s=rec["arrival_s"],
                deadline_s=rec["deadline_s"],
            )
            req.rid = rec["rid"]
            req.state = rec["state"]
            req.retries = rec["retries"]
            req.output = list(rec["output"])
            req.token_times = list(rec["token_times"])
            if req.token_times:
                req.t_first_token = req.token_times[0]
            by_rid[req.rid] = req

        slots: List[Optional[_Slot]] = []
        for rec in s["slots"]:
            if rec is None:
                slots.append(None)
            else:
                slots.append(
                    _Slot(
                        by_rid[rec["rid"]],
                        rec["remaining"],
                        _unpack_rng_state(rec["rng"]),
                    )
                )
        state = _EngineState(
            requests=[by_rid[r] for r in sorted(by_rid)],
            queue=[by_rid[r] for r in s["queue_rids"]],
            slots=slots,
            cache=tree["cache"],
            cur=np.asarray(tree["cur"], np.int32).copy(),
            tick=s["tick"],
            snaps=s["snaps"],
        )
        self._next_rid = max(self._next_rid, s["next_rid"])
        self.last_events = []
        # continue the run's clock where it stopped, so arrival offsets and
        # deadlines keep their meaning across the restart
        self._t0 = time.perf_counter() - s["elapsed_s"]
        self._event("resume", self._clock(), tick=state.tick, step=step)
        self._serve(state)
        return state.requests


def serve_sequential(
    cfg: ArchConfig,
    params,
    requests: List[Request],
    *,
    max_len: int = 256,
    seed: int = 0,
) -> List[Request]:
    """Naive one-request-at-a-time oracle: batch 1, no slot machinery, no
    co-batching — the reference the differential tests hold ``ServeEngine``
    to, token for token.  Shares ``_sample`` and the per-request RNG keying
    with the engine so sampling (not just greedy argmax) is comparable.
    Fault-free and deadline-blind by construction — it defines the token
    sequences the fault-tolerant engine must reproduce."""
    # one compile for every token (an eager call retraces the layer scan)
    decode = jax.jit(lambda p, t, c: Z.decode_step(p, t, cfg, c))
    for rid, r in enumerate(requests):
        if len(r.prompt) + r.max_new_tokens > max_len:
            raise ValueError("request exceeds max_len")
        r.rid = rid
        rng = _request_rng(seed, rid)
        cache = Z.init_cache(1, max_len, cfg)
        tokens = jnp.asarray(np.asarray(r.prompt, np.int32)[None, :])
        logits, cache = Z.prefill(params, tokens, cfg, cache)
        tok = _sample(np.asarray(logits)[0], r.temperature, rng)
        r.output = [tok]
        while len(r.output) < r.max_new_tokens:
            logits, cache = decode(params, jnp.asarray([tok], np.int32), cache)
            tok = _sample(np.asarray(logits)[0], r.temperature, rng)
            r.output.append(tok)
        r.state = STATE_OK
    return list(requests)

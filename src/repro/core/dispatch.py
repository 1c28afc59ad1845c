"""Autotuned QMM backend dispatch — the measured half of the §III-C engine.

BETA's QMM engine is *configurable*: per precision mode it picks the
datapath (packed-parallel vs bit-serial) that the operands deserve.  The
software analogue is shape-dependent as well — which integer backend
(``mxu`` / ``popcount`` / ``pallas``) wins depends on ``(M, K, N)``, the
operand precisions, and what this host can actually run — so the right
dispatch policy is *measured*, not hardcoded.

This module provides :class:`AutotuneCache`:

* keyed on ``(M, K, N, act_bits, weight_bits, candidate set, phase tag)``;
  ``M`` is bucketed to the next power of two so serving waves with ragged
  prompt lengths share entries;
* on first miss it times every candidate backend on synthetic operands of
  the key's exact shape/precision (compile warmup, then ``reps`` timed
  calls under ``jax.block_until_ready``) and records the winner;
* thereafter the winner is served from the cache — including from inside
  ``jax.jit`` traces, where shapes are static and the eager timing run
  happens once at trace time;
* persists to JSON (:meth:`AutotuneCache.save` / :meth:`AutotuneCache.load`)
  so serving processes skip the warmup entirely.

``qmm(backend="auto")`` delegates here; prefill and decode run under
distinct :func:`tuning_phase` tags because their ``M`` differs by orders of
magnitude and the winner need not be the same backend.

Environment knobs:

* ``REPRO_QMM_AUTOTUNE=0``      — disable timing; "auto" resolves to "mxu".
* ``REPRO_QMM_AUTOTUNE_CACHE``  — JSON path auto-loaded into the default
  cache on first use (written back by ``ServeEngine`` when configured).

The cache-file format is documented in docs/qmm-engine.md.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import json
import os
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

__all__ = [
    "BACKENDS",  # deprecated dynamic view; use backend_registry.backend_names()
    "DEFAULT_BACKEND",
    "DEFAULT_SCORES_BACKEND",
    "AutotuneError",
    "TuneKey",
    "TuneRecord",
    "AutotuneCache",
    "candidate_backends",
    "make_problem",
    "make_scores_problem",
    "choose_backend",
    "choose_scores_backend",
    "get_cache",
    "reset_cache",
    "tuning_phase",
    "current_phase",
    "pin_demotion",
    "clear_demotions",
    "demotions",
    "resolve_backend",
]

#: Fallback when autotuning is disabled or a cache entry is missing.
DEFAULT_BACKEND = "mxu"

#: Scores-family fallback: the packed AND-popcount core (always available,
#: bit-exact against every other scores core).
DEFAULT_SCORES_BACKEND = "binary"


def __getattr__(name: str) -> Tuple[str, ...]:
    # Deprecated: ``dispatch.BACKENDS`` predates the backend registry (and
    # backend *families*).  Every legacy call site reads it as "names valid
    # for ``QE.qmm``", so it is served dynamically (PEP 562) as the qmm
    # family; new code should call
    # ``repro.core.backend_registry.backend_names(family=...)`` directly.
    if name == "BACKENDS":
        from repro.core import backend_registry

        return backend_registry.backend_names(family="qmm")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


_CACHE_ENV = "REPRO_QMM_AUTOTUNE_CACHE"
_DISABLE_ENV = "REPRO_QMM_AUTOTUNE"

_PHASE: contextvars.ContextVar = contextvars.ContextVar("qmm_tuning_phase", default="")


def current_phase() -> str:
    """The active tuning tag ("" outside any :func:`tuning_phase` block)."""
    return _PHASE.get()


@contextlib.contextmanager
def tuning_phase(tag: str):
    """Scope a tuning tag (e.g. "prefill" / "decode") over qmm(auto) calls.

    Tags split the cache key: a decode-shaped QMM (M = batch) and a
    prefill-shaped one (M = batch * prompt) must never share a timing
    verdict even if bucketing would otherwise merge them.
    """
    token = _PHASE.set(tag)
    try:
        yield
    finally:
        _PHASE.reset(token)


# ---------------------------------------------------------------------------
# backend demotion (the serving degradation policy's dispatch hook)
# ---------------------------------------------------------------------------

# Process-wide demotion table: {failing backend -> known-good fallback}.
# Pinned by the serving engine when a backend fails repeatedly (e.g. the
# fused Pallas kernel refusing to lower off-TPU); consulted by ``qmm`` AFTER
# name resolution, so it overrides explicit config names, per-layer
# overrides, and autotune verdicts alike — the autotune cache itself is left
# untouched (a demotion is an availability fact, not a timing verdict).
_DEMOTIONS: Dict[str, str] = {}


def pin_demotion(src: str, dst: str) -> None:
    """Route every dispatch of ``src`` to ``dst`` for this process.

    Both names must be registered; pinning a cycle (``dst`` already resolving
    back to ``src``) is rejected — a demotion chain must terminate.
    """
    from repro.core import backend_registry

    known = set(backend_registry.backend_names())
    for name in (src, dst):
        if name not in known:
            raise ValueError(
                f"cannot pin demotion {src!r} -> {dst!r}: unknown backend {name!r}"
            )
    if src == dst or resolve_backend(dst) == src:
        raise ValueError(f"demotion {src!r} -> {dst!r} would form a cycle")
    _DEMOTIONS[src] = dst


def clear_demotions() -> None:
    """Drop every pinned demotion (tests; operator-driven re-promotion)."""
    _DEMOTIONS.clear()


def demotions() -> Dict[str, str]:
    """A copy of the active demotion table."""
    return dict(_DEMOTIONS)


def resolve_backend(name: str) -> str:
    """Follow the demotion chain from ``name`` to its serving backend."""
    seen = set()
    while name in _DEMOTIONS and name not in seen:
        seen.add(name)
        name = _DEMOTIONS[name]
    return name


def _bucket_m(m: int) -> int:
    """Round M up to a power of two (>= 8) so ragged serving waves share
    cache entries instead of re-tuning per prompt length."""
    b = 8
    while b < m:
        b <<= 1
    return b


def candidate_backends(
    m: int,
    k: int,
    n: int,
    act_bits: int,
    weight_bits: int,
    *,
    rank2: bool = True,
    family: str = "qmm",
) -> Tuple[str, ...]:
    """Backends eligible for this problem on this host (the "availability"
    component of the cache key) — enumerated from the backend registry, so
    a newly registered backend becomes an autotune candidate with zero
    dispatcher edits."""
    from repro.core import backend_registry  # lazy: keeps core import-light

    return backend_registry.candidate_names(
        m, k, n, act_bits, weight_bits, rank2=rank2, family=family
    )


@dataclasses.dataclass(frozen=True)
class TuneKey:
    """One autotune cell. ``m`` is bucketed; ``candidates`` captures backend
    availability so a cache file moved across hosts never serves a backend
    the new host would not have timed."""

    m: int
    k: int
    n: int
    act_bits: int
    weight_bits: int
    candidates: Tuple[str, ...]
    tag: str = ""
    #: Operator family: "qmm" (rank-2 matmul) or "scores" (rank-4 attention
    #: scores, m = B*H*S, k = dh, n = T).  Families never share entries.
    family: str = "qmm"


@dataclasses.dataclass
class TuneRecord:
    backend: str
    timings_us: Dict[str, float]
    timed: bool  # False when forced, single-candidate, or autotune disabled
    # Every timing probe raised: the record is an in-process fallback only —
    # never persisted, so the next process re-times instead of inheriting a
    # transient failure as a permanent verdict.
    failed: bool = False


def make_problem(key: TuneKey):
    """Synthetic operands matching the key, in the layout serving uses.

    weight_bits == 1 (act x weight): sign-binarized weights, BIT-PACKED with
    a precomputed colsum — exactly what ``pack_linear_for_serving`` feeds
    the engine; timing unpacked weights would measure a problem production
    never runs.  Multi-bit right operands are act x act and stay unpacked,
    as the attention path quantizes them on the fly."""
    from repro.core import flow_abstraction as FA
    from repro.core import quantization as Q

    rng = np.random.default_rng(
        (key.m * 1000003 + key.k * 10007 + key.n * 101 + key.act_bits * 7 + key.weight_bits)
        % (2**32)
    )
    import jax.numpy as jnp

    x = jnp.asarray(rng.standard_normal((key.m, key.k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((key.k, key.n)).astype(np.float32))
    xq = Q.quantize_activation(x, key.act_bits)
    wq = Q.quantize_weight(w, key.weight_bits)
    colsum = None
    if key.weight_bits == 1:
        colsum = FA.weight_corrections(wq)
        wq = wq.pack(axis=0)
    return xq, wq, colsum


def make_scores_problem(key: TuneKey):
    """Synthetic packed Q/K bit-planes for one scores-family key.

    The key folds ``B*H*S`` into ``m``, ``dh`` into ``k`` and ``T`` into
    ``n``; timing collapses the batch/head dims to 1 and puts the whole
    ``m`` on the S axis — the popcount/MXU cores are lane-parallel over
    rows, so the timing is representative of any (B, H, S) split with the
    same product."""
    from repro.core import packing

    import jax.numpy as jnp

    rng = np.random.default_rng(
        (key.m * 1000003 + key.k * 10007 + key.n * 101 + 5) % (2**32)
    )
    q_bits = rng.integers(0, 2, size=(1, 1, key.m, key.k), dtype=np.uint8)
    k_bits = rng.integers(0, 2, size=(1, 1, key.n, key.k), dtype=np.uint8)
    q_planes = packing.pack_bits(jnp.asarray(q_bits), 1, axis=-1)
    k_planes = packing.pack_bits(jnp.asarray(k_bits), 1, axis=-1)
    return q_planes, k_planes


class AutotuneError(RuntimeError):
    """An autotune candidate raised on the chip."""


def _candidate_errors_fatal() -> bool:
    """On the chip every offered backend must run: a candidate that raises
    is a broken kernel, and skipping it would quietly serve another
    backend.  Off the chip (Pallas in interpret mode) a failing candidate
    just loses."""
    return jax.default_backend() == "tpu"


def _candidate_failed(backend: str, key: TuneKey, err: Exception) -> None:
    if _candidate_errors_fatal():
        raise AutotuneError(
            f"{key.family} backend {backend!r} raised while autotuning "
            f"M={key.m} K={key.k} N={key.n} "
            f"act_bits={key.act_bits} weight_bits={key.weight_bits}: {err!r}"
        ) from err


def _wallclock_timer(fn: Callable[[], object], *, warmup: int = 1, reps: int = 3) -> float:
    """Best-of-``reps`` wall-clock of ``fn`` in seconds, after compile warmup.

    Min, not mean: on a contended host the minimum is the robust estimator
    of a kernel's intrinsic cost (contention only ever adds time)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


class AutotuneCache:
    """Shape/precision-keyed backend choice, measured once per key.

    ``timer`` is injectable (tests pass a deterministic fake); the default
    times real jitted calls.  ``timing_runs`` counts individual backend
    probes — a loaded cache must not grow it.
    """

    def __init__(
        self,
        *,
        timer: Optional[Callable[[Callable[[], object]], float]] = None,
        warmup: int = 1,
        reps: int = 3,
    ):
        self._entries: Dict[TuneKey, TuneRecord] = {}
        self._timer = timer or functools.partial(
            _wallclock_timer, warmup=warmup, reps=reps
        )
        self.timing_runs = 0

    # -- lookup --------------------------------------------------------------

    def choose(
        self,
        m: int,
        k: int,
        n: int,
        act_bits: int,
        weight_bits: int,
        *,
        tag: Optional[str] = None,
        rank2: bool = True,
        family: str = "qmm",
    ) -> str:
        """The winning backend for this problem (timing on first miss)."""
        mb = _bucket_m(int(m))
        key = TuneKey(
            mb,
            int(k),
            int(n),
            int(act_bits),
            int(weight_bits),
            candidate_backends(
                mb, k, n, act_bits, weight_bits, rank2=rank2, family=family
            ),
            current_phase() if tag is None else tag,
            family,
        )
        rec = self._entries.get(key)
        if rec is None:
            rec = self._tune(key)
            self._entries[key] = rec
        return rec.backend

    def record(self, key: TuneKey) -> Optional[TuneRecord]:
        return self._entries.get(key)

    @property
    def entries(self) -> Dict[TuneKey, TuneRecord]:
        return dict(self._entries)

    def __len__(self) -> int:
        return len(self._entries)

    # -- timing --------------------------------------------------------------

    def _tune(self, key: TuneKey) -> TuneRecord:
        if len(key.candidates) == 1:
            return TuneRecord(key.candidates[0], {}, False)
        if key.family == "scores":
            return self._tune_scores(key)
        from repro.core import qmm as QE

        xq, wq, colsum = make_problem(key)
        timings: Dict[str, float] = {}
        for b in key.candidates:
            call = jax.jit(
                functools.partial(QE.qmm, backend=b, w_colsum=colsum)
            )
            try:
                timings[b] = self._timer(lambda c=call: c(xq, wq))
            except Exception as e:  # noqa: BLE001 — fatal on the chip only
                _candidate_failed(b, key, e)
                continue
            self.timing_runs += 1
        if not timings:
            return TuneRecord(DEFAULT_BACKEND, {}, False, failed=True)
        best = min(timings, key=timings.get)
        return TuneRecord(best, {b: t * 1e6 for b, t in timings.items()}, True)

    def _tune_scores(self, key: TuneKey) -> TuneRecord:
        """Scores-family timing: each candidate's ``run_scores`` over the
        same packed planes.  All scores cores are bit-exact against
        ``ref.binary_attn_scores_ref``, so the winner is purely a speed
        verdict — numerics (and batch invariance) don't depend on it."""
        from repro.core import backend_registry

        q_planes, k_planes = make_scores_problem(key)
        timings: Dict[str, float] = {}
        for b in key.candidates:
            spec = backend_registry.get_backend(b)
            call = jax.jit(functools.partial(spec.run_scores, dh=key.k))
            try:
                timings[b] = self._timer(lambda c=call: c(q_planes, k_planes))
            except Exception as e:  # noqa: BLE001 — fatal on the chip only
                _candidate_failed(b, key, e)
                continue
            self.timing_runs += 1
        if not timings:
            return TuneRecord(DEFAULT_SCORES_BACKEND, {}, False, failed=True)
        best = min(timings, key=timings.get)
        return TuneRecord(best, {b: t * 1e6 for b, t in timings.items()}, True)

    # -- persistence ---------------------------------------------------------

    def to_json(self) -> dict:
        return {
            "version": 1,
            "entries": [
                {
                    "m": k.m,
                    "k": k.k,
                    "n": k.n,
                    "act_bits": k.act_bits,
                    "weight_bits": k.weight_bits,
                    "candidates": list(k.candidates),
                    "tag": k.tag,
                    "family": k.family,
                    "backend": r.backend,
                    "timings_us": r.timings_us,
                    "timed": r.timed,
                }
                for k, r in self._entries.items()
                if not r.failed
            ],
        }

    def save(self, path: str) -> None:
        """Atomic JSON dump (write + rename) of every tuned entry."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
        os.replace(tmp, path)

    def load(self, path: str) -> int:
        """Merge entries from ``path``; returns how many were loaded.

        Entries naming a backend this build does not know are skipped (a
        cache file is advice, never an obligation)."""
        with open(path) as f:
            blob = json.load(f)
        if blob.get("version") != 1:
            raise ValueError(f"unsupported autotune cache version in {path}")
        from repro.core import backend_registry

        known = set(backend_registry.backend_names())
        loaded = 0
        for e in blob.get("entries", ()):
            if e["backend"] not in known:
                continue
            key = TuneKey(
                int(e["m"]),
                int(e["k"]),
                int(e["n"]),
                int(e["act_bits"]),
                int(e["weight_bits"]),
                tuple(e["candidates"]),
                e.get("tag", ""),
                e.get("family", "qmm"),
            )
            self._entries[key] = TuneRecord(
                e["backend"], dict(e.get("timings_us", {})), bool(e.get("timed"))
            )
            loaded += 1
        return loaded


# ---------------------------------------------------------------------------
# module-level default cache (what qmm(backend="auto") consults)
# ---------------------------------------------------------------------------

_default_cache: Optional[AutotuneCache] = None


def get_cache() -> AutotuneCache:
    """The process-wide cache, auto-loading ``$REPRO_QMM_AUTOTUNE_CACHE``."""
    global _default_cache
    if _default_cache is None:
        _default_cache = AutotuneCache()
        path = os.environ.get(_CACHE_ENV)
        if path and os.path.exists(path):
            _default_cache.load(path)
    return _default_cache


def reset_cache(cache: Optional[AutotuneCache] = None) -> AutotuneCache:
    """Swap the default cache (tests; serving with a preloaded cache)."""
    global _default_cache
    _default_cache = cache if cache is not None else AutotuneCache()
    return _default_cache


def autotune_enabled() -> bool:
    return os.environ.get(_DISABLE_ENV, "1").lower() not in ("0", "off", "false")


def choose_backend(
    m: int,
    k: int,
    n: int,
    act_bits: int,
    weight_bits: int,
    *,
    tag: Optional[str] = None,
    rank2: bool = True,
    cache: Optional[AutotuneCache] = None,
) -> str:
    """Resolve "auto" for one QMM problem (the core.qmm entry point).

    The returned name has demotions applied: a demoted backend's cached
    timing verdict survives (re-promotion needs no re-timing) but is never
    served while the pin is active.
    """
    if not autotune_enabled():
        return resolve_backend(DEFAULT_BACKEND)
    return resolve_backend(
        (cache or get_cache()).choose(
            m, k, n, act_bits, weight_bits, tag=tag, rank2=rank2
        )
    )


def choose_scores_backend(
    b: int,
    h: int,
    s: int,
    t: int,
    dh: int,
    *,
    tag: Optional[str] = None,
    cache: Optional[AutotuneCache] = None,
) -> str:
    """Resolve the scores-family core for one attention-scores problem.

    Keys on ``m = B*H*S`` (bucketed), ``k = dh``, ``n = T`` under the
    "scores" family, W1A1 by construction.  Demotions apply to the returned
    name exactly like qmm dispatch — ``pin_demotion("binary", "mxu")``
    reroutes the popcount core to the MXU core without changing numerics
    (every scores core is bit-exact against the ref oracle).
    """
    if not autotune_enabled():
        return resolve_backend(DEFAULT_SCORES_BACKEND)
    return resolve_backend(
        (cache or get_cache()).choose(
            int(b) * int(h) * int(s), dh, t, 1, 1, tag=tag, family="scores"
        )
    )

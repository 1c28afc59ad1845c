"""The one traffic generator: a mix file of parameters in, a request plan out.

A mix (``bench/traffic/<name>.json``) holds:

* ``arrival``: ``{"kind": "poisson", "rate_rps": r}``, open loop, or
  ``{"kind": "backlog", "n_requests": n}``, every request due at 0;
* ``ramp_s`` (poisson): how long the traffic runs before the window opens;
* ``prompt_pool``: the prompt lengths, fixed in the file;
* ``output``: lognormal ``median``, ``sigma`` and ``clip`` of output lengths;
* ``temperature`` and ``greedy_share``: the share of requests decoded greedily;
* ``slots``, ``grace_s``, ``check_tokens``: engine slots, how long a request
  due in the window is waited for after it closes, and how many served
  tokens the correctness check compares;
* ``plan_seed``: seeds the SET of sizes and gaps.

The arrival times and the set of prompt lengths, output lengths and greedy
flags depend only on the mix and the window length, so every ``--seed``
gets the same work at the same moments.  The seed picks which request gets
which sizes, and the token ids.

Copied from ``repro.runtime.traffic.generate_requests`` (seeded open-loop
Poisson) and extended with the pool, lognormal outputs and the backlog.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Planned:
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int
    temperature: float
    arrival_s: float


def horizon_s(mix: Dict, seconds: float) -> float:
    """Poisson arrivals cover the ramp and the window, and stop at its close."""
    return float(mix["ramp_s"]) + seconds


def _lognormal(rng, spec: Dict, n: int) -> np.ndarray:
    lo, hi = spec["clip"]
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), lo, hi).astype(int)


def plan(mix: Dict, seed: int, seconds: float, vocab_size: int) -> List[Planned]:
    """The requests of one run, in arrival order."""
    plan_rng = np.random.default_rng(mix["plan_seed"])
    arrival = mix["arrival"]
    if arrival["kind"] == "poisson":
        gaps = [plan_rng.exponential(1.0 / arrival["rate_rps"])]
        while sum(gaps) < horizon_s(mix, seconds):
            gaps.append(plan_rng.exponential(1.0 / arrival["rate_rps"]))
        gaps.pop()  # the last arrival would fall after the close
        n = len(gaps)
    elif arrival["kind"] == "backlog":
        n = int(arrival["n_requests"])
        gaps = [0.0] * n
    else:
        raise ValueError(f"unknown arrival kind {arrival['kind']!r}")
    pool = list(mix["prompt_pool"])
    prompt_lens = np.array([pool[i % len(pool)] for i in range(n)])
    out_lens = _lognormal(plan_rng, mix["output"], n)
    n_greedy = int(round(mix.get("greedy_share", 1.0) * n))
    greedy = np.arange(n) < n_greedy

    rng = np.random.default_rng(seed)
    prompt_lens = rng.permutation(prompt_lens)
    out_lens = rng.permutation(out_lens)
    greedy = rng.permutation(greedy)
    arrivals = np.cumsum(gaps) if arrival["kind"] == "poisson" else np.zeros(n)
    return [
        Planned(
            prompt=rng.integers(0, vocab_size, size=int(prompt_lens[i])).astype(np.int32),
            max_new_tokens=int(out_lens[i]),
            temperature=0.0 if greedy[i] else float(mix["temperature"]),
            arrival_s=float(arrivals[i]),
        )
        for i in range(n)
    ]

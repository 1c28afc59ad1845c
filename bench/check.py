"""Decide ``correct``: what the timed path served, against the plain reference.

During the window a :class:`LogitTap` records, at the engine's boundary (the
engine's sampling function, ``repro.runtime.serve_loop._sample``), the
logits from which each token was chosen: their values at a fixed, seeded set
of vocabulary ids.  After the window has closed and the program's state is
freed, a sample of the requests served in the window, drawn from the seed
with the longest among them (and the longest greedy one, where the mix has
greedy requests), is run through the reference
(``bench/reference/<family>.py``) over each prompt and its served tokens.
Two numbers are read:

* ``max_logit_err``: over every served position, greedy or sampled, the
  largest difference between a program logit and the reference's at a
  recorded id, over the standard deviation of the reference's logits at
  that position;
* ``max_logit_gap``: over the greedy requests' served positions, the widest
  gap by which a served token's logit lies below the reference's best (a
  sampled token may lie below it by design).

Each number the configuration's ``limits`` names is compared with that
limit, by :func:`judge`.  The control (the reference at fewer activation
bits in the program's place) and a served token altered to the least likely
one go through the same :func:`judge`, and have to come out not correct
(``bench/tools/readings.py``); ``PERF.md`` gives the readings and limits.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

N_IDS = 512  # vocabulary ids whose logits are recorded per served token


class LogitTap:
    """Records each sampled row's logits at ``ids``, for the request that
    the engine emits the token to next (``claim`` from its ``on_token``)."""

    def __init__(self, vocab_size: int, seed: int):
        rng = np.random.default_rng([seed, 3])
        self.ids = np.sort(rng.choice(vocab_size, min(N_IDS, vocab_size), replace=False))
        self.rows: Dict[int, List[np.ndarray]] = {}
        self._pending: Optional[np.ndarray] = None
        self._module = self._orig = None

    def __enter__(self):
        from repro.runtime import serve_loop

        self._module, self._orig = serve_loop, serve_loop._sample
        serve_loop._sample = self._sample
        return self

    def __exit__(self, *exc):
        self._module._sample = self._orig

    def _sample(self, logits, temperature, rng):
        self._pending = np.asarray(logits[self.ids], np.float32)
        return self._orig(logits, temperature, rng)

    def claim(self, req) -> None:
        if len(req.output) == 1:  # a first token (again, after a replay)
            self.rows[id(req)] = []
        self.rows[id(req)].append(self._pending)


def _size(r) -> int:
    return len(r.prompt) + len(r.output)


def sample(requests, window, want_tokens: int, seed: int) -> List:
    """Requests with a token in the window: the longest, the longest greedy
    one, then others in a seeded order, until ``want_tokens`` served tokens
    are in."""
    served = [r for r in requests if r.output and any(window.in_window(t) for t in r.token_times)]
    if not served:
        return []
    picked = [max(served, key=_size)]
    greedy = [r for r in served if r.temperature <= 0]
    if greedy and picked[0].temperature > 0:
        picked.append(max(greedy, key=_size))
    rest = [r for r in served if all(r is not p for p in picked)]
    order = np.random.default_rng([seed, 1]).permutation(len(rest))
    n = sum(len(r.output) for r in picked)
    for i in order:
        if n >= want_tokens:
            break
        picked.append(rest[i])
        n += len(rest[i].output)
    return picked


def readings(ref_rows: np.ndarray, served: List[int], prog_rows: np.ndarray, ids) -> Tuple[float, float]:
    """(max_logit_err, max_logit_gap) of one request's served positions."""
    scale = ref_rows.std(axis=-1, keepdims=True)
    err = np.max(np.abs(prog_rows - ref_rows[:, ids]) / scale)
    gap = np.max(ref_rows.max(axis=-1) - ref_rows[np.arange(len(served)), served])
    return float(err), float(gap)


def judge(values: Dict[str, float], limits: Dict) -> Dict:
    """Each number that ``limits`` names beside its limit, and the verdict."""
    checks = {k: {"value": v, "limit": float(limits[k])} for k, v in values.items() if k in limits}
    return {"correct": all(c["value"] <= c["limit"] for c in checks.values()), "checks": checks}


def compare(reference, config: Dict, seed: int, picked, tap: LogitTap, control_bits=None) -> Dict:
    """The verdict on the program, each number compared beside its limit; with
    ``control_bits`` also the verdicts on the control and on an altered token."""
    limits = config["limits"]
    seqs = [(np.asarray(r.prompt, np.int32), list(r.output)) for r in picked]
    if not seqs:
        return {"correct": False, "checks": {"tokens_compared": {"value": 0, "limit": 1}}}
    bits = config["act_bits"]
    widths = [bits] + ([control_bits] if control_bits else [])
    logits = reference.served_logits(config, seed, seqs, widths)
    greedy = [r.temperature <= 0 for r in picked]
    prog = [readings(ref, t, np.stack(tap.rows[id(r)]), tap.ids) for ref, (_, t), r in zip(logits[bits], seqs, picked)]
    values = {"max_logit_err": max(e for e, _ in prog)}
    if any(greedy):
        values["max_logit_gap"] = max(g for (_, g), gr in zip(prog, greedy) if gr)
    out = judge(values, limits)
    out["checks"]["tokens_compared"] = {"value": sum(len(t) for _, t in seqs), "limit": 1}
    out["readings"] = values
    if control_bits:
        # at each served position, the token that the lower precision puts first
        ctl = [readings(ref, list(low.argmax(-1)), low[:, tap.ids], tap.ids)
               for ref, low in zip(logits[bits], logits[control_bits])]
        out["control"] = judge({"max_logit_err": max(e for e, _ in ctl), "max_logit_gap": max(g for _, g in ctl)}, limits)
        # the fault "a token altered where it is produced", to the least likely
        # one, at the greedy position where that lies farthest below the best
        if any(greedy):
            worst = max(float(np.max(ref.max(-1) - ref.min(-1))) for ref, gr in zip(logits[bits], greedy) if gr)
            out["altered_token"] = judge(dict(values, max_logit_gap=worst), limits)
    return out

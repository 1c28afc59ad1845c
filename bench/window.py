"""The measured window, opened and closed from the engine's token stream.

The engine serves the whole plan in one ``ServeEngine.run`` call.  Each
request's ``on_token`` hook sees every token as it is emitted, with the
engine's own stamp of it (``Request.token_times``: seconds from the start of
``run``, the clock of ``arrival_s``, ``t_admitted`` and the decode ticks).
That is where the window opens and closes:

* a Poisson mix's window is ``[ramp_s, ramp_s + seconds)``, and its plan
  has no arrival after it; a backlog's opens once ``slots`` requests have
  had a first token, and closes ``seconds`` later;
* at the first token after the close, every request with a first token is
  given a deadline that has passed, so the engine's next sweep ends it.  A
  Poisson request still queued is served until its first token, then ended
  the same way (a first token later than ``grace_s`` after the close counts
  as failed).  A backlog's queued requests, none of them due in the window,
  are all ended at once.

The engine ends a request past its deadline from its slot, or from the
queue.  It removes a queued one with ``list.remove``, which compares it to
every request ahead of it by value; so a queued request is ended only when
every request ahead of it is ended too.

What the metrics read is :class:`Run`, a plain record of the run.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass
class ReqRecord:
    arrival: float
    admitted: Optional[float]
    first: Optional[float]
    token_times: List[float]
    prompt_len: int
    state: str
    greedy: bool
    due: bool  # arrived inside the window


@dataclasses.dataclass
class Tick:
    t: float
    live: List[int]  # tokens in the cache of each active slot at this tick


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read.

    Times are seconds from the start of ``ServeEngine.run``, all of them
    stamped by the engine.
    """

    seconds: float
    open: float
    close: float
    grace_s: float
    setup_s: float
    requests: List[ReqRecord]
    ticks: List[Tick]
    compile_events: List[tuple]  # (t, seconds)
    need: Callable[[List[int]], tuple]  # live lengths -> (ops, bytes)
    peak: Dict
    trace: Optional[object] = None  # trace.Summary in a --trace 1 run

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.open <= t < self.close

    def answered(self, r: ReqRecord) -> bool:
        """A first token, no later than ``grace_s`` after the close."""
        return r.first is not None and r.first <= self.close + self.grace_s and r.state != "failed"


class Window:
    def __init__(
        self,
        requests,
        seconds: float,
        grace_s: float,
        *,
        open_at_s: Optional[float] = None,
        open_after_first_tokens: Optional[int] = None,
        on_open: Optional[Callable[[], None]] = None,
        on_close: Optional[Callable[[], None]] = None,
    ):
        self.requests = list(requests)
        self.seconds = seconds
        self.grace_s = grace_s
        self.open_at_s = open_at_s
        self.open_after_first_tokens = open_after_first_tokens
        self.on_open = on_open
        self.on_close = on_close
        self.open: Optional[float] = open_at_s
        self.close: Optional[float] = None if open_at_s is None else open_at_s + seconds
        self.opened = False
        self.closed = False
        self.first_tokens = 0
        for r in self.requests:
            r.on_token = self._hook(r)

    def in_window(self, t: float) -> bool:
        return self.open is not None and self.open <= t < self.close

    def _hook(self, req):
        return lambda _token: self._on_token(req)

    def _on_token(self, req) -> None:
        now = req.token_times[-1]
        if len(req.output) == 1:
            self.first_tokens += 1
        if not self.opened:
            if self.open is None and self.first_tokens >= self.open_after_first_tokens:
                self.open, self.close = now, now + self.seconds
            if self.open is not None and now >= self.open:
                self.opened = True
                if self.on_open:
                    self.on_open()
        elif not self.closed and now >= self.close:
            self.closed = True
            if self.on_close:
                self.on_close()
            backlog = self.open_at_s is None
            for r in self.requests:
                if r.t_first_token is not None or backlog:
                    self._stop(r)
        elif self.closed and len(req.output) == 1:
            self._stop(req)

    def _stop(self, r) -> None:
        # a deadline that has passed: the engine's next sweep ends the request
        r.deadline_s = self.close - r.arrival_s - 1e-3


def records(requests, window: Window) -> List[ReqRecord]:
    return [_record(r, window) for r in requests]


def _record(r, window: Window) -> ReqRecord:
    return ReqRecord(
        arrival=r.arrival_s,
        admitted=r.t_admitted,
        first=r.t_first_token,
        token_times=list(r.token_times or []),
        prompt_len=len(r.prompt),
        state=r.state,
        greedy=r.temperature <= 0,
        due=window.open <= r.arrival_s < window.close,
    )


def ticks(events: List[Dict], requests) -> List[Tick]:
    """Decode ticks with the live cache length of each active slot.

    A slot's cache holds its prompt and every token emitted before the
    tick; the tick writes one more and attends over all of them.
    """
    by_rid = {r.rid: r for r in requests}
    out = []
    for e in events:
        if e["kind"] != "decode_tick":
            continue
        live = []
        for rid in e["rids"]:
            r = by_rid.get(rid)
            if r is None:
                continue
            before = bisect.bisect_left(r.token_times or [], e["t"])
            live.append(len(r.prompt) + before)
        out.append(Tick(t=e["t"], live=live))
    return out

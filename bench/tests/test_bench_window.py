"""Window statistics and the window's own bookkeeping, on hand-made runs."""

import pytest

from bench import window as W
from bench.spec import ROOT, load_module


def reader(name):
    return load_module(ROOT / "bench" / "metrics" / f"{name}.py").read


def req(arrival, times, admitted=None, state="ok", due=True):
    return W.ReqRecord(
        arrival=arrival, admitted=admitted, first=times[0] if times else None,
        token_times=list(times), prompt_len=16, state=state, greedy=True, due=due,
    )


def run(requests, open_=0.0, close=10.0, grace=5.0, ticks=(), compile_events=()):
    return W.Run(
        seconds=close - open_, open=open_, close=close, grace_s=grace, setup_s=1.5,
        requests=list(requests), ticks=list(ticks), compile_events=list(compile_events),
        need=lambda live: (1e9 * len(live), 0), peak={"int8_ops_per_s": 1e12},
    )


def steady(start, n=80, gap=0.1):
    return [start + i * gap for i in range(n)]


def stalled(start, n=80, gap=0.1, at=5.0, stall=2.0):
    return [t + stall if t >= at else t for t in steady(start, n, gap)]


def test_stall_inside_window_moves_throughput_and_slow_ticks_move_itl():
    calm = run([req(0.5, steady(1.0 + k * 0.01)) for k in range(4)])
    hit = run([req(0.5, stalled(1.0 + k * 0.01, at=2.0 + k, stall=2.0)) for k in range(4)])
    slow = run([req(0.5, steady(1.0 + k * 0.01, gap=0.11)) for k in range(4)])
    assert reader("itl_p50_ms")(calm) == pytest.approx(100.0)
    assert reader("itl_p50_ms")(hit) == pytest.approx(100.0)  # one stalled gap in 79
    assert reader("itl_p50_ms")(slow) == pytest.approx(110.0)
    # the same 320 tokens, but the stall pushes some past the close
    assert reader("out_tokens_per_s")(calm) == pytest.approx(32.0)
    assert reader("out_tokens_per_s")(hit) < 32.0


def test_tokens_after_close_do_not_count():
    r = run([req(0.5, [9.0, 9.5, 10.0, 10.5, 11.0])])
    assert reader("out_tokens_per_s")(r) == pytest.approx(2 / 10)
    assert reader("itl_p50_ms")(r) == pytest.approx(500.0)  # only 9.0 -> 9.5 lies inside


def test_failures_count_as_late():
    ok = [req(1.0, [1.5]), req(2.0, [2.5]), req(3.0, [3.5])]
    assert reader("ttft_p50_s")(run(ok)) == pytest.approx(0.5)
    failed = ok[:1] + [req(4.0, [], state="deadline"), req(5.0, [5.2], state="failed"), req(6.0, [16.0])]
    # 0.5, inf (no token), inf (failed), inf (first token after close + grace) -> median inf
    with pytest.raises(RuntimeError):
        reader("ttft_p50_s")(run(failed))
    # 0.5, inf, inf, 0.5, 0.5 -> 0.5
    assert reader("ttft_p50_s")(run(failed[:3] + ok[1:])) == pytest.approx(0.5)


def test_not_due_requests_give_no_ttft():
    assert reader("ttft_p50_s")(run([req(0.0, [1.0], due=False)])) is None


def test_host_span_metrics():
    r = run(
        [req(0.0, [2.0, 2.1], admitted=1.0), req(0.0, [12.0], admitted=11.0), req(0.0, [4.0], admitted=3.5)],
        compile_events=[(1.2, 0.5), (3.6, 0.25), (10.5, 9.0)],
        ticks=[W.Tick(t=1.0, live=[5]), W.Tick(t=2.0, live=[5, 6]), W.Tick(t=4.0, live=[7])],
    )
    assert reader("admit_s")(r) == pytest.approx(0.75)
    assert reader("compile_share")(r) == pytest.approx(7.5)
    # ticks at 2.0 (2 slots, 1.0 s after the last) and 4.0 (1 slot, 2.0 s after)
    assert reader("decode_mfu")(r) == pytest.approx(100.0 * 3e9 / 3.0 / 1e12)
    assert reader("setup_s")(r) == 1.5


class FakeRequest:
    """Emits tokens as the engine does: stamped on its clock, then ``on_token``."""

    def __init__(self, arrival):
        self.arrival_s, self.output, self.t_first_token, self.deadline_s = arrival, [], None, None
        self.on_token, self.t_admitted, self.prompt, self.state, self.temperature = None, None, [1, 2], "ok", 0.0
        self.token_times = []

    def emit(self, t):
        self.output.append(7)
        self.token_times.append(t)
        if self.t_first_token is None:
            self.t_first_token = t
        self.on_token(7)


def test_backlog_window_opens_on_full_slots_and_ends_everything():
    reqs = [FakeRequest(0.0) for _ in range(3)]
    w = W.Window(reqs, 5.0, 0.0, open_after_first_tokens=2)
    reqs[0].emit(1.0)
    assert not w.opened
    reqs[1].emit(2.0)
    assert (w.opened, w.open, w.close) == (True, 2.0, 7.0)
    assert all(r.deadline_s is None for r in reqs)
    reqs[0].emit(7.5)
    assert w.closed
    # every request, the queued third one too, is past its deadline at 7.5
    assert all(7.5 - r.arrival_s > r.deadline_s for r in reqs)


def test_poisson_window_keeps_a_queued_due_request_until_its_first_token():
    early, due = FakeRequest(0.5), FakeRequest(5.5)
    w = W.Window([early, due], 5.0, 30.0, open_at_s=1.0)
    early.emit(0.8)
    assert not w.opened
    early.emit(1.2)
    assert (w.opened, w.open, w.close) == (True, 1.0, 6.0)
    early.emit(6.2)
    assert w.closed and early.deadline_s < 6.2 - early.arrival_s
    assert due.deadline_s is None  # still queued: served until its first token
    due.emit(9.0)
    assert 9.0 - due.arrival_s > due.deadline_s
    recs = W.records([early, due], w)
    assert [r.token_times for r in recs] == [[0.8, 1.2, 6.2], [9.0]]
    assert [r.first for r in recs] == [0.8, 9.0]
    assert [r.due for r in recs] == [False, True]

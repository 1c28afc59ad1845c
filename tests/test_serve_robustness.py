"""Fault-tolerant serving: differential tests against the sequential oracle.

The contract under test (docs/serving-robustness.md): failures are inputs,
not outages.  A request that hits an injected fault is retried/re-admitted
under the same ``(seed, rid)`` RNG key, so its final token sequence is
bit-identical to a run with no fault at all — which is what lets every test
here diff the fault-tolerant engine against the fault-free
``serve_sequential`` oracle, token for token.
"""

import os
import signal
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.smoke import smoke_variant
from repro.core import dispatch
from repro.models import model_zoo as Z
from repro.runtime.faults import FaultPlan
from repro.runtime.serve_loop import (
    STATE_DEADLINE,
    STATE_FAILED,
    STATE_OK,
    Request,
    ServeEngine,
    serve_sequential,
)

MAX_LEN = 48


@pytest.fixture(autouse=True)
def _clean_demotions():
    dispatch.clear_demotions()
    yield
    dispatch.clear_demotions()


@pytest.fixture(scope="module")
def model():
    cfg = smoke_variant(get_config("granite-8b"))
    params = Z.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, Z.prepare_serving_params(params, cfg)


def _requests(cfg, n=4, temperature=0.8, max_new=6, deadline=None):
    """Deterministic mixed-length request set (fresh objects per call, so
    engine and oracle never share mutable state)."""
    rng = np.random.default_rng(1234)
    return [
        Request(
            prompt=rng.integers(0, cfg.vocab_size, size=(3 + 2 * i,)).astype(np.int32),
            max_new_tokens=max_new,
            temperature=temperature,
            deadline_s=deadline,
        )
        for i in range(n)
    ]


def _oracle(model, **kw):
    cfg, params = model
    return serve_sequential(cfg, params, _requests(cfg, **kw), max_len=MAX_LEN, seed=0)


def _engine(model, **kw):
    cfg, params = model
    return ServeEngine(cfg, params, batch_slots=2, max_len=MAX_LEN, seed=0, **kw)


def _assert_token_identical(got, want):
    for g, w in zip(got, want):
        assert g.output == w.output, (
            f"rid={g.rid} diverged after faults: {g.output} != {w.output}"
        )


# ---------------------------------------------------------------------------
# (a) mid-decode failure -> retry/re-admission is bit-identical
# ---------------------------------------------------------------------------


def test_transient_tick_fault_retries_in_place(model):
    """A one-shot decode-tick fault is absorbed by the in-place retry: no
    request loses progress, outputs match the unfailed oracle exactly."""
    want = _oracle(model)
    eng = _engine(model, fault_plan=FaultPlan(decode_fail_ticks=(1, 4)))
    got = eng.run(_requests(model[0]))
    kinds = [e["kind"] for e in eng.last_events]
    assert kinds.count("step_fault") == 2
    assert "retry_tick" in kinds
    assert all(r.state == STATE_OK and r.retries == 0 for r in got)
    _assert_token_identical(got, want)


def test_nan_logits_fail_one_request_and_replay_bit_identical(model):
    """THE re-admission guarantee: NaN logits mid-generation kill ONE
    request's progress; its replay from the prompt — same (seed, rid) RNG,
    temperature > 0 — emits the exact token sequence of an unfailed run,
    and co-batched requests never notice."""
    want = _oracle(model)
    eng = _engine(model, fault_plan=FaultPlan(nan_ticks={1: 0}))
    got = eng.run(_requests(model[0]))
    kinds = [e["kind"] for e in eng.last_events]
    assert "nan_logits" in kinds and "requeue" in kinds
    assert sum(r.retries for r in got) == 1  # exactly one victim
    assert all(r.state == STATE_OK for r in got)
    _assert_token_identical(got, want)


def test_prefill_fault_readmits_bit_identical(model):
    want = _oracle(model)
    eng = _engine(model, fault_plan=FaultPlan(prefill_fail_rids={0: 1}))
    got = eng.run(_requests(model[0]))
    assert any(e["kind"] == "prefill_fault" for e in eng.last_events)
    assert got[0].retries == 1 and got[0].state == STATE_OK
    _assert_token_identical(got, want)


def test_retry_exhaustion_is_terminal_but_engine_survives(model):
    """A persistent decode failure burns the whole retry budget: requests
    end "failed" (never silently lost), and the SAME engine then serves a
    clean queue — the failure was contained to the run, not the process."""
    eng = _engine(
        model,
        fault_plan=FaultPlan(decode_fail_attempts=tuple(range(500))),
        max_retries=1,
        retry_backoff_s=0.0,
    )
    got = eng.run(_requests(model[0], n=3))
    assert all(r.state == STATE_FAILED for r in got)
    assert all(r.retries == eng.max_retries + 1 for r in got)
    assert eng.last_failed == [r.rid for r in got]
    # engine object still healthy: a fresh fault-free engine semantics check
    clean = _engine(model)
    again = clean.run(_requests(model[0], n=3))
    assert all(r.state == STATE_OK for r in again)
    assert clean.last_failed == []
    _assert_token_identical(again, _oracle(model, n=3))


# ---------------------------------------------------------------------------
# (c) backend demotion: repeated fused failures -> pinned mxu fallback
# ---------------------------------------------------------------------------


def test_repeated_backend_failures_demote_with_zero_lost_requests(model):
    want = _oracle(model)
    eng = _engine(
        model, fault_plan=FaultPlan(backend_fail={"fused": 2}), demote_after=2
    )
    got = eng.run(_requests(model[0]))
    demotes = [e for e in eng.last_events if e["kind"] == "demote"]
    assert demotes and demotes[0]["from"] == "fused" and demotes[0]["to"] == "mxu"
    assert dispatch.demotions() == {"fused": "mxu"}
    assert dispatch.resolve_backend("fused") == "mxu"
    # zero lost: every request terminal-ok with full, oracle-exact output
    assert all(r.state == STATE_OK for r in got)
    _assert_token_identical(got, want)


def test_demotion_pins_dispatch_for_explicit_backends():
    dispatch.pin_demotion("fused", "mxu")
    assert dispatch.resolve_backend("fused") == "mxu"
    assert dispatch.resolve_backend("mxu") == "mxu"
    with pytest.raises(ValueError):
        dispatch.pin_demotion("mxu", "fused")  # would cycle
    dispatch.clear_demotions()
    assert dispatch.resolve_backend("fused") == "fused"


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------


def test_queued_request_past_deadline_is_expired_not_served(model):
    cfg, params = model
    eng = ServeEngine(cfg, params, batch_slots=1, max_len=MAX_LEN, seed=0)
    head = Request(
        prompt=np.arange(4, dtype=np.int32) % cfg.vocab_size, max_new_tokens=4
    )
    # one slot: the second request waits behind head's (compiling) prefill
    # far longer than its deadline allows
    starved = Request(
        prompt=np.arange(5, dtype=np.int32) % cfg.vocab_size,
        max_new_tokens=4,
        deadline_s=0.01,
    )
    done = eng.run([head, starved])
    assert done[0].state == STATE_OK
    assert done[1].state == STATE_DEADLINE
    assert not done[1].output
    misses = [e for e in eng.last_events if e["kind"] == "deadline_miss"]
    assert [e["rid"] for e in misses] == [done[1].rid]


def test_running_request_past_deadline_frees_its_slot(model):
    cfg, params = model
    # 0.2 s injected latency per tick against a 0.5 s deadline: whatever the
    # compile overhead, no request can reach its 30-token budget in time
    eng = _engine(model, fault_plan=FaultPlan(every_tick_delay_s=0.2))
    reqs = _requests(cfg, n=2, temperature=0.0, max_new=30, deadline=0.5)
    done = eng.run(reqs)
    assert all(r.state == STATE_DEADLINE for r in done)
    assert all(len(r.output) < r.max_new_tokens for r in done)
    # the availability block surfaces the misses
    from repro.runtime.traffic import summarize_availability

    avail = summarize_availability(done, eng.last_events)
    assert avail["n_deadline_missed"] == 2
    assert avail["deadline_miss_rate"] == 1.0


def test_validation_rejects_bad_deadlines_and_shapes(model):
    cfg, params = model
    eng = _engine(model)
    with pytest.raises(ValueError, match="rank-1"):
        eng.run([Request(prompt=np.zeros((2, 3), np.int32), max_new_tokens=2)])
    with pytest.raises(ValueError, match="deadline_s"):
        eng.run(
            [
                Request(
                    prompt=np.zeros((4,), np.int32),
                    max_new_tokens=2,
                    deadline_s=0.0,
                )
            ]
        )
    with pytest.raises(ValueError, match="non-empty"):
        eng.run([Request(prompt=np.zeros((4,), np.int32), max_new_tokens=0)])


def test_oracle_parity_under_temperature_without_faults(model):
    """Baseline for every differential above: at T>0 the engine and oracle
    share sampling exactly (same _sample, same per-rid RNG)."""
    want = _oracle(model, temperature=1.1)
    eng = _engine(model)
    got = eng.run(_requests(model[0], temperature=1.1))
    _assert_token_identical(got, want)


# ---------------------------------------------------------------------------
# (b) crash-recoverable engine state
# ---------------------------------------------------------------------------


def test_snapshot_resume_in_process(model, tmp_path):
    """An engine built from only (config, params, snapshot_dir) finishes a
    snapshotted run token-for-token identically — nothing about the live
    process was load-bearing."""
    want = _oracle(model)
    snap = str(tmp_path / "snap")
    eng = _engine(model, snapshot_every=2, snapshot_dir=snap)
    eng.run(_requests(model[0]))
    assert any(e["kind"] == "snapshot" for e in eng.last_events)

    fresh = _engine(model, snapshot_every=2, snapshot_dir=snap)
    res = fresh.resume()
    assert [e["kind"] for e in fresh.last_events][0] == "resume"
    _assert_token_identical(sorted(res, key=lambda r: r.rid), want)


def test_resume_rejects_geometry_mismatch(model, tmp_path):
    cfg, params = model
    snap = str(tmp_path / "snap")
    eng = _engine(model, snapshot_every=1, snapshot_dir=snap)
    eng.run(_requests(cfg, n=2))
    other = ServeEngine(
        cfg, params, batch_slots=3, max_len=MAX_LEN, seed=0, snapshot_dir=snap
    )
    with pytest.raises(ValueError, match="geometry mismatch"):
        other.resume()
    empty = ServeEngine(
        cfg, params, batch_slots=2, max_len=MAX_LEN, seed=0,
        snapshot_dir=str(tmp_path / "nothing-here"),
    )
    with pytest.raises(FileNotFoundError):
        empty.resume()


def test_snapshot_write_crash_is_an_event_not_an_outage(model, tmp_path):
    want = _oracle(model)
    eng = _engine(
        model,
        fault_plan=FaultPlan(snapshot_fail_at=(0,)),
        snapshot_every=2,
        snapshot_dir=str(tmp_path / "snap"),
    )
    got = eng.run(_requests(model[0]))
    kinds = [e["kind"] for e in eng.last_events]
    assert "snapshot_failed" in kinds
    assert "snapshot" in kinds  # the next boundary succeeded
    assert all(r.state == STATE_OK for r in got)
    _assert_token_identical(got, want)


_CHILD = textwrap.dedent(
    """
    import jax, numpy as np
    from repro.configs import get_config
    from repro.configs.smoke import smoke_variant
    from repro.models import model_zoo as Z
    from repro.runtime.faults import FaultPlan
    from repro.runtime.serve_loop import Request, ServeEngine

    cfg = smoke_variant(get_config("granite-8b"))
    params = Z.prepare_serving_params(Z.init_params(jax.random.PRNGKey(0), cfg), cfg)
    rng = np.random.default_rng(1234)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, size=(3 + 2 * i,)).astype(np.int32),
                max_new_tokens=12, temperature=0.8)
        for i in range(4)
    ]
    eng = ServeEngine(
        cfg, params, batch_slots=2, max_len=48, seed=0,
        fault_plan=FaultPlan(every_tick_delay_s=0.5),
        snapshot_every=1, snapshot_dir={snap!r},
    )
    eng.run(reqs)
    print("CHILD_FINISHED", flush=True)
    """
)


@pytest.mark.slow
def test_sigkill_mid_batch_then_resume_matches_oracle(model, tmp_path):
    """The crash-recovery acceptance test: a serving process is SIGKILLed
    mid-batch (a real subprocess, no cooperative shutdown); a fresh engine
    resumes from the last committed snapshot and completes every in-flight
    request token-for-token identical to the sequential oracle."""
    cfg, params = model
    snap = str(tmp_path / "snap")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.Popen(
        [sys.executable, "-c", _CHILD.format(snap=snap)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
    )
    try:
        # wait for the first COMMITTED snapshot, then kill hard mid-batch
        deadline = time.time() + 240
        committed = None
        while time.time() < deadline and proc.poll() is None:
            mgr_dirs = [
                d for d in (os.listdir(snap) if os.path.isdir(snap) else [])
                if d.startswith("step_")
                and os.path.exists(os.path.join(snap, d, "_COMMITTED"))
            ]
            if mgr_dirs:
                committed = mgr_dirs
                break
            time.sleep(0.05)
        assert committed, "child never committed a snapshot"
        assert proc.poll() is None, (
            "child finished before SIGKILL: "
            + proc.stdout.read().decode(errors="replace")
        )
        time.sleep(0.6)  # land the kill strictly inside the decode loop
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        assert proc.returncode == -signal.SIGKILL
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)

    # the oracle for the child's workload (identical generator seed)
    rng = np.random.default_rng(1234)
    reqs = [
        Request(
            prompt=rng.integers(0, cfg.vocab_size, size=(3 + 2 * i,)).astype(np.int32),
            max_new_tokens=12,
            temperature=0.8,
        )
        for i in range(4)
    ]
    want = serve_sequential(cfg, params, reqs, max_len=48, seed=0)

    eng = ServeEngine(
        cfg, params, batch_slots=2, max_len=48, seed=0,
        snapshot_every=0, snapshot_dir=snap,
    )
    res = sorted(eng.resume(), key=lambda r: r.rid)
    assert all(r.state == STATE_OK for r in res)
    for got, exp in zip(res, want):
        assert got.output == exp.output, (
            f"rid={got.rid}: resumed run diverged from oracle after SIGKILL: "
            f"{got.output} != {exp.output}"
        )


def test_serve_cli_exits_nonzero_when_requests_fail(monkeypatch, capsys):
    """``launch/serve.py`` prints the failed count on a line of its own; a
    failed request is a failed run unless the failures were injected."""
    import json
    import sys

    from repro.launch import serve

    plan = FaultPlan(decode_fail_attempts=tuple(range(100)))
    argv = ["serve", "--arch", "granite-8b", "--smoke", "--requests", "2",
            "--prompt-len", "4", "--max-new", "2", "--max-len", "16"]
    monkeypatch.setattr(sys, "argv", argv + ["--fault-plan", json.dumps(plan.to_dict())])
    serve.main()  # injected: reported, exit 0
    assert "[serve] failed: 2 of 2 requests" in capsys.readouterr().out

    monkeypatch.setattr(sys, "argv", argv)
    monkeypatch.setattr(serve, "parse_fault_plan", lambda _: plan)
    with pytest.raises(SystemExit) as exc:
        serve.main()  # same failures, none asked for: the run fails
    assert exc.value.code == 1
    assert "[serve] failed: 2 of 2 requests" in capsys.readouterr().out

"""The harness at a smoke size on the CPU, through its functions: a whole run
with the look for a chip skipped, the same run with a served token altered
where the engine produces it, and the refusals without a chip."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness
from bench.spec import ROOT, Cell
from bench.tests.kit import add_cell, copy_bench, tiny_config, tiny_mix

# Readings of the tiny cell on the CPU (one seed): max_logit_err 0.082 for the
# program, 0.90 for the control (4-bit activations); max_logit_gap 0.0 for the
# program, 0.11 for the control, and about the logits' whole range for a token
# altered to the least likely one.
TINY_LIMITS = {"max_logit_err": 0.3, "max_logit_gap": 0.05}


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    # a backlog: the window opens on a decode batch that is already full, so
    # tokens fall inside it however slowly this CPU admits requests
    root = copy_bench(tmp_path_factory.mktemp("bench"))
    mix = tiny_mix(arrival={"kind": "backlog", "n_requests": 8}, grace_s=0.0)
    return Cell(add_cell(root, tiny_config(**TINY_LIMITS), mix), root)


def test_run_at_smoke_size_is_correct(tiny_cell):
    res = harness.run(tiny_cell, 2**31 + 101, 4.0, False, time.perf_counter(), require_tpu=False)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    # no request is due inside a backlog's window: no time to first token
    assert set(res["metrics"]) == {"itl_p50_ms", "out_tokens_per_s", "setup_s"}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert res["checks"]["tokens_compared"]["value"] >= 1


def test_altered_token_is_not_correct(tiny_cell, monkeypatch):
    from repro.runtime import serve_loop

    def least_likely(logits, temperature, rng):
        return int(np.argmin(logits))

    monkeypatch.setattr(serve_loop, "_sample", least_likely)
    res = harness.run(tiny_cell, 2**31 + 101, 4.0, False, time.perf_counter(), require_tpu=False)
    assert res["correct"] is False
    assert res["checks"]["max_logit_gap"]["value"] > res["checks"]["max_logit_gap"]["limit"]


def test_control_is_not_correct_at_small_size(tiny_cell):
    """The reference at 4-bit activations in the program's place, judged by
    the same comparison as the program, comes out not correct; so does a
    served token altered to the least likely one."""
    details = {}
    res = harness.run(tiny_cell, 2**31 + 103, 4.0, False, time.perf_counter(), require_tpu=False,
                      control_bits=4, details=details)
    assert res["correct"] is True, res["checks"]
    control = details["verdict"]["control"]
    assert control["correct"] is False
    assert control["checks"]["max_logit_err"]["value"] > control["checks"]["max_logit_err"]["limit"]
    assert details["verdict"]["altered_token"]["correct"] is False


def test_sampled_requests_are_compared(tmp_path):
    """Requests sampled at a temperature have their logits compared too; the
    served-token gap is read on the greedy ones alone."""
    root = copy_bench(tmp_path)
    mix = tiny_mix(arrival={"kind": "backlog", "n_requests": 8}, grace_s=0.0, temperature=0.7,
                   greedy_share=0.25)
    cell = Cell(add_cell(root, tiny_config(**TINY_LIMITS), mix), root)
    details = {}
    res = harness.run(cell, 2**31 + 105, 4.0, False, time.perf_counter(), require_tpu=False, details=details)
    assert res["correct"] is True, res["checks"]
    assert any(r.temperature > 0 for r in details["picked"])
    assert "max_logit_err" in res["checks"]


def test_failed_request_is_not_correct(tiny_cell, monkeypatch):
    from repro.runtime import serve_loop

    admit = serve_loop.ServeEngine._admit
    first_planned = len(tiny_cell.mix["prompt_pool"])  # rids after the warm-up's

    def failing(self, req, *a, **kw):
        if req.rid == first_planned:
            raise RuntimeError("prefill fault")
        return admit(self, req, *a, **kw)

    monkeypatch.setattr(serve_loop.ServeEngine, "_admit", failing)
    res = harness.run(tiny_cell, 2**31 + 101, 4.0, False, time.perf_counter(), require_tpu=False)
    assert res["failed"] >= 1 and res["correct"] is False
    assert res["checks"]["failed_requests"] == {"value": res["failed"], "limit": 0}


def test_no_chip_is_refused():
    with pytest.raises(harness.NoChip):
        harness.device_info(1)


def _run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env, capture_output=True, text=True, timeout=300
    )


def test_run_py_without_a_tpu_exits_nonzero_and_prints_no_result():
    p = _run_py(ROOT, "--workload", "granite-8b.complete", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode == 3 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    root = copy_bench(tmp_path)
    p = _run_py(root, "--workload", "granite-8b.complete", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert json.loads((root / "BENCHMARK.json").read_text())["paths"] == ["bench"]

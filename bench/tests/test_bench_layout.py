"""The benchmark is driven by data: a new configuration, traffic mix or
metric is new files plus entries in ``BENCHMARK.json``, and nothing else."""

import hashlib
import json

import numpy as np
import pytest

from bench import traffic
from bench import window as W
from bench.spec import ROOT, Cell
from bench.tests.kit import copy_bench

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest() for p in root.rglob("*") if p.is_file()}


def test_throwaway_mix_and_metric_need_only_new_files(tmp_path):
    root = copy_bench(tmp_path)
    before = digest(root)
    mix = json.loads((root / "bench" / "traffic" / "complete.json").read_text())
    mix.update(arrival={"kind": "poisson", "rate_rps": 2.0}, prompt_pool=[64, 128], plan_seed=7)
    (root / "bench" / "traffic" / "burst.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "first_tokens.py").write_text(
        "def read(run):\n    return float(sum(1 for r in run.requests if run.in_window(r.first)))\n"
    )
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "granite-8b.burst", "config": "granite-8b", "traffic": "burst", "chips": 1, "why": "test"}
    )
    bench["per_layer"].append({
        "name": "first_tokens", "unit": "requests", "better": "higher", "source": "host_clock",
        "layer": "engine (runtime/serve_loop.py)", "moves": "ttft_p50_s", "workloads": ["granite-8b.burst"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = digest(root)
    assert [p for p in before if before[p] != after[p]] == [root.relative_to(root) / "BENCHMARK.json"]

    cell = Cell("granite-8b.burst", root)
    assert [m["name"] for m in cell.metrics("per_layer")] == ["first_tokens"]
    assert [m["name"] for m in cell.metrics("end_to_end")] == ["itl_p50_ms", "setup_s"]
    planned = traffic.plan(cell.mix, seed=11, seconds=20.0, vocab_size=cell.config["vocab_size"])
    assert planned and {len(p.prompt) for p in planned} == {64, 128}
    rec = W.ReqRecord(arrival=11.0, admitted=11.5, first=12.0, token_times=[12.0], prompt_len=64,
                      state="ok", greedy=True, due=True)
    run = W.Run(seconds=20.0, open=10.0, close=30.0, grace_s=30.0, setup_s=1.0, requests=[rec],
                ticks=[], compile_events=[], need=lambda live: (0, 0), peak={})
    assert cell.reader("first_tokens").read(run) == 1.0


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = Cell(cell)
    names = [m["name"] for m in c.metrics("end_to_end") + c.metrics("per_layer")]
    assert "setup_s" in names and len(c.metrics("end_to_end")) >= 2 and c.metrics("per_layer")
    for name in names:
        assert callable(c.reader(name).read)
    assert c.family().decode_need(c.config, [1])[0] > 0
    assert c.reference().served_logits
    assert c.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        c.peaks("cpu")


def test_plan_gives_every_seed_the_same_work():
    cell = Cell("granite-8b.complete")
    vocab = cell.config["vocab_size"]
    a = traffic.plan(cell.mix, 1, 40.0, vocab)
    b = traffic.plan(cell.mix, 2**31 + 77, 40.0, vocab)
    again = traffic.plan(cell.mix, 1, 40.0, vocab)
    assert all(np.array_equal(x.prompt, y.prompt) and x.arrival_s == y.arrival_s for x, y in zip(a, again))
    for key in (lambda p: len(p.prompt), lambda p: p.max_new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert a[-1].arrival_s < cell.mix["ramp_s"] + 40.0
    assert [p.arrival_s for p in a] == [p.arrival_s for p in b]
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    pool = set(cell.mix["prompt_pool"])
    assert {len(p.prompt) for p in a} == pool
    assert all(len(p.prompt) + p.max_new_tokens <= cell.config["max_len"] for p in a)


def test_backlog_is_due_at_once():
    cell = Cell("granite-8b.batch")
    plan = traffic.plan(cell.mix, 5, 40.0, cell.config["vocab_size"])
    assert len(plan) == cell.mix["arrival"]["n_requests"] and {p.arrival_s for p in plan} == {0.0}

"""The program's named scopes and spans, read from a small recorded trace:
device time per group of the decode program, the engine's host work per
tick and per admission, and idle gaps labelled by the span over them."""

import json

import jax
import numpy as np
import pytest

from bench import spans as S
from bench import trace as T
from bench import window as W
from bench.spec import ROOT, load_module
from bench.tests.kit import add_cell, copy_bench, tiny_config, tiny_mix

BODY = "jit(_decode)/while/body/closed_call/"


DECODE = "jit__decode(15569214447562970945)"

# Two decode executions of 100 ns on one device (starts and durations in ns),
# shaped like a TPU v5e trace: the layer scan's ``while`` spans its body; in
# the body, two QMM sites, the attention core's scores and AV, an unscoped
# residual add; after the scan the head.  The second execution also runs a
# loop inside the attention core that spans its scores and AV.  Each row:
# (start, duration, opcode, scope path of the instruction).
ROWS = [
    (1_000, 80, "while", "jit(_decode)/while"),
    (1_000, 30, "convert_convert_fusion", BODY + "ffn.up/convert_element_type"),
    (1_030, 10, "fusion", BODY + "attn.q/dot_general"),
    (1_040, 20, "fusion", BODY + "attn.core/attn.qk/dot_general"),
    (1_060, 10, "fusion", BODY + "attn.core/attn.av/exp"),
    (1_070, 10, "add", BODY + "add"),
    (1_080, 20, "fusion", "jit(_decode)/head/dot_general"),
    (1_150, 40, "fusion", None),  # the prefill scan: not a decode instruction
    (1_300, 80, "while", "jit(_decode)/while"),
    (1_300, 40, "fusion", BODY + "ffn.down/dot_general"),
    (1_340, 30, "while", BODY + "attn.core/while"),
    (1_340, 10, "fusion", BODY + "attn.core/attn.cache/dynamic-update-slice"),
    (1_350, 20, "fusion", BODY + "attn.core/attn.qk/dot_general"),
    (1_370, 10, "add", BODY + "add"),
    (1_380, 20, "fusion", "jit(_decode)/head/dot_general"),
    (1_700, 100, "fusion", BODY + "ffn.up/dot_general"),
]


def recorded(rows, modules):
    """A one-device trace of ``rows``, each its own instruction of the decode program."""
    ops = [(s, d, f"%{kind}.{i} = s32[8] {kind}(%p)") for i, (s, d, kind, _) in enumerate(rows)]
    scopes = {f"{kind}.{i}": path for i, (_, _, kind, path) in enumerate(rows) if path}
    return S.Trace([T.Device(modules, ops)], [], 1_000, {DECODE: scopes})


MODULES = [
    (1_000, 100, DECODE),
    (1_150, 40, "jit_scan(6716087165648592643)"),
    (1_300, 100, DECODE),
    (1_700, 100, DECODE),  # after the window
]
TRACE = recorded(ROWS, MODULES)


def test_groups_by_outermost_scope_and_never_control_flow():
    assert S.group_of("%fusion.1 = f32[8] fusion(%p)", BODY + "ffn.gate/mul") == "qmm"
    assert S.group_of("%fusion.1 = f32[8] fusion(%p)", BODY + "attn.core/attn.qk/mul") == "attn"
    assert S.part_of("%fusion.1 = f32[8] fusion(%p)", BODY + "attn.core/attn.qk/mul") == "attn.qk"
    assert S.part_of("%fusion.1 = f32[8] fusion(%p)", BODY + "attn.core/add") == "attn.core"
    assert S.group_of("%fusion.1 = f32[8] fusion(%p)", "jit(_decode)/head/dot_general") == "head"
    assert S.group_of("%add.1 = f32[8] add(%p)", BODY + "add") is None
    assert S.group_of("%while.6 = (s32[]) while(%t)", BODY + "attn.core/while") is None


def test_decode_groups_by_hand():
    # qmm 30+10 and 40, attn 20+10 and 10+20, head 20 and 20, over two executions
    got = S.decode_scopes(TRACE, 1_000, 1_500)
    assert got == pytest.approx({"qmm": 40e-6, "attn": 30e-6, "head": 20e-6})
    parts = S.decode_scopes(TRACE, 1_000, 1_500, S.part_of, S.PARTS)
    assert parts["ffn.up"] == pytest.approx(15e-6) and parts["ffn.down"] == pytest.approx(20e-6)
    assert parts["attn.qk"] == pytest.approx(20e-6) and parts["attn.cache"] == pytest.approx(5e-6)
    assert parts["attn.core"] == 0.0
    assert sum(parts.values()) == pytest.approx(sum(got.values()))


def test_a_loop_that_spans_its_body_is_not_counted_twice():
    second = S.decode_scopes(TRACE, 1_200, 1_500)  # the execution with the loop in the core
    assert second["attn"] == pytest.approx(30e-6)  # cache 10 + scores 20, not the loop's 30 again
    loopless = recorded([r for r in ROWS if r[2] != "while"], MODULES)
    assert S.decode_scopes(loopless, 1_000, 1_500) == S.decode_scopes(TRACE, 1_000, 1_500)


def test_groups_never_overlap_and_fit_in_the_step():
    # an attention op recorded over a QMM op: the shared instant counts once
    rows = [(1_000, 60, "fusion", BODY + "ffn.up/x"), (1_040, 40, "fusion", BODY + "attn.core/attn.av/y")]
    got = S.decode_scopes(recorded(rows, MODULES[:1]), 1_000, 1_500)
    assert got == pytest.approx({"qmm": 60e-6, "attn": 20e-6, "head": 0.0})

    class Run:
        trace = T.summarize(TRACE.devices, 1_000, 1_500)

    step = load_module(ROOT / "bench" / "metrics" / "decode_step_ms.py").read(Run)
    assert sum(S.decode_scopes(TRACE, 1_000, 1_500).values()) <= step


def test_nothing_traced_reads_nothing():
    assert S.decode_scopes(S.Trace([], []), 0, 1_000) == {}
    assert S.program_scopes(b"", S.DECODE_PROGRAM) == {}
    assert S.tick_host_ms([], 0.0, 10.0) is None
    assert S.admit_dispatch_s([], 0.0, 10.0) is None


# the engine's spans of one admission and two ticks (seconds on its clock)
EVENTS = [
    dict(kind="admit", t=1.0, end=1.5, parent=None, rid=0, slot=0, prompt_len=1373),
    dict(kind="prefill", t=1.0, end=1.3, parent=0, rid=0),
    dict(kind="insert", t=1.3, end=1.35, parent=0, rid=0),
    dict(kind="fetch", t=1.35, end=1.45, parent=0, rid=0),
    dict(kind="sample", t=1.45, end=1.5, parent=0, rid=0),
    dict(kind="tick", t=2.0, end=2.2, parent=None, tick=0),
    dict(kind="decode", t=2.0, end=2.001, parent=5),
    dict(kind="fetch", t=2.001, end=2.19, parent=5),
    dict(kind="decode_tick", t=2.19, rids=[0]),
    dict(kind="sample", t=2.19, end=2.2, parent=5),
    dict(kind="tick", t=2.2, end=2.41, parent=None, tick=1),
    dict(kind="decode", t=2.2, end=2.202, parent=10),
    dict(kind="fetch", t=2.202, end=2.4, parent=10),
    dict(kind="sample", t=2.4, end=2.405, parent=10),
    dict(kind="finish", t=2.405, end=2.41, parent=10, rid=0, slot=0),
    dict(kind="tick", t=9.0, end=9.5, parent=None, tick=2),  # after the window
    dict(kind="fetch", t=9.0, end=9.1, parent=15),
]


def test_tick_host_and_admission_dispatch_by_hand():
    # ticks: 200 - 189 = 11 ms and 210 - 198 = 12 ms; median 11.5
    assert S.tick_host_ms(EVENTS, 0.0, 5.0) == pytest.approx(11.5)
    assert S.admit_dispatch_s(EVENTS, 0.0, 5.0) == pytest.approx(0.35)
    assert S.admit_dispatch_s(EVENTS, 1.2, 5.0) is None  # admitted before the window


# host spans on the trace's clock (ns): one admission, then a tick
SPANS = [
    (1_000, 500, "serve.admit", {"rid": 0, "slot": 0, "prompt_len": 1373}),
    (1_000, 300, "serve.prefill", {"rid": 0}),
    (1_350, 100, "serve.fetch", {"rid": 0}),
    (2_000, 200, "serve.tick", {}),
    (2_001, 189, "serve.fetch", {}),
]


def test_gaps_take_the_innermost_span_over_their_middle():
    assert S.label(1_100, 100, SPANS) == "serve.prefill (1373-token admit)"
    assert S.label(1_300, 40, SPANS) == "serve.admit (1373-token admit)"
    assert S.label(2_100, 20, SPANS) == "serve.fetch"
    assert S.label(2_190, 8, SPANS) == "serve.tick"


def test_an_uncovered_gap_keeps_the_reconstructed_label():
    rec = W.ReqRecord(arrival=0.0, admitted=3.0, first=3.6, token_times=[3.6], prompt_len=89,
                      state="ok", greedy=True, due=False)
    ticks = [W.Tick(t=2.19, live=[1374])]
    # engine clock (s) onto the trace's (ns): the trace's 1000 ns is the engine's 0
    got = S.label_gaps([(1.05e-6, 2e-8), (3.0, 0.5)], SPANS, lambda t: 1_000 + t * 1e9, [rec], ticks)
    assert got == ["serve.fetch", "guessed: admission (89-token prefill, retrace, cache insert)"]


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    """The tiny backlog cell, with the CPU given the chip's peaks so that a
    traced run reaches its per-layer metrics."""
    from bench.spec import Cell

    root = copy_bench(tmp_path_factory.mktemp("bench"))
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    (root / "bench" / "peaks.json").write_text(json.dumps(peaks))
    mix = tiny_mix(arrival={"kind": "backlog", "n_requests": 8}, grace_s=0.0)
    return Cell(add_cell(root, tiny_config(max_logit_err=0.3, max_logit_gap=0.05), mix), root)


def test_load_reads_the_engines_spans_from_a_trace(tmp_path):
    from repro.configs import get_config
    from repro.configs.smoke import smoke_variant
    from repro.models import model_zoo as Z
    from repro.runtime.serve_loop import Request, ServeEngine

    cfg = smoke_variant(get_config("granite-8b"))
    params = Z.init_serving_params(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=32, seed=0)
    prompt = np.arange(7, dtype=np.int32)
    eng.run([Request(prompt=prompt, max_new_tokens=3)])  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(T.ANCHOR):
            pass
        eng.run([Request(prompt=prompt, max_new_tokens=3)])
    (path,) = tmp_path.rglob("*.xplane.pb")
    trace = S.load(str(path))
    assert trace.anchor_ns is not None and trace.devices == []  # no TPU plane on the CPU
    # the decode program's optimized HLO from the metadata plane names every scope
    (paths,) = trace.scopes.values()
    parts = {S.part_of("%fusion.1 = f32[] fusion()", p) for p in paths.values()}
    assert set(S.PARTS) <= parts
    names = [s[2] for s in trace.spans]
    assert names.count("serve.admit") == 1 and names.count("serve.tick") == 2
    admit = next(s for s in trace.spans if s[2] == "serve.admit")
    assert admit[3]["prompt_len"] == 7 and admit[0] >= trace.anchor_ns
    prefill = next(s for s in trace.spans if s[2] == "serve.prefill")
    assert S.label(prefill[0], prefill[1], trace.spans) == "serve.prefill (7-token admit)"


def test_breakdown_tool_reads_the_host_spans_on_the_cpu(tiny_cell):
    from bench.tools.breakdown import breakdown

    line = breakdown(tiny_cell, 2**31 + 7, 4.0, False, require_tpu=False)
    assert line["result"]["correct"] is True
    scopes = line["scopes"]
    assert scopes["tick_host_ms"] > 0 and scopes["admit_dispatch_s"] > 0
    assert line["parts"] == {} and scopes["decode_qmm_ms"] is None  # no TPU plane
    assert line["itl_p50_ms"] > 0

"""Host spans of ``ServeEngine`` and named scopes of the decode program.

The engine records each admission and each decode tick as a span in
``last_events`` (``t`` to ``end``, ``parent``) and as a ``serve.<kind>``
annotation on the profiler's host plane; the model names its QMM sites,
attention core and head with ``jax.named_scope``.  CPU, smoke size.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.smoke import smoke_variant
from repro.models import model_zoo as Z
from repro.runtime.serve_loop import Request, ServeEngine

ADMIT_CHILDREN = ("prefill", "insert", "fetch", "sample")
TICK_CHILDREN = ("decode", "fetch", "sample")
SITES = ("attn.q", "attn.k", "attn.v", "attn.o", "ffn.up", "ffn.gate", "ffn.down")
SCOPES = SITES + ("attn.core", "attn.cache", "attn.qk", "attn.av", "head")


@pytest.fixture(scope="module")
def model():
    cfg = smoke_variant(get_config("granite-8b"))
    params = Z.prepare_serving_params(Z.init_params(jax.random.PRNGKey(0), cfg), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def served(model):
    """Five requests, greedy and sampled, through two slots."""
    cfg, params = model
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, seed=0)
    rng = np.random.default_rng(3)
    reqs = [
        Request(
            prompt=rng.integers(0, cfg.vocab_size, size=(4 + i,)).astype(np.int32),
            max_new_tokens=2 + i,
            temperature=0.7 if i % 2 else 0.0,
        )
        for i in range(5)
    ]
    done = eng.run(reqs)
    return eng.last_events, done


def _spans(events, kind=None):
    return [e for e in events if "parent" in e and kind in (None, e["kind"])]


def _children(events, parent, kind):
    idx = events.index(parent)
    return [e for e in _spans(events, kind) if e["parent"] == idx]


def test_every_span_is_closed_and_lies_inside_its_parent(served):
    events, _ = served
    assert _spans(events, "admit") and _spans(events, "tick")
    for e in _spans(events):
        assert "end" in e and e["t"] <= e["end"], e
        if e["parent"] is not None:
            p = events[e["parent"]]
            assert "parent" in p, "a span's parent is a span"
            assert p["t"] <= e["t"] and e["end"] <= p["end"], (p, e)


@pytest.mark.parametrize("kind", ADMIT_CHILDREN)
def test_each_admission_has_one_child_of_each_kind_with_its_rid(served, kind):
    events, done = served
    admits = _spans(events, "admit")
    assert {a["rid"] for a in admits} == {r.rid for r in done}
    for a in admits:
        kids = _children(events, a, kind)
        assert len(kids) == 1, (a, kids)
        assert kids[0]["rid"] == a["rid"]
        assert a["parent"] is None


@pytest.mark.parametrize("kind", TICK_CHILDREN)
def test_each_tick_has_one_child_of_each_kind(served, kind):
    events, _ = served
    ticks = _spans(events, "tick")
    assert [t["tick"] for t in ticks] == list(range(len(ticks)))
    for t in ticks:
        assert len(_children(events, t, kind)) == 1


def test_each_finished_slot_is_reset_inside_a_tick(served):
    events, done = served
    finishes = _spans(events, "finish")
    assert sorted(f["rid"] for f in finishes) == sorted(r.rid for r in done)
    for f in finishes:
        assert events[f["parent"]]["kind"] == "tick"


def test_spans_are_per_tick_and_per_request_not_per_slot(served):
    events, done = served
    n_ticks = len(_spans(events, "tick"))
    want = (1 + len(ADMIT_CHILDREN)) * len(done) + (1 + len(TICK_CHILDREN)) * n_ticks + len(done)
    assert len(_spans(events)) == want


def test_decode_tick_is_stamped_with_its_tokens(served):
    events, done = served
    by_rid = {r.rid: r for r in done}
    ticks = [e for e in events if e["kind"] == "decode_tick"]
    assert ticks
    for e in ticks:
        assert "parent" not in e  # still a point event
        for rid in filter(lambda r: r is not None, e["rids"]):
            assert e["t"] in by_rid[rid].token_times
    fetches = {events[f["parent"]]["tick"]: f for f in _spans(events, "fetch")
               if f["parent"] is not None and events[f["parent"]]["kind"] == "tick"}
    for n, e in enumerate(ticks):
        assert fetches[n]["end"] <= e["t"]


def test_admit_keeps_its_stamp(served):
    events, done = served
    by_rid = {r.rid: r for r in done}
    for a in _spans(events, "admit"):
        r = by_rid[a["rid"]]
        assert a["t"] == r.t_admitted
        assert a["t"] <= r.t_first_token <= a["end"]


def test_spans_land_on_the_profilers_host_plane(model, tmp_path):
    from jax.profiler import ProfileData

    cfg, params = model
    eng = ServeEngine(cfg, params, batch_slots=2, max_len=48, seed=0)
    prompts = [np.arange(5 + i, dtype=np.int32) % cfg.vocab_size for i in range(2)]
    eng.run([Request(prompt=p, max_new_tokens=3) for p in prompts])  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        eng.run([Request(prompt=p, max_new_tokens=3) for p in prompts])
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    names = {
        ev.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }
    kinds = {"admit", "tick", "finish"} | set(ADMIT_CHILDREN) | set(TICK_CHILDREN)
    assert {f"serve.{k}" for k in kinds} <= names


@pytest.fixture(scope="module")
def decode_text(model):
    cfg, params = model
    cache = Z.init_cache(2, 48, cfg)
    tokens = jnp.zeros((2,), jnp.int32)
    lowered = jax.jit(lambda p, t, c: Z.decode_step(p, t, cfg, c)).lower(params, tokens, cache)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_decode_step_names_the_scope(decode_text, scope):
    # a location's name is its scope path: "jit(<lambda>)/head/...", and
    # inside the layer scan's body "attn.core/attn.qk/..."
    assert re.search(rf'loc\("(?:[^"]*/)?{re.escape(scope)}/', decode_text)

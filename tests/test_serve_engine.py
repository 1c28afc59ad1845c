"""Serving engine + dry-run helper units (fast, no big compiles)."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import SHAPES_BY_NAME
from repro.configs.smoke import smoke_variant
from repro.models import model_zoo as Z
from repro.runtime.serve_loop import Request, ServeEngine, serve_sequential


@pytest.fixture(scope="module")
def engine():
    cfg = smoke_variant(get_config("granite-8b"))
    params = Z.init_params(jax.random.PRNGKey(0), cfg)
    serving = Z.prepare_serving_params(params, cfg)
    return cfg, ServeEngine(cfg, serving, batch_slots=2, max_len=48, seed=0)


def _mixed_requests(cfg, n=5, seed=0, max_new=(3, 7), plen=(3, 11)):
    rng = np.random.default_rng(seed)
    return [
        Request(
            prompt=rng.integers(
                0, cfg.vocab_size, size=(int(rng.integers(*plen)),)
            ).astype(np.int32),
            max_new_tokens=int(rng.integers(*max_new)),
        )
        for _ in range(n)
    ]


def test_engine_serves_a_queue(engine):
    cfg, eng = engine
    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab_size, size=(5 + i,)).astype(np.int32),
                max_new_tokens=6)
        for i in range(5)  # 5 requests through 2 slots -> 3 waves
    ]
    done = eng.run(reqs)
    assert len(done) == 5
    assert all(len(r.output) == 6 for r in done)
    assert all(0 <= t < cfg.vocab_size for r in done for t in r.output)


def test_greedy_is_deterministic(engine):
    cfg, eng = engine
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    a = eng.run([Request(prompt=prompt, max_new_tokens=5)])[0].output
    b = eng.run([Request(prompt=prompt, max_new_tokens=5)])[0].output
    assert a == b


def test_temperature_sampling_varies(engine):
    cfg, eng = engine
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    outs = {
        tuple(eng.run([Request(prompt=prompt, max_new_tokens=8, temperature=1.5)])[0].output)
        for _ in range(3)
    }
    assert len(outs) > 1  # overwhelmingly likely with T=1.5


# ---------------------------------------------------------------------------
# differential: continuous batching vs the one-request-at-a-time oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["granite-8b", "mamba2-130m"])
def test_differential_greedy_matches_oracle(arch):
    """THE serving-correctness guarantee: slot-managed continuous batching
    (mixed-length requests co-scheduled in one packed decode batch) produces
    exactly the tokens the naive sequential loop produces — scheduling is
    numerically invisible (per-row cache state + per-token quantization)."""
    cfg = smoke_variant(get_config(arch))
    params = Z.init_params(jax.random.PRNGKey(0), cfg)
    serving = Z.prepare_serving_params(params, cfg)
    reqs = _mixed_requests(cfg, n=5, seed=42)
    oracle = serve_sequential(
        cfg, serving, _mixed_requests(cfg, n=5, seed=42), max_len=48, seed=0
    )
    eng = ServeEngine(cfg, serving, batch_slots=2, max_len=48, seed=0)
    done = eng.run(reqs)
    for got, want in zip(done, oracle):
        assert got.output == want.output, (
            f"{arch}: engine diverged from oracle "
            f"(prompt_len={len(got.prompt)}): {got.output} != {want.output}"
        )


def test_differential_invariant_to_arrivals(engine):
    """Outputs must not depend on WHEN requests arrive (open-loop traffic):
    staggered admission only changes the schedule, never the tokens."""
    cfg, eng = engine
    a = eng.run(_mixed_requests(cfg, n=4, seed=7))
    staggered = _mixed_requests(cfg, n=4, seed=7)
    for i, r in enumerate(staggered):
        r.arrival_s = 0.05 * i
    b = eng.run(staggered)
    assert [r.output for r in a] == [r.output for r in b]


def test_streaming_callbacks_and_timing(engine):
    cfg, eng = engine
    seen = []
    reqs = _mixed_requests(cfg, n=3, seed=3)
    for i, r in enumerate(reqs):
        r.on_token = lambda tok, i=i: seen.append((i, tok))
    done = eng.run(reqs)
    for i, r in enumerate(done):
        assert [t for j, t in seen if j == i] == r.output  # streamed == final
        assert len(r.token_times) == r.max_new_tokens
        assert r.t_admitted is not None and r.t_first_token is not None
        assert r.t_admitted <= r.t_first_token <= r.t_finished
        assert r.token_times == sorted(r.token_times)


def test_request_validation(engine):
    cfg, eng = engine
    big = Request(prompt=np.zeros((40,), np.int32), max_new_tokens=20)  # 60 > 48
    with pytest.raises(ValueError):
        eng.run([big])
    empty = Request(prompt=np.zeros((0,), np.int32), max_new_tokens=2)
    with pytest.raises(ValueError):
        eng.run([empty])


def test_event_trace_records_slot_lifecycle(engine):
    cfg, eng = engine
    done = eng.run(_mixed_requests(cfg, n=3, seed=9))
    kinds = [e["kind"] for e in eng.last_events]
    for k in ("admit", "prefill", "insert", "decode_tick", "finish", "reset"):
        assert k in kinds
    admits = [e for e in eng.last_events if e["kind"] == "admit"]
    finishes = [e for e in eng.last_events if e["kind"] == "finish"]
    assert {e["rid"] for e in admits} == {r.rid for r in done}
    assert {e["rid"] for e in finishes} == {r.rid for r in done}


# ---------------------------------------------------------------------------
# dry-run helper units
# ---------------------------------------------------------------------------


def test_collective_byte_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
      %ag = f32[128,256]{1,0} all-gather(%x), replica_groups={}
      %ar.1 = bf16[64]{0} all-reduce(%y), to_apply=%sum
      %nothing = f32[2,2]{1,0} add(%a, %b)
      %aa = (s8[16,16]{1,0}, s8[16,16]{1,0}) all-to-all(%p, %q)
    """
    out = collective_bytes(hlo)
    assert out["all-gather"]["bytes"] == 128 * 256 * 4
    assert out["all-gather"]["count"] == 1
    assert out["all-reduce"]["bytes"] == 64 * 2
    assert out["all-to-all"]["bytes"] == 2 * 16 * 16
    assert out["total_bytes"] == 128 * 256 * 4 + 128 + 512


def test_skip_rules_match_design_doc():
    from repro.launch.dryrun import skip_reason

    long = SHAPES_BY_NAME["long_500k"]
    assert skip_reason(get_config("mistral-nemo-12b"), long)  # full attention
    assert skip_reason(get_config("gemma3-27b"), long)  # has global layers
    assert skip_reason(get_config("mamba2-130m"), long) is None  # SSM runs
    assert skip_reason(get_config("recurrentgemma-2b"), long) is None  # hybrid runs
    train = SHAPES_BY_NAME["train_4k"]
    for arch in ("granite-8b", "deepseek-v3-671b", "whisper-tiny"):
        assert skip_reason(get_config(arch), train) is None


def test_input_specs_cover_frontends():
    from repro.launch.dryrun import input_specs

    shape = SHAPES_BY_NAME["train_4k"]
    s1 = input_specs(get_config("granite-8b"), shape)
    assert set(s1) == {"tokens"} and s1["tokens"].shape == (256, 4096)
    s2 = input_specs(get_config("whisper-tiny"), shape)
    assert s2["frontend"].shape == (256, 1500, 384)
    s3 = input_specs(get_config("internvl2-2b"), shape)
    assert s3["frontend"].shape == (256, 256, 1024)


def test_opt_transforms_apply():
    from repro.launch.dryrun import apply_opts

    cfg = get_config("granite-8b")
    out = apply_opts(cfg, ["packed_gather"])
    assert out.quant.prebinarize_gather
    assert not cfg.quant.prebinarize_gather
    assert dataclasses.replace(out, quant=cfg.quant) == cfg  # nothing else moved
    assert apply_opts(cfg, ["accum4"]) == cfg  # accumulation is run_cell's
    with pytest.raises(ValueError, match="unknown opt 'gqa_expand'"):
        apply_opts(cfg, ["gqa_expand"])

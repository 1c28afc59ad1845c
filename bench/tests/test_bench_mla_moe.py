"""The MLA + routed-expert family (deepseek-v2-lite) at a smoke size on the
CPU: the plain reference against the program's prefill and decode through
``ServeEngine``'s cache, its weights and independence, the control, the
counts of a decode tick, and the two readers of the grouped expert kernel.

The smoke model is deepseek-v2-lite's configuration file with its sizes cut
to one dense layer and two MoE layers of 8 experts (top-2, 2 shared), d 64,
YaRN on; the program's side is the registered config at the same sizes.
"""

import ast
import dataclasses
import functools
import json
import time

import jax
import numpy as np
import pytest

from bench import harness
from bench import trace as T
from bench import window as W
from bench.spec import ROOT, Cell, load_module
from bench.tests.kit import add_cell, copy_bench, tiny_mix

REF = load_module(ROOT / "bench" / "reference" / "mla_moe.py")
FAMILY = load_module(ROOT / "bench" / "families" / "mla_moe.py")
FILE = json.loads((ROOT / "bench" / "configs" / "deepseek-v2-lite-16b.json").read_text())
TINY_NAME = "deepseek-v2-lite-16b-tiny"
TINY = {
    # the program's keys (harness.ARCH_KEYS) ...
    "n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_head": 16, "d_ff": 128, "vocab_size": 256,
    # ... and the published ones the family and the reference read
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 128, "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "n_shared_experts": 2, "max_len": 64,
}
# Readings of the tiny cell on the CPU (seeds 2**31 + 107 to 109):
# max_logit_err 0.36, 0.14, 0.35 for the program (router swaps, see below),
# 1.62-1.86 for the control (4-bit activations); max_logit_gap 0.005, 0, 0
# for the program, 0.11-0.22 for the control, and about the logits' whole
# range for a token altered to the least likely one.
TINY_LIMITS = {"max_logit_err": 0.8, "max_logit_gap": 0.05}


def tiny_config(**limits):
    """The configuration file at the smoke size, and its program config
    registered under :data:`TINY_NAME` (smoke widths, three layers)."""
    from repro.configs import get_config
    from repro.configs.base import _REGISTRY, register
    from repro.configs.smoke import smoke_variant

    if TINY_NAME not in _REGISTRY:
        cfg = smoke_variant(get_config("deepseek-v2-lite-16b"))
        register(dataclasses.replace(cfg, name=TINY_NAME, n_layers=3))
    c = dict(FILE, registry_name=TINY_NAME, **TINY)
    c["limits"] = dict(FILE["limits"], **limits)
    return c


def served(c, seed, prompts, n_new, slots=2):
    """Greedy requests through ``ServeEngine``; each request's tokens and the
    logits they were chosen from."""
    from repro.models import model_zoo as Z
    from repro.runtime import serve_loop
    from repro.runtime.serve_loop import Request, ServeEngine

    cfg = harness.program_config(c)
    params = Z.init_serving_params(jax.random.PRNGKey(seed), cfg)
    pending, rows = [None], [[] for _ in prompts]
    orig = serve_loop._sample

    def sample(logits, temperature, rng):
        pending[0] = np.array(logits)
        return orig(logits, temperature, rng)

    reqs = [
        Request(prompt=p, max_new_tokens=n_new, on_token=lambda t, i=i: rows[i].append(pending[0]))
        for i, p in enumerate(prompts)
    ]
    serve_loop._sample = sample
    try:
        ServeEngine(cfg, params, batch_slots=slots, max_len=c["max_len"], seed=seed).run(reqs)
    finally:
        serve_loop._sample = orig
    return [(r.prompt, r.output) for r in reqs], [np.stack(x) for x in rows]


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_reference_follows_prefill_and_decode_through_the_engine(seed):
    """Three requests, two slots: prefill, the insert into the engine's
    packed latent cache, and decode beside another slot; each served
    position's largest logit difference over the reference's spread there
    (``check.readings``' ``max_logit_err``).

    The program keeps its residual stream in bf16 and rounds every
    projection's output to bf16 (2^-8 relative per rounding).  Its router
    reads that bf16 stream, the reference's the float32 one, and at this
    size a token's 2nd and 3rd of 8 router scores often lie close: of the
    98 token-layers of a seed (2 MoE layers, 49 positions), the program's
    and the reference's top-2 sets differed at 3, 2, 2 and 1 (seeds 3, 5,
    9, 2**31 + 9).  A swap moves that layer's output at that token by the
    swapped experts' difference, so the readings spread wider than a dense
    model's: 0.10-0.34 over ten seeds (3, 5, 9, 11-16, 2**31 + 9), against
    0.82-1.18 for the reference at 4-bit activations (the control).  0.5
    lies between them with room on both sides."""
    c = tiny_config()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, c["vocab_size"], n).astype(np.int32) for n in (12, 7, 15)]
    seqs, prog = served(c, seed, prompts, 6)
    logits = REF.served_logits(c, seed, seqs, widths=(8, 4))
    for ref, low, mine in zip(logits[8], logits[4], prog):
        scale = ref.std(-1, keepdims=True)
        assert (np.abs(mine - ref) / scale).max() < 0.5
        assert (np.abs(low - ref) / scale).max() > 0.5


def test_weights_are_the_programs_recipe():
    """Same seed, same binarized matrices as the program packs: an expert of
    the second MoE layer, the shared experts, the latent projection, and the
    router as it is (fp32, not binarized)."""
    from repro.core import quantization as Q
    from repro.models import model_zoo as Z

    c = tiny_config()
    _, _, keys = REF._keys(c, 5)
    w = REF._layer_weights(REF._dims(c), True, keys[2])
    cfg = harness.program_config(c)
    period = Z.init_params(jax.random.PRNGKey(5), cfg)["stack"]["period"][0]
    layer = jax.tree.map(lambda a: a[1], period)  # the second MoE layer
    want = Q.binarize_weight(layer["moe"]["down"]["w"][3]).dequantize()
    np.testing.assert_allclose(np.asarray(w["experts"]["down"][3]), np.asarray(want), rtol=1e-6)
    want = Q.binarize_weight(layer["moe"]["shared"]["gate"]["w"]).dequantize()
    np.testing.assert_allclose(np.asarray(w["shared"]["gate"]), np.asarray(want), rtol=1e-6)
    want = Q.binarize_weight(layer["attn"]["kv_down"]["w"]).dequantize()
    np.testing.assert_allclose(np.asarray(w["kv_down"]), np.asarray(want), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(w["router"]), np.asarray(layer["moe"]["router"]["w"]))


def test_reference_imports_nothing_of_the_program():
    tree = ast.parse((ROOT / "bench" / "reference" / "mla_moe.py").read_text())
    for node in ast.walk(tree):
        names = [a.name for a in node.names] if isinstance(node, ast.Import) else (
            [node.module or ""] if isinstance(node, ast.ImportFrom) else [])
        assert not any(n.startswith(("repro", "bench")) for n in names), names


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory):
    root = copy_bench(tmp_path_factory.mktemp("bench"))
    mix = tiny_mix(arrival={"kind": "backlog", "n_requests": 6}, grace_s=0.0, prompt_pool=[8, 12],
                   output={"median": 5, "sigma": 0.3, "clip": [3, 8]})
    return Cell(add_cell(root, tiny_config(**TINY_LIMITS), mix, name="tinymoe"), root)


def test_run_is_correct_and_the_control_is_not(tiny_cell):
    """A whole run of the tiny cell through the harness: the program is
    correct; the reference at 4-bit activations in its place, and a served
    token altered to the least likely one, are not."""
    details = {}
    res = harness.run(tiny_cell, 2**31 + 107, 4.0, False, time.perf_counter(), require_tpu=False,
                      control_bits=4, details=details)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["checks"]["tokens_compared"]["value"] >= 1
    control = details["verdict"]["control"]
    assert control["correct"] is False
    assert control["checks"]["max_logit_err"]["value"] > control["checks"]["max_logit_err"]["limit"]
    assert details["verdict"]["altered_token"]["correct"] is False


# ---------------------------------------------------------------------------
# the counts of a decode tick, by hand for deepseek-v2-lite
# ---------------------------------------------------------------------------

# attention of one layer: q 2048x3072, kv_down 2048x512, k_rope 2048x64,
# k_up 512x2048, v_up 512x2048, o 2048x2048
ATTN_MACS = 2048 * 3072 + 2048 * 512 + 2048 * 64 + 2 * 512 * 2048 + 2048 * 2048
EXPERT_MACS = 3 * 2048 * 1408
DENSE_MACS = 3 * 2048 * 10944
SHARED_MACS = 3 * 2048 * 2816
ROUTER_MACS = 2048 * 64
EXPERT_WORDS = EXPERT_MACS // 8  # bytes of one expert's packed words


def test_tick_by_hand():
    assert FAMILY.token_macs(FILE) == (
        27 * ATTN_MACS + DENSE_MACS + 26 * (6 * EXPERT_MACS + SHARED_MACS + ROUTER_MACS)
    )
    assert FAMILY.cache_bytes_per_token(FILE) == 27 * (512 + 2 * 64) == 17_280
    live = [100, 2000]
    ops, nbytes = FAMILY.decode_need(FILE, live)
    per_cached = 16 * (2 * 512 + 64)  # latent QK, rope QK, latent PV
    assert ops == sum(2 * (FAMILY.token_macs(FILE) + 27 * per_cached * n + 102400 * 2048) for n in live)
    scales = lambda *ns: 4 * sum(ns)  # noqa: E731 — one fp32 scale per output channel
    attn_bytes = ATTN_MACS // 8 + scales(3072, 512, 64, 2048, 2048, 2048)
    weights = (
        27 * attn_bytes
        + DENSE_MACS // 8 + scales(10944, 10944, 2048)
        + 26 * (6 * (EXPERT_WORDS + scales(1408, 1408, 2048)) + SHARED_MACS // 8
                + scales(2816, 2816, 2048) + 4 * ROUTER_MACS)
        + 102400 * 2048 * 2
    )
    assert nbytes == weights + 17_280 * 2100


def test_expert_need_by_hand():
    ops, nbytes = FAMILY.expert_need(FILE, [10] * 16)
    assert ops == 2 * 26 * 16 * 6 * EXPERT_MACS
    assert nbytes == 26 * 6 * EXPERT_WORDS  # 216 MB: the least any routing reads
    assert FAMILY.expert_need(FILE, []) == (0, 0)
    assert FAMILY.decode_need(FILE, []) == (0, 0)


# ---------------------------------------------------------------------------
# the readers of the grouped expert kernel
# ---------------------------------------------------------------------------


def _run(top_ops, executions=4):
    summary = T.Summary(
        window_s=1.0, busy_s=0.5, program_ns={"jit__decode": [10_000_000] * executions},
        top_ops=top_ops, gaps=[], devices=1,
    )
    ticks = [W.Tick(t=0.1 * i, live=[300] * 16) for i in range(1, 5)]
    return W.Run(
        seconds=1.0, open=0.0, close=1.0, grace_s=0.0, setup_s=1.0, requests=[], ticks=ticks,
        compile_events=[], need=functools.partial(FAMILY.decode_need, FILE),
        peak={"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}, trace=summary,
    )


def test_expert_readers():
    ms = load_module(ROOT / "bench" / "metrics" / "decode_expert_ms.py")
    roof = load_module(ROOT / "bench" / "metrics" / "decode_expert_roofline.py")
    run = _run([("while", 0.03), ("expert_decode_qmm", 0.008)])
    assert ms.read(run) == pytest.approx(2.0)  # 8 ms over 4 executions
    ops, nbytes = FAMILY.expert_need(FILE, [300] * 16)
    least = max(ops / 393e12, nbytes / 819e9)
    assert roof.read(run) == pytest.approx(100 * least / 2e-3)
    absent = _run([("while", 0.03), ("decode_qmm", 0.001)])  # a program without the kernel
    assert ms.read(absent) is None and roof.read(absent) is None

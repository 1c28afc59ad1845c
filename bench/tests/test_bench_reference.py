"""The plain reference against the program's prefill and decode logits at a
smoke size, on the CPU; and its independence from the program."""

import ast

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.spec import ROOT, load_module
from bench.tests.kit import tiny_config

REF = load_module(ROOT / "bench" / "reference" / "dense_gqa.py")


def program_logits(c, seed, prompt, n_new):
    """Prefill, then greedy decode through the cache: (n_new, vocab) logits."""
    from repro.models import model_zoo as Z

    cfg = harness.program_config(c)
    params = Z.init_serving_params(jax.random.PRNGKey(seed), cfg)
    logits, cache = Z.prefill(params, jnp.asarray(prompt[None]), cfg, Z.init_slot_cache(c["max_len"], cfg))
    rows = [np.asarray(logits[0])]
    step = jax.jit(lambda p, t, cc: Z.decode_step(p, t, cfg, cc))
    while len(rows) < n_new:
        logits, cache = step(params, jnp.asarray([rows[-1].argmax()], jnp.int32), cache)
        rows.append(np.asarray(logits[0]))
    return np.stack(rows)


@pytest.mark.parametrize("seed", [3, 2**31 + 9])
def test_reference_follows_prefill_and_decode(seed):
    c = tiny_config()
    prompt = np.random.default_rng(seed).integers(0, c["vocab_size"], 12).astype(np.int32)
    prog = program_logits(c, seed, prompt, 7)
    served = list(prog.argmax(-1))
    logits = REF.served_logits(c, seed, [(prompt, served)], widths=(8, 4))
    ref, low = logits[8][0], logits[4][0]
    scale = ref.std()
    # The program keeps its residual stream in bf16 and rounds every
    # projection's output to bf16 (2^-8 relative per rounding): measured
    # 0.07-0.09 of the logits' spread at this size.  Activations at 4 bits
    # (the control) move them by about one spread.
    assert np.abs(prog - ref).max() < 0.25 * scale
    assert np.abs(low - ref).max() > 0.5 * scale
    assert (ref.argmax(-1) == prog.argmax(-1)).all()


def test_weights_are_the_programs_recipe():
    """Same seed, same binarized matrix as the program packs (sign and scale)."""
    from repro.core import quantization as Q

    c = tiny_config()
    _, _, layer_keys = REF._keys(c, 5)
    dims = (c["d_model"], c["n_heads"], c["n_kv_heads"], c["d_head"], c["d_ff"])
    w = REF._layer_weights(dims, layer_keys[1])["down"]
    cfg = harness.program_config(c)
    from repro.models import model_zoo as Z

    latent = Z.init_params(jax.random.PRNGKey(5), cfg)["stack"]["period"][0]["ffn"]["down"]["w"][1]
    want = Q.binarize_weight(latent).dequantize()
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-6)


def test_reference_imports_nothing_of_the_program():
    for path in (ROOT / "bench" / "reference").glob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            assert not any(n.startswith(("repro", "bench")) for n in names), (path, names)

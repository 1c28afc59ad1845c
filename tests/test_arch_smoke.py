"""Per-architecture smoke tests (assignment requirement).

For each assigned arch: instantiate the REDUCED config of the same family
(configs/smoke.py), run one forward/train step and one prefill+decode step
on CPU, assert output shapes and no NaNs.  The FULL configs are exercised
via the dry-run only.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED, get_config
from repro.configs.smoke import smoke_variant
from repro.models import model_zoo as Z

ALL = ASSIGNED + ("bit-bert-base",)


def _batch(cfg, b=2, s=16):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    if cfg.encoder is not None:
        d_in = cfg.encoder.d_input or cfg.d_model
        batch["frontend"] = jax.random.normal(
            jax.random.PRNGKey(2), (b, cfg.encoder.n_positions, d_in), jnp.float32
        )
    return batch


@pytest.fixture(scope="module")
def built():
    """Init each smoke model once per test session."""
    cache = {}

    def get(name):
        if name not in cache:
            cfg = smoke_variant(get_config(name))
            params = Z.init_params(jax.random.PRNGKey(0), cfg)
            cache[name] = (cfg, params)
        return cache[name]

    return get


@pytest.mark.parametrize("name", ALL)
def test_train_step_shapes_and_finite(built, name):
    cfg, params = built(name)
    batch = _batch(cfg)
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: Z.loss_fn(p, batch, cfg, "train"), has_aux=True
    )(params)
    assert jnp.isfinite(loss), f"{name}: non-finite loss"
    # gradients exist and are finite for latent weights
    leaves = jax.tree.leaves(grads)
    assert leaves, "no gradients"
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves), f"{name}: NaN grads"


@pytest.mark.parametrize("name", ALL)
def test_forward_logits_shape(built, name):
    cfg, params = built(name)
    batch = _batch(cfg)
    logits, aux = Z.forward_logits(
        params, batch["tokens"], cfg, "train", batch.get("frontend")
    )
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))


@pytest.mark.parametrize("name", ALL)
def test_serve_prefill_decode(built, name):
    cfg, params = built(name)
    if not cfg.has_decoder and cfg.family == "encoder":
        pytest.skip("encoder-only: no decode step (assignment rule)")
    batch = _batch(cfg)
    sp = Z.prepare_serving_params(params, cfg)
    cache = Z.init_cache(2, 32, cfg)
    logits, cache = Z.prefill(sp, batch["tokens"], cfg, cache, batch.get("frontend"))
    assert logits.shape == (2, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache2 = Z.decode_step(sp, nxt, cfg, cache)
    assert logits2.shape == (2, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits2)))


@pytest.mark.parametrize("name", ["granite-8b", "qwen3-32b", "mamba2-130m"])
def test_serve_decode_matches_full_forward(built, name):
    """Decode-with-cache must agree with full-sequence forward (float mode,
    no quantization noise): the cache machinery itself is exact."""
    import dataclasses

    from repro.configs.base import FLOAT_QUANT

    cfg, _ = built(name)
    cfg = dataclasses.replace(cfg, quant=FLOAT_QUANT, name=cfg.name + "-fp")
    params = Z.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    full_logits, _ = Z.forward_logits(params, tokens, cfg, "float")
    cache = Z.init_cache(1, 16, cfg)
    _, cache = Z.prefill(params, tokens[:, :-1], cfg, cache)
    step_logits, _ = Z.decode_step(params, tokens[:, -1], cfg, cache)
    import numpy as np

    np.testing.assert_allclose(
        np.asarray(step_logits[0]),
        np.asarray(full_logits[0, -1]),
        rtol=2e-2,
        atol=2e-2,
    )


@pytest.mark.parametrize("name", ["granite-8b"])
def test_quantized_serve_close_to_float(built, name):
    """W1A8 serving must track the QAT (fake-quant) forward: same weights,
    integer vs float datapath."""
    cfg, params = built(name)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 12), 0, cfg.vocab_size)
    train_logits, _ = Z.forward_logits(params, tokens, cfg, "train")
    sp = Z.prepare_serving_params(params, cfg)
    cache = Z.init_cache(1, 16, cfg)
    serve_logits, _ = Z.prefill(sp, tokens, cfg, cache)
    t = jnp.argsort(train_logits[0, -1])[-5:]
    s = jnp.argsort(serve_logits[0])[-5:]
    # datapaths differ in quantizer granularity; demand ranking overlap
    overlap = len(set(map(int, t)) & set(map(int, s)))
    assert overlap >= 2, f"serve/train top-5 overlap too low: {overlap}"


@pytest.mark.parametrize(
    "name", ["granite-8b", "deepseek-v2-lite-16b", "whisper-tiny", "mamba2-130m"]
)
def test_init_serving_params_matches_two_step_path(name):
    """Packing each layer as it is initialised gives, bit for bit, what
    packing the whole latent model gives (both under jit)."""
    import numpy as np

    cfg = smoke_variant(get_config(name))
    key = jax.random.PRNGKey(7)
    want = jax.jit(lambda k: Z.prepare_serving_params(Z.init_params(k, cfg), cfg))(key)
    got = Z.init_serving_params(key, cfg)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

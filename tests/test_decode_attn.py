"""Decode attention in one kernel: ``ops.decode_attn`` (``kernels/decode_attn.py``).

On a TPU, a single-token decode over a global int8 GQA cache runs in the
``decode_attn`` kernel instead of the XLA chain ``_scores_int`` -> mask ->
softmax -> ``_pv_int``.  The kernel does the same arithmetic over each slot's
live rows only, so it must agree with the chain up to float32 rounding, and
leave nothing of the rows past a slot's position in its result.  The
engagement is a predicate on the cache and shapes
(``attention.decode_core_engages``), and the ``attn.qk`` site record names
the core that ran.  On the CPU the kernel runs interpreted; the tests force
the platform check (``ops.on_tpu``) where they need the TPU's choice.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config
from repro.configs.smoke import smoke_variant
from repro.core import quantization as Q
from repro.core import site_log
from repro.kernels import decode_attn as DA
from repro.kernels import ops
from repro.models import attention as A
from repro.models import model_zoo as Z
from repro.runtime.serve_loop import Request, ServeEngine

# granite-8b's and mistral-nemo-12b's heads: 32 query heads over 8 kv heads of 128
H, KVH, DH = 32, 8, 128


@pytest.fixture
def tpu_choice(monkeypatch):
    """Make the engine choose as on a TPU (kernels still interpreted)."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        yield


def _slots(seed, b, t):
    """q, a cache full of nonzero int8 rows, and per-slot affines."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    u = lambda i, lo, hi: jax.random.uniform(ks[i], (b,), minval=lo, maxval=hi)  # noqa: E731
    return dict(
        q=jax.random.normal(ks[0], (b, 1, H, DH)),
        k=jax.random.randint(ks[1], (b, t, KVH, DH), -128, 128).astype(jnp.int8),
        v=jax.random.randint(ks[2], (b, t, KVH, DH), -128, 128).astype(jnp.int8),
        k_scale=u(3, 0.005, 0.02), k_offset=u(4, -1.5, -0.5),
        v_scale=u(5, 0.005, 0.02), v_offset=u(6, -1.5, -0.5),
    )


def _xla_chain(c, pos):
    """Today's decode branch: ``_scores_int`` -> mask -> softmax -> ``_pv_int``."""
    t = c["k"].shape[1]
    scores = A._scores_int(c["q"], c["k"], c["k_scale"], c["k_offset"], 8)
    scores = scores / jnp.sqrt(jnp.float32(DH))
    valid = jnp.arange(t)[None, :] <= pos[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, A._NEG_INF)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
    return A._pv_int(probs, c["v"], c["v_scale"], c["v_offset"])[:, 0]


def _kernel(c, pos, block_t):
    qr = Q.recenter(Q.quantize_activation(c["q"].astype(jnp.float32), 8, per_channel_axis=0))
    return ops.decode_attn(
        qr.mantissa[:, 0], qr.scale, qr.offset, c["k"], c["v"],
        c["k_scale"], c["k_offset"], c["v_scale"], c["v_offset"], pos,
        block_t=block_t, interpret=True,
    )


def _positions(t, bt):
    """A fresh slot, both sides of the first block boundary, the last row."""
    return jnp.asarray([0, bt - 1, bt, bt + 1, t - 1], jnp.int32)


@pytest.mark.parametrize("t,bt", [(256, 64), (512, 128), (512, 256)])
def test_kernel_equals_xla_chain(t, bt):
    """The context agrees with the XLA chain's over every slot position.

    Tolerance: the two softmaxes sum in different orders, so ``p`` differs
    in its last float32 bits, and where ``255 p`` lies that close to a half
    the probability rounds to the neighbouring step of the 1/255 grid; each
    such row moves a context entry by at most max|V| / 255.  Two such steps
    bound the error; everything else is float32 rounding of terms of the
    size of the context (1e-4 of its largest entry)."""
    c = _slots(t + bt, 5, t)
    pos = _positions(t, bt)
    out = np.asarray(_kernel(c, pos, bt))
    ref = np.asarray(_xla_chain(c, pos))
    v_max = float(jnp.max(jnp.abs(
        A._dequantize_from_cache(c["v"], c["v_scale"], c["v_offset"], jnp.float32)
    )))
    err = np.abs(out - ref)
    assert err.max() <= 2 * v_max / 255.0, err.max()
    assert np.quantile(err, 0.99) <= 1e-4 * np.abs(ref).max()
    # greedy decoding over a small head reads the same token from both
    head = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (H * DH, 97)))
    b = out.shape[0]
    np.testing.assert_array_equal(
        np.argmax(out.reshape(b, -1) @ head, -1), np.argmax(ref.reshape(b, -1) @ head, -1)
    )


@pytest.mark.parametrize("t,bt", [(256, 64), (512, 256)])
def test_rows_past_position_do_not_leak(t, bt):
    """Zeroing every row past each slot's position leaves the kernel's result
    bit for bit as it was: those rows are never part of it."""
    c = _slots(3 * t + bt, 5, t)
    pos = _positions(t, bt)
    live = (jnp.arange(t)[None, :] <= pos[:, None])[:, :, None, None]
    fresh = dict(c, k=jnp.where(live, c["k"], 0), v=jnp.where(live, c["v"], 0))
    np.testing.assert_array_equal(
        np.asarray(_kernel(c, pos, bt)), np.asarray(_kernel(fresh, pos, bt))
    )


def test_block_fit():
    """granite's 4096 rows take blocks of 512 within the VMEM budget; a cache
    that holds a slot's scores and V rows beyond it keeps the XLA chain."""
    assert DA.block_rows(4096) == 512 and DA.block_rows(256) == 256
    assert DA.fits(4096, KVH, DH, H)
    assert DA.vmem_bytes(4096, KVH, DH, H) == 11 << 20
    assert not DA.fits(32768, KVH, DH, H)
    assert not DA.fits(4000, KVH, DH, H)  # no whole blocks


# --- engagement ------------------------------------------------------------


def _granite_heads(**changes):
    """granite-8b's smoke variant with granite's own heads (32/8 of 128)."""
    cfg = smoke_variant(get_config("granite-8b"))
    return dataclasses.replace(cfg, n_heads=H, n_kv_heads=KVH, d_head=DH, **changes)


def _qk_cores(cfg, phase, max_len=256, batch=4):
    """``int_core`` of every attention site record of one traced phase."""
    sds = jax.ShapeDtypeStruct
    sp = jax.eval_shape(lambda k: Z.init_serving_params(k, cfg), sds((2,), jnp.uint32))
    cache = jax.eval_shape(lambda: Z.init_cache(batch, max_len, cfg))
    if phase == "decode":
        fn, tokens = Z.decode_step, sds((batch,), jnp.int32)
    else:
        fn, tokens = Z.prefill, sds((batch, 16), jnp.int32)
    with site_log.recording() as sites:
        jax.eval_shape(lambda p, t, c: fn(p, t, cfg, c), sp, tokens, cache)
    return [(s["site"], s["int_core"]) for s in sites if s["kind"] == "attn"]


def test_granite_decode_records_the_kernel(tpu_choice):
    assert _qk_cores(_granite_heads(), "decode") == [("attn.qk", "decode_attn")]


def test_off_tpu_decode_keeps_the_xla_chain():
    assert _qk_cores(_granite_heads(), "decode") == [("attn.qk", "xla")]


def test_prefill_keeps_the_xla_chain(tpu_choice):
    assert _qk_cores(_granite_heads(), "prefill") == [("attn.qk", "xla")]


def test_ring_buffer_local_layer_keeps_the_xla_chain(tpu_choice):
    """A local layer's ring buffer keeps the chain; the global layer beside
    it, over a cache of the same model, takes the kernel."""
    cfg = _granite_heads(pattern_period=("l", "g"), n_layers=2, window_size=8)
    assert _qk_cores(cfg, "decode") == [("attn.qk", "xla"), ("attn.qk", "decode_attn")]


def test_binary_scores_cache_keeps_the_xla_chain(tpu_choice):
    cfg = _granite_heads()
    quant = dataclasses.replace(cfg.quant, backend_overrides=(("attn.qk", "binary"),))
    cfg = dataclasses.replace(cfg, quant=quant)
    assert _qk_cores(cfg, "decode") == [("attn.qk", "xla")]


def test_float_cache_takes_no_integer_core(tpu_choice):
    """A float cache scores in float: no act x act site, so no kernel."""
    cfg = _granite_heads()
    cfg = dataclasses.replace(cfg, quant=dataclasses.replace(cfg.quant, kv_cache_bits=16))
    assert _qk_cores(cfg, "decode") == []


def test_mla_latent_cache_keeps_the_xla_chain(tpu_choice):
    cfg = smoke_variant(get_config("deepseek-v2-lite-16b"))
    assert set(_qk_cores(cfg, "decode")) == {("attn.qk_latent", "xla")}


def test_engine_greedy_tokens_equal_through_both_cores(monkeypatch):
    """Served greedy tokens are the same whether decode attention runs in the
    (interpreted) kernel or in the XLA chain."""
    cfg = _granite_heads()
    params = Z.init_serving_params(jax.random.PRNGKey(16), cfg)
    prompts = [[3, 14, 15, 92, 65], [35, 89, 79, 32, 38, 46, 26], [43]]

    def serve():
        eng = ServeEngine(cfg, params, batch_slots=4, max_len=256)
        reqs = [Request(prompt=np.asarray(p, np.int32), max_new_tokens=6) for p in prompts]
        return [(r.state, r.output) for r in eng.run(reqs)]

    with site_log.recording() as sites:
        xla = serve()
    assert {s.get("int_core") for s in sites if s["site"] == "attn.qk"} == {"xla"}
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode(), site_log.recording() as sites:
        kernel = serve()
    assert "decode_attn" in {s.get("int_core") for s in sites if s["site"] == "attn.qk"}
    assert all(state == "ok" and len(out) == 6 for state, out in xla)
    assert kernel == xla

"""Serving a routed-expert model: dropless routing, the grouped 1-bit expert
decode kernel, and YaRN.

In serve mode every token gets all of its top-k experts and its output
depends on no other token.  At decode's few tokens on a TPU the experts run
in ``binary_qmm.expert_decode_qmm``, which reads only the routed experts'
packed words; its integer product is exact, so it must equal the XLA
integer path's bit for bit.  On the CPU the kernel runs interpreted; the
tests force the platform check (``ops.on_tpu``) where they need the TPU's
choice.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config
from repro.configs.smoke import smoke_variant
from repro.core import flow_abstraction as FA
from repro.core import packing, site_log
from repro.kernels import binary_qmm as BK
from repro.kernels import ops
from repro.models import layers as L
from repro.models import model_zoo as Z
from repro.models import moe as M
from repro.runtime import serve_loop
from repro.runtime.serve_loop import Request, ServeEngine

RNG = np.random.default_rng(1515)


@pytest.fixture
def tpu_choice(monkeypatch):
    """Make the model choose as on a TPU (kernels still interpreted)."""
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture(scope="module")
def smoke():
    """deepseek-v2-lite's blocks at a smoke size: one dense layer, two MoE
    layers of 8 experts, top-2, 2 shared; YaRN on."""
    cfg = dataclasses.replace(smoke_variant(get_config("deepseek-v2-lite-16b")), n_layers=3)
    return cfg, Z.init_serving_params(jax.random.PRNGKey(15), cfg)


# ---------------------------------------------------------------------------
# the grouped kernel
# ---------------------------------------------------------------------------


def _experts(e, k, n):
    w = jnp.asarray(RNG.integers(0, 2, size=(e, k, n)), jnp.int32)
    return w, packing.pack_bits(w, 1, axis=1)


@pytest.mark.parametrize(
    "routing",
    ["spread", "one_expert_has_every_row", "some_experts_have_none"],
)
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048), (200, 130)])
def test_grouped_kernel_equals_xla_int_product(routing, k, n):
    """Each row's int32 product with its expert's weight, through the kernel
    == the XLA integer path's (``default_int_matmul`` on the unpacked
    weight).  2048 -> 1408 and 1408 -> 2048 are deepseek-v2-lite's gate/up
    and down; 200 -> 130 needs padding of K, Kw and N."""
    e, r = 6, 20
    a = jnp.asarray(RNG.integers(-128, 128, size=(r, k)), jnp.int8)
    w, wp = _experts(e, k, n)
    if routing == "spread":
        row_expert = RNG.integers(0, e, size=r)
    elif routing == "one_expert_has_every_row":
        row_expert = np.full(r, 4)  # 20 rows: three tiles of one expert
    else:
        row_expert = RNG.choice([1, 5], size=r)  # 0, 2, 3, 4 get no rows
    row_expert = jnp.asarray(row_expert, jnp.int32)
    out = ops.expert_decode_qmm_int(a, ops.expert_tiles(row_expert, e), wp, interpret=True)
    expect = jnp.concatenate(
        [FA.default_int_matmul(a[i : i + 1], w[row_expert[i]].astype(jnp.int8), 8, 1) for i in range(r)]
    )
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("k,n", [(2048, 1408), (200, 130)])
def test_grouped_kernel_reads_a_layer_of_the_stack(layer, k, n):
    """Every layer's words ``(L, E, K/32, N)`` and a layer index give the
    product with that layer's experts: the stack read in place equals its
    slice passed alone."""
    n_layers, e, r = 3, 4, 12
    a = jnp.asarray(RNG.integers(-128, 128, size=(r, k)), jnp.int8)
    stack = jnp.stack([_experts(e, k, n)[1] for _ in range(n_layers)])
    tiles = ops.expert_tiles(jnp.asarray(RNG.integers(0, e, size=r), jnp.int32), e)
    got = ops.expert_decode_qmm_int(a, tiles, stack, jnp.int32(layer), interpret=True)
    want = ops.expert_decode_qmm_int(a, tiles, stack[layer], interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_grouped_kernel_needs_the_layer_of_a_stack():
    tiles = ops.expert_tiles(jnp.zeros((4,), jnp.int32), 2)
    with pytest.raises(ValueError, match="layer"):
        ops.expert_decode_qmm_int(
            jnp.zeros((4, 64), jnp.int8), tiles, jnp.zeros((3, 2, 2, 128), jnp.uint32)
        )


@pytest.mark.parametrize("r,e", [(96, 64), (12, 8), (3, 64), (64, 2)])
def test_expert_tiles_cover_every_row_once(r, e):
    """Each row lands in one padded slot of a tile of its own expert, tiles of
    an expert are consecutive, and the tiles in use fit ``n_max``."""
    row_expert = jnp.asarray(RNG.integers(0, e, size=r), jnp.int32)
    t = ops.expert_tiles(row_expert, e)
    bm = BK.EXPERT_TILE_ROWS
    slot, source = np.asarray(t.slot), np.asarray(t.source)
    tile_expert, n_tiles = np.asarray(t.tile_expert), int(t.n_tiles[0])
    assert len(set(slot)) == r and (source[slot] == np.arange(r)).all()
    assert (source < r).sum() == r
    assert (tile_expert[slot // bm] == np.asarray(row_expert)).all()
    sizes = np.bincount(np.asarray(row_expert), minlength=e)
    assert n_tiles == sum(-(-s // bm) for s in sizes) <= len(tile_expert)
    used = tile_expert[:n_tiles]
    assert (np.diff(used) >= 0).all()  # in expert order, each expert's tiles together
    assert (tile_expert[n_tiles:] == used[-1]).all()  # the tail fetches nothing new


def test_expert_block_takes_a_whole_expert():
    assert BK.expert_block(64, 1408) == (1408, 64)  # gate, up: 352 KiB of words
    assert BK.expert_block(44, 2048) == (2048, 44)  # down
    assert BK.expert_block(448, 4096) == (512, 448)  # too wide for one block


# ---------------------------------------------------------------------------
# dropless routing
# ---------------------------------------------------------------------------


def _moe_layer(params):
    """The first MoE layer's serving params (scan step 0 of the period)."""
    return jax.tree.map(lambda a: a[0], params["stack"]["period"][0]["moe"])


def _per_token_gather(p, x, cfg):
    """Each token alone through each of its top-k experts, sliced out of the
    stacked weights, weighted by its router probabilities; plus the shared."""
    xf = x.reshape(-1, cfg.d_model)
    logits = jnp.dot(xf.astype(jnp.float32), p["router"]["w"], precision=jax.lax.Precision.HIGHEST)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), cfg.moe.top_k)
    out = []
    for t in range(xf.shape[0]):
        tok, acc = xf[t : t + 1], 0.0
        for j in range(cfg.moe.top_k):
            pe = jax.tree.map(lambda a: a[int(idx[t, j])], {k: p[k] for k in ("gate", "up", "down")})
            g = L.qlinear(pe["gate"], tok, cfg.quant, "serve")
            u = L.qlinear(pe["up"], tok, cfg.quant, "serve")
            h = jax.nn.silu(g.astype(jnp.float32)).astype(tok.dtype) * u
            acc = acc + w[t, j] * L.qlinear(pe["down"], h, cfg.quant, "serve").astype(jnp.float32)
        out.append(acc.astype(tok.dtype) + L.ffn(p["shared"], tok, cfg.ffn_type, cfg.quant, "serve"))
    return jnp.concatenate(out).reshape(x.shape)


@pytest.mark.parametrize("tokens", [1, 5, 16])
@pytest.mark.parametrize("core", ["scan", "grouped_kernel"])
def test_serve_moe_equals_per_token_gather(smoke, tokens, core, request):
    """Serve mode runs every token through all of its top-k experts: the
    scan over experts (prefill, and decode off the TPU) and the grouped
    kernel (decode on a TPU) both equal a per-token gather of its experts,
    to the bf16 rounding of the output."""
    if core == "grouped_kernel":
        request.getfixturevalue("tpu_choice")
    cfg, params = smoke
    p = _moe_layer(params)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (1, tokens, cfg.d_model)).astype(jnp.bfloat16)
    assert M.expert_kernel_engages(tokens, cfg.quant) == (core == "grouped_kernel")
    got, _ = M.moe_ffn(p, x, cfg, "serve")
    want = _per_token_gather(p, x, cfg)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=2**-7, atol=2**-7
    )


def test_serving_scan_holds_the_expert_words_whole(smoke):
    """The layer scan slices every period param but the routed experts'
    words, which each step reads out of the whole stack at its layer."""
    _, params = smoke
    period = params["stack"]["period"]
    scanned, held = M.hold_expert_words(period)
    for m in ("gate", "up", "down"):
        assert "w_packed" not in scanned[0]["moe"][m]
        assert held[0][m] is period[0]["moe"][m]["w_packed"]
        assert "w_scale" in scanned[0]["moe"][m]
    blk = M.lend_expert_words(jax.tree.map(lambda a: a[1], scanned[0]), held[0], jnp.int32(1))
    assert blk["moe"]["up"]["w_packed"].ndim == 4 and int(blk["moe"]["up"]["layer"]) == 1
    dense = {"ffn": {"w": jnp.zeros((2, 2))}}
    assert M.hold_expert_words([dense]) == ([dense], [None])


def test_decode_step_through_the_kernel_equals_the_scan(smoke, monkeypatch):
    """A whole decode step (dense layer, then the scanned MoE layers reading
    the held stack) gives the same logits through the grouped kernel as
    through the scan over experts, which slices each layer's words."""
    cfg, params = smoke
    tokens = jnp.asarray([3, 17, 40, 99], jnp.int32)
    step = lambda: Z.decode_step(params, tokens, cfg, Z.init_cache(4, 32, cfg))[0]  # noqa: E731
    scan = step()
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        assert M.expert_kernel_engages(4, cfg.quant)
        kernel = step()
    np.testing.assert_allclose(np.asarray(kernel, np.float32), np.asarray(scan, np.float32), rtol=2**-7, atol=2**-7)


def test_prefill_with_the_prefix_compiled_equals_it_eager(smoke):
    """The engine runs the dense prefix layer of its eager prefill as one
    compiled program (``compiled_prefix``): the same logits as the layer
    dispatched op by op, but for bf16 rounding that XLA's fusion moves (a
    hundredth of a logit through three layers, a tenth of the logits' std
    at most), and the cache at the same position."""
    cfg, params = smoke
    tokens = jnp.asarray([[5, 9, 77, 3, 41]], jnp.int32)
    eager, eager_cache = Z.prefill(params, tokens, cfg, Z.init_slot_cache(32, cfg))
    got, cache = Z.prefill(params, tokens, cfg, Z.init_slot_cache(32, cfg), compiled_prefix=True)
    eager, got = np.asarray(eager, np.float32), np.asarray(got, np.float32)
    assert np.abs(got - eager).max() < 0.1 * eager.std()
    assert jax.tree.structure(cache) == jax.tree.structure(eager_cache)
    np.testing.assert_array_equal(np.asarray(cache["stack"]["prefix"][0]["pos"]), 5)


def test_serve_moe_drops_no_token_when_all_route_alike(smoke):
    """Sixteen copies of one token all pick the same experts: capacity
    dispatch (``round(1.25 * 32 / 8)`` = 5 a expert) would drop eleven of
    them; serving gives every copy the same output."""
    cfg, params = smoke
    p = _moe_layer(params)
    x = jnp.broadcast_to(jax.random.normal(jax.random.PRNGKey(0), (1, 1, cfg.d_model)), (1, 16, cfg.d_model))
    got, _ = M.moe_ffn(p, x.astype(jnp.bfloat16), cfg, "serve")
    got = np.asarray(got, np.float32)[0]
    np.testing.assert_array_equal(got, np.broadcast_to(got[0], got.shape))


def test_expert_sites_reach_the_site_log(smoke, tpu_choice):
    """Decode records each routed-expert site with the grouped kernel's core,
    and the shared experts under names of their own."""
    cfg, params = smoke
    cache = Z.init_cache(4, 32, cfg)
    with site_log.recording() as sites:
        jax.eval_shape(lambda p, t, c: Z.decode_step(p, t, cfg, c), params, jnp.zeros((4,), jnp.int32), cache)
    by = {s["site"]: s for s in sites if s["kind"] == "qlinear"}
    for m in ("gate", "up", "down"):
        assert by[f"moe.experts.{m}"]["int_core"] == "packed"
        assert f"moe.shared.{m}" in by
    assert "ffn.gate" in by  # the dense layer


class _Tap:
    """The logits each served token was sampled from, by request (around the
    engine's ``_sample``; ``on_token`` follows each sample of its request)."""

    def __init__(self, monkeypatch):
        self.pending = None
        orig = serve_loop._sample

        def sample(logits, temperature, rng):
            self.pending = np.array(logits)
            return orig(logits, temperature, rng)

        monkeypatch.setattr(serve_loop, "_sample", sample)

    def request(self, prompt, n):
        rows = []
        req = Request(prompt=prompt, max_new_tokens=n, on_token=lambda tok: rows.append(self.pending))
        return req, rows


def test_batch_invariance_through_the_engine(smoke, monkeypatch):
    """A request's logits are the same served alone and beside seven others:
    routing, experts and the cache are per token and per slot."""
    cfg, params = smoke
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, 6).astype(np.int32) for _ in range(8)]
    tap = _Tap(monkeypatch)
    alone, alone_rows = tap.request(prompts[0], 4)
    ServeEngine(cfg, params, batch_slots=8, max_len=32, seed=0).run([alone])
    served = [tap.request(p, 4) for p in prompts]
    ServeEngine(cfg, params, batch_slots=8, max_len=32, seed=0).run([r for r, _ in served])
    assert alone.output == served[0][0].output
    np.testing.assert_array_equal(np.stack(alone_rows), np.stack(served[0][1]))


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def test_yarn_by_hand():
    """deepseek-v2-lite's rope: d 64, base 10000, factor 40 over 4096
    positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707."""
    import math

    ys = get_config("deepseek-v2-lite-16b").rope_scaling
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    inv = L.rope_inv_freq(64, 10000.0, ys)
    extra = lambda i: 10000.0 ** (-2 * i / 64)  # noqa: E731
    assert inv[0] == pytest.approx(1.0)
    assert inv[10] == pytest.approx(extra(10), rel=1e-6)  # ramp 0: unscaled
    assert inv[16] == pytest.approx(extra(16) * (6 / 13 / 40 + 7 / 13), rel=1e-6)
    assert inv[23] == pytest.approx(extra(23) / 40, rel=1e-6)  # ramp 1: divided by factor
    assert inv[31] == pytest.approx(extra(31) / 40, rel=1e-6)
    assert (np.abs(inv / [extra(i) for i in range(32)] - 1) > 1e-6).sum() == 21
    assert L.attention_scale(192, ys) == pytest.approx(0.114721, abs=5e-7)
    assert L.yarn_mscale(40, 0.707) == pytest.approx(1.2608, abs=1e-4)


def test_rope_without_scaling_is_the_unscaled_program():
    """A config without ``rope_scaling`` lowers ``rope`` to the program it had
    before YaRN: the frequencies ``theta^(-i/half)``, no extra multiply."""

    def before(x, positions, theta):
        half = x.shape[-1] // 2
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        angles = positions.astype(jnp.float32)[..., None] * freqs
        angles = angles[..., None, :]
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)

    x = jax.ShapeDtypeStruct((2, 5, 4, 128), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((2, 5), jnp.int32)
    new = jax.jit(lambda a, b: L.rope(a, b, 1e7)).lower(x, pos).as_text(debug_info=False)
    old = jax.jit(lambda a, b: before(a, b, 1e7)).lower(x, pos).as_text(debug_info=False)
    assert new == old


def test_rope_scaling_needs_mla():
    from repro.configs.base import RopeScaling

    with pytest.raises(ValueError, match="MLA"):
        dataclasses.replace(get_config("granite-8b"), rope_scaling=RopeScaling(40.0, 4096))

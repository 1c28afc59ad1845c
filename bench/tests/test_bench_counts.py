"""The needed-work counts of a dense GQA decode step, against hand arithmetic
for granite-8b (36 layers, d 4096, 32/8 heads of 128, d_ff 14336, vocab 49152)."""

import json

from bench.spec import ROOT, load_module

FAMILY = load_module(ROOT / "bench" / "families" / "dense_gqa.py")
GRANITE = json.loads((ROOT / "bench" / "configs" / "granite-8b.json").read_text())

# one layer: q 4096x4096, k and v 4096x1024, o 4096x4096, gate/up 4096x14336, down 14336x4096
LAYER_MACS = 16_777_216 + 4_194_304 + 4_194_304 + 16_777_216 + 3 * 58_720_256
# 1 bit per weight, one fp32 scale per output channel (4096+1024+1024+4096+14336+14336+4096)
LAYER_BYTES = LAYER_MACS // 8 + 4 * 43_008
HEAD_BYTES = 49_152 * 4096 * 2  # tied bf16 embedding
KV_PER_TOKEN = 36 * 2 * 8 * 128  # int8 K and V, 36 layers


def test_one_layer_by_hand():
    assert LAYER_MACS == 218_103_808
    assert FAMILY.layer_macs(GRANITE) == LAYER_MACS
    assert FAMILY.weight_bytes(GRANITE) == 36 * 27_435_008 + HEAD_BYTES
    assert FAMILY.kv_bytes_per_token(GRANITE) == KV_PER_TOKEN == 73_728


def test_decode_tick_by_hand():
    live = [100, 2000]
    ops, nbytes = FAMILY.decode_need(GRANITE, live)
    attn = 2 * 32 * 128  # QK and PV MACs per cached token per layer
    want_ops = sum(2 * (36 * (LAYER_MACS + attn * n) + 49_152 * 4096) for n in live)
    assert ops == want_ops
    assert nbytes == 36 * LAYER_BYTES + HEAD_BYTES + KV_PER_TOKEN * 2100


def test_idle_tick_needs_nothing():
    assert FAMILY.decode_need(GRANITE, []) == (0, 0)

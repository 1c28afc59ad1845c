"""Work that one decode step of a dense GQA decoder needs, from its shapes.

Counted at the configuration's declared precisions, not from what the
program reads: 1-bit weights with one fp32 scale per output channel, the
head (tied embedding or unembedding) at its stored dtype, the int8 KV cache
at the LIVE length of each active slot, int8 activations.  A program that
reads more (unpacked weights, the whole ``max_len`` cache, a second cache)
is slower than this, never faster, so a share of it stays under 100%.

``c`` is a configuration file's dict (``bench/configs/<name>.json``).
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _matrices(c: Dict) -> Iterable[Tuple[int, int]]:
    """(K, N) of every binarized matrix of one layer."""
    d, h, kvh, dh, ff = c["d_model"], c["n_heads"], c["n_kv_heads"], c["d_head"], c["d_ff"]
    yield d, h * dh  # q
    yield d, kvh * dh  # k
    yield d, kvh * dh  # v
    yield h * dh, d  # o
    yield d, ff  # gate
    yield d, ff  # up
    yield ff, d  # down


def layer_macs(c: Dict) -> int:
    """Multiply-accumulates of one layer's projections for one token."""
    return sum(k * n for k, n in _matrices(c))


def weight_bytes(c: Dict) -> int:
    """Bytes a decode step must read once: packed weights, scales, head."""
    bits = c["weight_bits"]
    per_layer = sum(k * n * bits // 8 + 4 * n for k, n in _matrices(c))
    head = c["vocab_size"] * c["d_model"] * DTYPE_BYTES[c["embedding_dtype"]]
    return c["n_layers"] * per_layer + head


def kv_bytes_per_token(c: Dict) -> int:
    return c["n_layers"] * 2 * c["n_kv_heads"] * c["d_head"] * c["kv_cache_bits"] // 8


def decode_need(c: Dict, live: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) one decode tick needs for slots at ``live``.

    Operations count a multiply-accumulate as two: the projections, QK and
    PV over each slot's live length, and the head.  Bytes: the weights once
    per tick; of each slot's K and V, the ``n - 1`` cached rows read and the
    new row written (``n`` counts the token this tick adds).
    """
    live = list(live)
    attn = 2 * c["n_heads"] * c["d_head"]  # QK + PV MACs per cached token per layer
    ops = 0
    for n in live:
        ops += 2 * (c["n_layers"] * (layer_macs(c) + attn * n) + c["vocab_size"] * c["d_model"])
    kv = kv_bytes_per_token(c) * sum(live)
    return ops, (weight_bytes(c) if live else 0) + kv


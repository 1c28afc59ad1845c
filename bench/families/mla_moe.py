"""Work that one decode step of an MLA + routed-expert decoder needs, from its
shapes (DeepSeek-V2: latent attention, top-k of many fine experts beside
shared ones, ``first_k_dense_replace`` dense layers first).

Counted at the configuration's declared precisions, not from what the
program reads: 1-bit weights with one fp32 scale per output channel, the
fp32 router, the bf16 head, the latent cache (int8 latent, bf16 rope key)
at the LIVE length of each active slot, int8 activations.  A program that
reads more (unpacked weights, the whole ``max_len`` cache) is slower than
this, never faster, so a share of it stays under 100%.

The routed experts count at the least that any routing needs:
``num_experts_per_tok`` experts of each MoE layer whenever a slot is live,
read once however many slots route to them.  Which experts a tick's routing
really hit (about 51 of 64 a layer at 16 slots) is not visible to a reader
of the run, and a larger guess could read over 100%.

``c`` is a configuration file's dict (``bench/configs/<name>.json``), under
the published config's own key names.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _attention(c: Dict) -> Iterable[Tuple[int, int]]:
    """(K, N) of every binarized matrix of one layer's attention."""
    d, h, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    yield d, h * (dn + dr)  # q_proj (no q LoRA)
    yield d, r  # kv_down
    yield d, dr  # k_rope
    yield r, h * dn  # k_up, absorbed into q in decode
    yield r, h * dv  # v_up, absorbed into the context in decode
    yield h * dv, d  # o


def _glu(d: int, ff: int) -> Iterable[Tuple[int, int]]:
    yield d, ff  # gate
    yield d, ff  # up
    yield ff, d  # down


def _expert(c: Dict) -> Iterable[Tuple[int, int]]:
    return _glu(c["hidden_size"], c["moe_intermediate_size"])


def _shared(c: Dict) -> Iterable[Tuple[int, int]]:
    return _glu(c["hidden_size"], c["n_shared_experts"] * c["moe_intermediate_size"])


def _layers(c: Dict) -> Tuple[int, int]:
    """(dense layers, MoE layers)."""
    dense = c["first_k_dense_replace"]
    return dense, c["num_hidden_layers"] - dense


def _packed(mats: Iterable[Tuple[int, int]], bits: int, scales: bool = True) -> int:
    return sum(k * n * bits // 8 + (4 * n if scales else 0) for k, n in mats)


def token_macs(c: Dict) -> int:
    """Multiply-accumulates of one token through the stack's projections,
    absorbs, router and the experts it is routed to (attention over the
    cache apart)."""
    d = c["hidden_size"]
    dense, moe = _layers(c)
    attn = sum(k * n for k, n in _attention(c))  # the absorbs are k_up and v_up
    ffn = sum(k * n for k, n in _glu(d, c["intermediate_size"]))
    routed = c["num_experts_per_tok"] * sum(k * n for k, n in _expert(c))
    shared = sum(k * n for k, n in _shared(c))
    router = d * c["n_routed_experts"]
    return (dense + moe) * attn + dense * ffn + moe * (routed + shared + router)


def weight_bytes(c: Dict) -> int:
    """Bytes a decode step must read once: packed weights and scales, the
    least routed experts, the fp32 router, the head."""
    bits = c["weight_bits"]
    dense, moe = _layers(c)
    attn = _packed(_attention(c), bits)
    ffn = _packed(_glu(c["hidden_size"], c["intermediate_size"]), bits)
    routed = c["num_experts_per_tok"] * _packed(_expert(c), bits)
    shared = _packed(_shared(c), bits)
    router = 4 * c["hidden_size"] * c["n_routed_experts"]
    head = c["vocab_size"] * c["hidden_size"] * DTYPE_BYTES[c["embedding_dtype"]]
    return (dense + moe) * attn + dense * ffn + moe * (routed + shared + router) + head


def cache_bytes_per_token(c: Dict) -> int:
    """The latent cache of one token over every layer: the int8 latent and
    the bf16 rope key."""
    per_layer = c["kv_lora_rank"] * c["kv_cache_bits"] // 8 + 2 * c["qk_rope_head_dim"]
    return c["num_hidden_layers"] * per_layer


def decode_need(c: Dict, live: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) one decode tick needs for slots at ``live``.

    Operations count a multiply-accumulate as two: :func:`token_macs`, the
    latent and rope scores and the latent PV over each slot's live length,
    and the head.  Bytes: the weights once per tick (:func:`weight_bytes`);
    of each slot's latent cache, the ``n - 1`` cached rows read and the new
    row written (``n`` counts the token this tick adds).
    """
    live = list(live)
    h, r, dr = c["num_attention_heads"], c["kv_lora_rank"], c["qk_rope_head_dim"]
    attn = h * (2 * r + dr)  # latent QK, rope QK and latent PV MACs per cached token per layer
    head = c["vocab_size"] * c["hidden_size"]
    ops = sum(2 * (token_macs(c) + c["num_hidden_layers"] * attn * n + head) for n in live)
    cache = cache_bytes_per_token(c) * sum(live)
    return ops, (weight_bytes(c) if live else 0) + cache


def expert_need(c: Dict, live: Iterable[int]) -> Tuple[int, int]:
    """(operations, bytes) of the routed experts in one decode tick: the
    grouped expert kernel's share of :func:`decode_need`.  Operations: each
    live slot's ``num_experts_per_tok`` expert rows through gate, up and
    down in every MoE layer.  Bytes: the packed words of
    ``num_experts_per_tok`` experts a MoE layer, the least any routing reads
    (the kernel reads no scales: the epilogue applies them)."""
    live = list(live)
    if not live:
        return 0, 0
    _, moe = _layers(c)
    rows = c["num_experts_per_tok"] * len(live)
    macs = rows * sum(k * n for k, n in _expert(c))
    words = c["num_experts_per_tok"] * _packed(_expert(c), c["weight_bits"], scales=False)
    return 2 * moe * macs, moe * words

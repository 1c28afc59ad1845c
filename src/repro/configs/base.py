"""Architecture / shape / quantization config schema.

Every assigned architecture is a frozen :class:`ArchConfig`; the model zoo
(``repro.models.model_zoo``) builds params and step functions from it, the
launcher selects one by ``--arch <id>``, and the dry-run sweeps
``(arch x input-shape x mesh)``.

Layer patterns: a transformer stack is ``prefix_layers`` (unrolled) followed
by ``pattern_period`` repeated ``(n_layers - len(prefix)) / len(period)``
times (lowered as one ``lax.scan`` over stacked period params — keeps HLO
size bounded for 60+-layer models, which matters both for compile time and
for the dry-run's 512-way SPMD partitioning).

Block kinds:
  "g"   global attention + dense FFN
  "l"   local (sliding-window) attention + dense FFN
  "r"   RG-LRU recurrent block + dense FFN        (recurrentgemma)
  "s"   Mamba-2 SSD mixer (no separate FFN)       (mamba2)
  "Md"  MLA attention + dense FFN                 (deepseek dense layers)
  "Mm"  MLA attention + MoE FFN                   (deepseek MoE layers)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

__all__ = [
    "QuantConfig",
    "MoEConfig",
    "RopeScaling",
    "SSMConfig",
    "EncoderConfig",
    "ArchConfig",
    "InputShape",
    "LM_SHAPES",
    "register",
    "get_config",
    "list_configs",
]


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """BETA quantization spec — which QMMs are quantized and how.

    ``act_bits`` selects the engine's precision mode (W1A{1,2,4,8});
    ``attn_act_bits`` covers the act x act QMMs (QK^T, PV); ``kv_cache_bits``
    is the serving-side KV compression (8 -> int8 lanes, 4 -> packed nibbles).
    Non-QMM ops (softmax, norms, activations, routers, recurrences) stay full
    precision, as in the paper.
    """

    enabled: bool = True
    weight_bits: int = 1
    act_bits: int = 8
    attn_act_bits: int = 8
    quantize_attention: bool = True
    kv_cache_bits: int = 8
    # integer-MM backend: "auto" or any name registered in
    # core.backend_registry ("mxu", "popcount", "pallas", "fused", ...).
    # "auto" routes through the measured autotune cache (core.dispatch).
    backend: str = "mxu"
    # per-layer backend overrides: ((fnmatch pattern over the layer name,
    # backend), ...) — first match wins, e.g. (("ffn.down", "popcount"),
    # ("attn.*", "mxu")).  Unmatched layers use ``backend``.
    backend_overrides: Tuple[Tuple[str, str], ...] = ()
    # QAT weights are binarized+bit-packed BEFORE the FSDP all-gather, so
    # the wire carries 1-bit words instead of fp32 latents (32x — the
    # BETA storage insight applied to the collective fabric; §Perf).
    prebinarize_gather: bool = False

    @staticmethod
    def known_backends() -> Tuple[str, ...]:
        """Valid integer-MM backend names: "auto" (measured dispatch,
        core.dispatch) plus every backend in ``core.backend_registry``."""
        from repro.core import backend_registry

        return ("auto",) + backend_registry.backend_names()

    def __post_init__(self):
        known = self.known_backends()
        if self.backend not in known:
            raise ValueError(f"unknown backend {self.backend!r}; valid: {known}")
        for pattern, b in self.backend_overrides:
            if b not in known:
                raise ValueError(
                    f"backend_overrides[{pattern!r}] names unknown backend "
                    f"{b!r}; valid: {known}"
                )

    @property
    def mode_name(self) -> str:
        return f"W{self.weight_bits}A{self.act_bits}"

    def backend_for(self, layer_name: str = "") -> str:
        """Resolve the integer-MM backend for a named layer site.

        ``layer_name`` is the dotted site name the model layer passes down
        (e.g. "ffn.up", "attn.o"); unnamed sites resolve to the default.
        """
        if layer_name:
            import fnmatch

            for pattern, b in self.backend_overrides:
                if fnmatch.fnmatchcase(layer_name, pattern):
                    return b
        return self.backend


FLOAT_QUANT = QuantConfig(enabled=False)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_routed: int
    n_shared: int
    top_k: int
    d_expert_ff: int
    d_shared_ff: int = 0  # defaults to d_expert_ff * n_shared
    capacity_factor: float = 1.25
    router_scoring: str = "softmax"  # "softmax" | "sigmoid" (deepseek-v3)
    route_scale: float = 1.0

    @property
    def shared_ff(self) -> int:
        return self.d_shared_ff or self.d_expert_ff * self.n_shared


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    n_groups: int = 1
    chunk: int = 128

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """DeepSeek multi-head latent attention geometry."""

    kv_lora_rank: int = 512
    q_lora_rank: int = 0  # 0 -> direct q projection (v2-lite)
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN frequency scaling of RoPE (arXiv:2309.00071), with DeepSeek's
    attention-temperature terms, as a model's ``config.json`` publishes it
    under ``rope_scaling`` (``type: yarn``).

    Frequencies whose wavelength fits ``beta_fast`` times in the original
    context keep their value, those that fit fewer than ``beta_slow`` times
    are divided by ``factor``, and a linear ramp joins the two
    (``layers.rope_inv_freq``).  The softmax scale is multiplied by
    ``yarn_mscale(factor, mscale_all_dim) ** 2``, cos and sin by
    ``yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)``.
    """

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    """Frontend/encoder for enc-dec (whisper) and VLM (internvl2) archs.

    Per the assignment spec the modality frontend is a STUB: ``input_specs``
    provides precomputed frame/patch embeddings of shape
    ``(batch, n_positions, d_model)`` (projected in by a single stub linear),
    and for whisper a full transformer encoder runs on top for cross-attn.
    """

    kind: str  # "audio_stub" | "patch_stub"
    n_positions: int  # 1500 audio frames / vision patches per image
    n_layers: int = 0  # transformer layers on top of the stub (whisper: 4)
    d_input: int = 0  # stub embedding dim before projection (0 -> d_model)


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio | encoder
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: int = 0  # 0 -> d_model // n_heads
    pattern_period: Tuple[str, ...] = ("g",)
    prefix_layers: Tuple[str, ...] = ()
    window_size: int = 0
    qk_norm: bool = False
    ffn_type: str = "silu_glu"  # "gelu" | "silu_glu" | "gelu_glu"
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None  # YaRN; MLA attention only
    local_rope_theta: float = 0.0  # gemma3 uses a different theta locally
    pos_embedding: str = "rope"  # "rope" | "learned" | "sinusoidal" | "none"
    causal: bool = True
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    mla: Optional[MLAConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None
    quant: QuantConfig = QuantConfig()
    mtp_depth: int = 0  # deepseek-v3 multi-token prediction heads
    max_seq: int = 131072
    source: str = ""  # provenance note: [source; verified-tier]

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):  # as a configuration file states it
            object.__setattr__(self, "rope_scaling", RopeScaling(**self.rope_scaling))
        if self.rope_scaling is not None and self.mla is None:
            raise ValueError(f"{self.name}: rope_scaling is implemented for MLA attention only")
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)
        n_pattern = self.n_layers - len(self.prefix_layers)
        if n_pattern < 0 or (
            len(self.pattern_period) and n_pattern % len(self.pattern_period)
        ):
            raise ValueError(
                f"{self.name}: {self.n_layers} layers does not decompose into "
                f"prefix {self.prefix_layers} + k * period {self.pattern_period}"
            )

    @property
    def n_periods(self) -> int:
        return (self.n_layers - len(self.prefix_layers)) // len(self.pattern_period)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return self.prefix_layers + self.pattern_period * self.n_periods

    @property
    def is_sub_quadratic(self) -> bool:
        """True when no layer does full attention over the whole sequence
        (SSM / linear-recurrence / bounded-window only) — the long_500k
        eligibility rule (DESIGN.md §5)."""
        return all(k in ("l", "r", "s") for k in self.layer_kinds)

    @property
    def has_decoder(self) -> bool:
        return self.family != "encoder"

    def param_count(self) -> int:
        """Approximate parameter count (embeddings + blocks), for roofline
        MODEL_FLOPS = 6*N*D accounting."""
        d, ff = self.d_model, self.d_ff
        total = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        for kind in self.layer_kinds:
            if kind in ("g", "l"):
                attn = d * (self.n_heads + 2 * self.n_kv_heads) * self.d_head
                attn += self.n_heads * self.d_head * d
                ffp = self._ffn_params(ff)
                total += attn + ffp
            elif kind in ("Md", "Mm"):
                m = self.mla
                q = (
                    d * m.q_lora_rank
                    + m.q_lora_rank * self.n_heads * (m.qk_nope_dim + m.qk_rope_dim)
                    if m.q_lora_rank
                    else d * self.n_heads * (m.qk_nope_dim + m.qk_rope_dim)
                )
                kv = d * (m.kv_lora_rank + m.qk_rope_dim) + m.kv_lora_rank * self.n_heads * (
                    m.qk_nope_dim + m.v_head_dim
                )
                o = self.n_heads * m.v_head_dim * d
                total += q + kv + o
                if kind == "Md":
                    total += self._ffn_params(ff)
                else:
                    e = self.moe
                    total += e.n_routed * self._ffn_params(e.d_expert_ff)
                    total += self._ffn_params(e.shared_ff)
                    total += d * e.n_routed  # router
            elif kind == "r":
                di = self.d_model  # RG-LRU width = d_model (recurrentgemma)
                total += 2 * d * di + di * d + 3 * di  # in/gate/out + gates
                total += self._ffn_params(ff)
            elif kind == "s":
                s = self.ssm
                di = s.d_inner(d)
                nh = s.n_heads(d)
                total += d * (2 * di + 2 * s.n_groups * s.d_state + nh)  # in_proj
                total += di * d  # out_proj
                total += di * s.d_conv + nh * 2  # conv + A, D
        return total

    def _ffn_params(self, ff: int) -> int:
        mult = 3 if self.ffn_type.endswith("glu") else 2
        return mult * self.d_model * ff

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        if self.moe is None:
            return self.param_count()
        total = self.param_count()
        e = self.moe
        n_moe_layers = sum(1 for k in self.layer_kinds if k == "Mm")
        inactive = (e.n_routed - e.top_k) * self._ffn_params(e.d_expert_ff)
        return total - n_moe_layers * inactive


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


#: The assigned LM shape grid (each arch runs all four, minus documented skips).
LM_SHAPES: Tuple[InputShape, ...] = (
    InputShape("train_4k", 4096, 256, "train"),
    InputShape("prefill_32k", 32768, 32, "prefill"),
    InputShape("decode_32k", 32768, 128, "decode"),
    InputShape("long_500k", 524288, 1, "decode"),
)

SHAPES_BY_NAME: Dict[str, InputShape] = {s.name: s for s in LM_SHAPES}

_REGISTRY: Dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    if cfg.name in _REGISTRY:
        raise ValueError(f"duplicate arch config {cfg.name}")
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ArchConfig:
    # populate the registry on first use
    from repro import configs as _pkg  # noqa: F401  (imports all modules)

    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}") from None


def list_configs() -> Tuple[str, ...]:
    from repro import configs as _pkg  # noqa: F401

    return tuple(sorted(_REGISTRY))

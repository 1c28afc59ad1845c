"""Pass 1 — jaxpr invariant verifier for the quantized datapath.

The paper's efficiency claims rest on invariants the type system never sees
(§III-A computation flow abstraction): packed bit-planes may only meet
integer/bitwise ops until a popcount consumes them, accumulation stays
integer across bit significance, cache slots keep their initialized dtypes,
and every serve-mode QMM site quantizes to the precision its QuantConfig
declares.  This module traces real code to jaxprs (via ShapeDtypeStruct
inputs — no FLOPs, no RAM) and walks them enforcing:

  INV-PACKED-FLOAT  a packed-bit-plane value (uint32 words from
                    core/packing.py) reaches a floating-point primitive
  INV-ACCUM-LOWFP   a popcount/kernel accumulator is converted to
                    bf16/f16 (f32/f64 epilogue casts are the legal exit)
  INV-INT-DOT       dot_general over integer operands accumulates in
                    anything but i32/i64/u32/u64
  INV-CACHE-DTYPE   a prefill/decode output cache leaf differs in dtype
                    from the ``init_cache`` leaf (the PR 6 drift class)
  INV-CACHE-SHAPE   ... or in shape
  INV-CACHE-STRUCT  ... or the cache pytree structure itself changed
  INV-SITE-NAME     a serve-mode qlinear QMM ran at an unnamed site
                    (unnameable sites cannot get backend overrides)
  INV-SITE-BITS     a site quantized to a precision other than the one
                    its QuantConfig declares
  INV-SITE-MANTISSA a site produced a mantissa dtype violating the
                    quantizer contract (uint8 for <=8 bits, int8 after
                    re-centering, int32 above)

Taint semantics: uint32 trace inputs and outputs of the jitted pack
helpers (``pack_bits`` / ``pack_bitplanes`` / ``to_bitplanes``) carry the
"packed" taint; ``unpack_bits`` / ``from_bitplanes`` launder it;
``population_count`` and ``pallas_call`` (trusted kernel boundary — kernel
internals are covered by the parity tests against ``kernels/ref.py``)
consume "packed" and emit the "counts" taint on integer outputs.
Converting counts to f32/f64 is the legal epilogue exit — including a
Pallas kernel that applies the affine epilogue on-chip and returns f32
directly (``kernels/fused_qmm.py``); a kernel or cast producing bf16/f16
from packed/counts operands is INV-ACCUM-LOWFP.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.analysis.findings import Finding
from repro.core import backend_registry, packing
from repro.core import qmm as QE
from repro.core import site_log
from repro.core.quantization import QuantTensor

__all__ = [
    "check_function",
    "check_cache_contract",
    "verify_backends",
    "verify_binary_attention",
    "verify_arch",
    "verify_archs",
    "DEFAULT_ARCHS",
]

TAINT_PACKED = "packed"
TAINT_COUNTS = "counts"

#: jitted helpers whose outputs ARE packed bit-planes (don't recurse).
PACK_NAMES = frozenset({"pack_bits", "pack_bitplanes", "to_bitplanes"})
#: jitted helpers that consume packed words and return logical mantissas.
UNPACK_NAMES = frozenset({"unpack_bits", "from_bitplanes"})

_INT_ACCUM_DTYPES = {
    jnp.dtype(jnp.int32),
    jnp.dtype(jnp.int64),
    jnp.dtype(jnp.uint32),
    jnp.dtype(jnp.uint64),
}
# referenced as *data* (the dtypes the rule is about), hence string names
_LOWFP_DTYPES = {jnp.dtype("bfloat16"), jnp.dtype("float16")}


# ---------------------------------------------------------------------------
# taint walker
# ---------------------------------------------------------------------------


def _dtype(var) -> Optional[jnp.dtype]:
    aval = getattr(var, "aval", None)
    return jnp.dtype(aval.dtype) if getattr(aval, "dtype", None) is not None else None


def _is_float(var) -> bool:
    d = _dtype(var)
    return d is not None and jnp.issubdtype(d, jnp.floating)


def _is_int(var) -> bool:
    d = _dtype(var)
    return d is not None and jnp.issubdtype(d, jnp.integer)


class _TaintWalk:
    """One taint-propagation walk over a (closed) jaxpr tree."""

    def __init__(self, trace_name: str):
        self.trace_name = trace_name
        self.findings: List[Finding] = []
        self._seen: Set[Tuple[str, str, str]] = set()  # dedup per trace

    # -- findings ----------------------------------------------------------

    def _violate(self, rule: str, eqn, message: str, hint: str) -> None:
        key = (rule, eqn.primitive.name, message)
        if key in self._seen:
            return
        self._seen.add(key)
        self.findings.append(
            Finding(
                rule=rule,
                path=f"jaxpr:{self.trace_name}",
                line=0,
                symbol=self.trace_name,
                message=f"[{eqn.primitive.name}] {message}",
                hint=hint,
            )
        )

    # -- walk --------------------------------------------------------------

    def walk(self, jaxpr: jcore.Jaxpr, in_taints: Sequence[Set[str]]) -> List[Set[str]]:
        env: Dict[object, Set[str]] = {}

        def read(atom) -> Set[str]:
            if isinstance(atom, jcore.Literal):
                return set()
            return env.get(atom, set())

        def write(var, taints: Set[str]) -> None:
            if taints:
                env[var] = set(taints)

        n = min(len(jaxpr.invars), len(in_taints))
        for var, t in zip(jaxpr.invars[:n], in_taints[:n]):
            write(var, t)

        for eqn in jaxpr.eqns:
            self._eqn(eqn, read, write)
        return [read(v) for v in jaxpr.outvars]

    def _sub_jaxpr(self, obj) -> Optional[jcore.Jaxpr]:
        if isinstance(obj, jcore.Jaxpr):
            return obj
        inner = getattr(obj, "jaxpr", None)  # ClosedJaxpr
        return inner if isinstance(inner, jcore.Jaxpr) else None

    def _eqn(self, eqn, read, write) -> None:
        prim = eqn.primitive.name
        in_taints = [read(a) for a in eqn.invars]
        joined: Set[str] = set().union(*in_taints) if in_taints else set()

        if prim == "jit":
            name = eqn.params.get("name", "")
            if name in PACK_NAMES:
                for v in eqn.outvars:
                    write(v, {TAINT_PACKED})
                return
            if name in UNPACK_NAMES:
                return  # logical mantissas come out clean
            inner = self._sub_jaxpr(eqn.params.get("jaxpr"))
            if inner is not None:
                outs = self.walk(inner, in_taints)
                for v, t in zip(eqn.outvars, outs):
                    write(v, t)
                return

        elif prim == "scan":
            inner = self._sub_jaxpr(eqn.params.get("jaxpr"))
            if inner is not None:
                # invars = consts + carry + xs, outvars = carry + ys: 1:1
                outs = self.walk(inner, in_taints)
                for v, t in zip(eqn.outvars, outs):
                    write(v, t)
                return

        elif prim in ("cond", "switch"):
            branches = eqn.params.get("branches", ())
            merged: Optional[List[Set[str]]] = None
            for br in branches:
                inner = self._sub_jaxpr(br)
                if inner is None:
                    merged = None
                    break
                outs = self.walk(inner, in_taints[1:])  # invars[0] = index
                if merged is None:
                    merged = [set(t) for t in outs]
                else:
                    merged = [a | b for a, b in zip(merged, outs)]
            if merged is not None:
                for v, t in zip(eqn.outvars, merged):
                    write(v, t)
                return

        elif prim == "while":
            inner = self._sub_jaxpr(eqn.params.get("body_jaxpr"))
            if inner is not None:
                cn = eqn.params.get("cond_nconsts", 0)
                outs = self.walk(inner, in_taints[cn:])
                for v, t in zip(eqn.outvars, outs):
                    write(v, t)
                return

        elif prim.startswith("custom_jvp_call") or prim.startswith("custom_vjp_call"):
            inner = self._sub_jaxpr(
                eqn.params.get("call_jaxpr") or eqn.params.get("fun_jaxpr")
            )
            if inner is not None:
                outs = self.walk(inner, in_taints)
                for v, t in zip(eqn.outvars, outs):
                    write(v, t)
                return

        elif prim in ("remat", "remat2", "checkpoint"):
            inner = self._sub_jaxpr(eqn.params.get("jaxpr"))
            if inner is not None:
                outs = self.walk(inner, in_taints)
                for v, t in zip(eqn.outvars, outs):
                    write(v, t)
                return

        elif prim == "population_count":
            # the ONE legal consumer of packed words on the float side of the
            # engine: emits per-word set-bit counts (integer accumulators).
            for v in eqn.outvars:
                write(v, {TAINT_COUNTS})
            return

        elif prim == "pallas_call":
            # Trusted kernel boundary: internals are covered by the parity
            # tests against kernels/ref.py.  A kernel fed packed/counted
            # operands may exit in two legal ways: an integer accumulator
            # (staged kernels; tagged "counts"), or f32/f64 — the fused
            # kernel's on-chip affine epilogue.  bf16/f16 output would mean
            # the popcount accumulation was finished in a low-precision
            # float, losing exactness.
            if joined & {TAINT_PACKED, TAINT_COUNTS}:
                lowfp = [
                    v for v in eqn.outvars if _dtype(v) in _LOWFP_DTYPES
                ]
                if lowfp:
                    self._violate(
                        "INV-ACCUM-LOWFP",
                        eqn,
                        "packed/accumulator operands feed a Pallas kernel "
                        "with low-precision float output "
                        f"{[str(_dtype(v)) for v in lowfp]}",
                        "kernels must return integer accumulators or finish "
                        "the epilogue in f32 (the fused-kernel exit) — "
                        "bf16/f16 loses popcount exactness",
                    )
                for v in eqn.outvars:
                    if _is_int(v):
                        write(v, {TAINT_COUNTS})
            return

        if prim == "dot_general":
            var_ins = [a for a in eqn.invars if not isinstance(a, jcore.Literal)]
            if var_ins and all(_is_int(a) for a in var_ins):
                bad = [
                    v for v in eqn.outvars if _dtype(v) not in _INT_ACCUM_DTYPES
                ]
                if bad:
                    self._violate(
                        "INV-INT-DOT",
                        eqn,
                        "integer-operand dot_general accumulates in "
                        f"{[str(_dtype(v)) for v in bad]}",
                        "pass preferred_element_type=jnp.int32 — integer QMM "
                        "accumulation must stay exact (paper §III-A)",
                    )

        self._generic(eqn, joined, write)

    def _generic(self, eqn, joined: Set[str], write) -> None:
        """Default propagation: packed/counts flow through integer ops;
        floating outputs are either violations (packed; counts->bf16/f16)
        or the legal f32/f64 epilogue exit (counts); bool outputs
        (comparisons, masks) drop taint."""
        if not joined:
            return
        float_outs = [v for v in eqn.outvars if _is_float(v)]
        if TAINT_PACKED in joined:
            if float_outs:
                self._violate(
                    "INV-PACKED-FLOAT",
                    eqn,
                    "packed bit-plane words reach a floating-point value "
                    f"({[str(_dtype(v)) for v in float_outs]})",
                    "packed uint32 words are storage, not numbers: unpack "
                    "(core.packing.unpack_bits) or popcount before any "
                    "float math",
                )
            else:
                for v in eqn.outvars:
                    if _is_int(v):
                        write(v, {TAINT_PACKED})
        if TAINT_COUNTS in joined:
            lowfp = [v for v in float_outs if _dtype(v) in _LOWFP_DTYPES]
            if lowfp:
                self._violate(
                    "INV-ACCUM-LOWFP",
                    eqn,
                    "integer accumulator converted to "
                    f"{[str(_dtype(v)) for v in lowfp]} — low-precision float "
                    "accumulation loses popcount exactness",
                    "keep accumulators int32; cast to f32 (not bf16/f16) in "
                    "the affine epilogue",
                )
            for v in eqn.outvars:
                if _is_int(v):
                    write(v, {TAINT_COUNTS})


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def _seed_taints(jaxpr: jcore.Jaxpr, packed_argnums: Sequence[int]) -> List[Set[str]]:
    """uint32 trace inputs are packed storage (the only uint32 arrays in the
    datapath); ``packed_argnums`` adds explicit flat positions."""
    taints: List[Set[str]] = []
    for i, var in enumerate(jaxpr.invars):
        t: Set[str] = set()
        if _dtype(var) == jnp.dtype(jnp.uint32) or i in packed_argnums:
            t.add(TAINT_PACKED)
        taints.append(t)
    return taints


def check_function(
    fn,
    *args,
    name: str = "fn",
    packed_argnums: Sequence[int] = (),
) -> List[Finding]:
    """Trace ``fn(*args)`` (args may be ShapeDtypeStructs) and taint-walk it.

    ``packed_argnums`` marks additional *flattened* input positions as packed
    bit-planes; uint32 inputs are seeded automatically.
    """
    closed = jax.make_jaxpr(fn)(*args)
    walk = _TaintWalk(name)
    walk.walk(closed.jaxpr, _seed_taints(closed.jaxpr, packed_argnums))
    return walk.findings


def _compare_cache(init_sds, out_sds, trace_name: str) -> List[Finding]:
    """Pathwise dtype/shape compare of an output cache against its init."""
    path = f"jaxpr:{trace_name}"
    init_leaves, init_tree = jax.tree_util.tree_flatten_with_path(init_sds)
    out_leaves, out_tree = jax.tree_util.tree_flatten_with_path(out_sds)
    if init_tree != out_tree:
        return [
            Finding(
                rule="INV-CACHE-STRUCT",
                path=path,
                line=0,
                symbol=trace_name,
                message="cache pytree structure changed across the step",
                hint="steps must return the cache with the exact init "
                "structure (slots are splice-updated in place)",
            )
        ]
    out: List[Finding] = []
    for (kp, a), (_, b) in zip(init_leaves, out_leaves):
        leaf = jax.tree_util.keystr(kp)
        if jnp.dtype(a.dtype) != jnp.dtype(b.dtype):
            out.append(
                Finding(
                    rule="INV-CACHE-DTYPE",
                    path=path,
                    line=0,
                    symbol=leaf,
                    message=f"cache leaf {leaf} initialized {a.dtype} but the "
                    f"step writes {b.dtype}",
                    hint="derive the write dtype from the cache leaf "
                    "(cache[...].dtype), never a literal — init/write drift "
                    "makes batched decode diverge (the PR 6 bug)",
                )
            )
        if tuple(a.shape) != tuple(b.shape):
            out.append(
                Finding(
                    rule="INV-CACHE-SHAPE",
                    path=path,
                    line=0,
                    symbol=leaf,
                    message=f"cache leaf {leaf} initialized {tuple(a.shape)} "
                    f"but the step returns {tuple(b.shape)}",
                    hint="cache leaves are fixed-capacity ring/linear "
                    "buffers; steps may not grow them",
                )
            )
    return out


def check_cache_contract(init_thunk, step_fn, *step_args, name: str = "cache"):
    """``init_thunk() -> cache``; ``step_fn(cache, *step_args) -> cache``.

    Both run under ``jax.eval_shape`` (abstract — no FLOPs); returns
    INV-CACHE-* findings for any dtype/shape/structure drift.
    """
    init_sds = jax.eval_shape(init_thunk)
    out_sds = jax.eval_shape(step_fn, init_sds, *step_args)
    return _compare_cache(init_sds, out_sds, name)


# ---------------------------------------------------------------------------
# backend sweep
# ---------------------------------------------------------------------------

_M, _K, _N = 8, 64, 16


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _backend_cases(backend: str):
    """(case_name, fn, args) triples: one per paper QMM type x precision."""
    kw = packing.packed_len(_K, 1)

    def w1a8(xm, xs, xo, wp, ws, wo, colsum):
        x = QuantTensor(mantissa=xm, scale=xs, offset=xo, bits=8)
        w = QuantTensor(
            mantissa=wp, scale=ws, offset=wo, bits=1,
            packed=True, packed_axis=0, length=_K,
        )
        return QE.qmm(x, w, backend=backend, w_colsum=colsum)

    def w1a1(xm, xs, xo, wp, ws, wo):
        x = QuantTensor(mantissa=xm, scale=xs, offset=xo, bits=1)
        w = QuantTensor(
            mantissa=wp, scale=ws, offset=wo, bits=1,
            packed=True, packed_axis=0, length=_K,
        )
        return QE.qmm(x, w, backend=backend)

    def a8a8(xm, xs, xo, ym, ys, yo):
        x = QuantTensor(mantissa=xm, scale=xs, offset=xo, bits=8)
        y = QuantTensor(mantissa=ym, scale=ys, offset=yo, bits=8)
        return QE.qmm(x, y, backend=backend)

    f32 = jnp.float32
    return [
        (
            "w1a8",  # act x weight, the dense-layer QMM
            w1a8,
            (
                _sds((_M, _K), jnp.uint8), _sds((_M, 1), f32), _sds((_M, 1), f32),
                _sds((kw, _N), jnp.uint32), _sds((1, _N), f32), _sds((1, _N), f32),
                _sds((_N,), jnp.int32),
            ),
        ),
        (
            "w1a1",  # fully binary
            w1a1,
            (
                _sds((_M, _K), jnp.uint8), _sds((), f32), _sds((), f32),
                _sds((kw, _N), jnp.uint32), _sds((1, _N), f32), _sds((1, _N), f32),
            ),
        ),
        (
            "a8a8",  # act x act, the QMM type prior accelerators lack (§II)
            a8a8,
            (
                _sds((_M, _K), jnp.uint8), _sds((), f32), _sds((), f32),
                _sds((_K, _N), jnp.uint8), _sds((), f32), _sds((), f32),
            ),
        ),
    ]


def verify_backends(backends: Optional[Sequence[str]] = None) -> List[Finding]:
    """Taint-walk every registered QMM backend across the QMM-type grid.

    The sweep enumerates the registry's qmm family — a newly registered
    QMM backend is verified with zero edits here.  Scores-family backends
    have a different calling convention and are covered by
    :func:`verify_binary_attention`."""
    out: List[Finding] = []
    for backend in backends or backend_registry.backend_names(family="qmm"):
        for case, fn, args in _backend_cases(backend):
            out.extend(
                check_function(fn, *args, name=f"backend:{backend}:{case}")
            )
    return out


# ---------------------------------------------------------------------------
# model-zoo arch sweep
# ---------------------------------------------------------------------------

# batch / cache capacity / prompt length for the serving traces — the prompt
# must cover patch_stub splicing (encoder.n_positions patches over the prefix)
_B, _T, _S = 2, 32, 16


def _default_archs() -> Tuple[str, ...]:
    from repro.configs import ASSIGNED

    return ASSIGNED + ("bit-bert-base",)


# resolved lazily so importing the verifier doesn't import every config
DEFAULT_ARCHS: Tuple[str, ...] = ()


def _scores_only_backend(name: str) -> bool:
    """Is ``name`` a scores-family-only backend (a bitwise-attention
    engagement when it appears on an attn site record)?"""
    try:
        spec = backend_registry.get_backend(name)
    except (KeyError, ValueError):
        return False
    return "scores" in spec.families and "qmm" not in spec.families


def _site_findings(sites: Sequence[dict], cfg, trace_name: str) -> List[Finding]:
    path = f"jaxpr:{trace_name}"
    out: List[Finding] = []

    def add(rule, symbol, message, hint):
        out.append(
            Finding(
                rule=rule, path=path, line=0, symbol=symbol,
                message=message, hint=hint,
            )
        )

    for s in sites:
        kind = s.get("kind", "")
        site = s.get("site", "")
        bits = s.get("bits")
        mdt = s.get("mantissa_dtype", "")
        if kind == "qlinear":
            if not site:
                add(
                    "INV-SITE-NAME",
                    "<unnamed>",
                    "serve-mode qlinear QMM ran at an unnamed site",
                    "pass name= at the call site — unnamed sites cannot "
                    "receive backend_overrides or autotune phase tags",
                )
                continue
            if bits != s.get("cfg_bits"):
                add(
                    "INV-SITE-BITS",
                    site,
                    f"site quantized activations to {bits} bits but the "
                    f"QuantConfig declares act_bits={s.get('cfg_bits')}",
                    "per-site precision overrides are not part of the "
                    "engine contract; fix the call site or the config",
                )
            expected = "uint8" if (bits or 0) <= 8 else "int32"
            if mdt != expected:
                add(
                    "INV-SITE-MANTISSA",
                    site,
                    f"site produced mantissa dtype {mdt}, quantizer contract "
                    f"says {expected} for {bits}-bit activations",
                    "quantize_activation stores uint8 mantissas up to 8 "
                    "bits; wider precisions use int32",
                )
        elif kind == "attn":
            if _scores_only_backend(s.get("backend", "auto")):
                # bitwise engagement: the site elastically binarizes Q to
                # 1 bit by family contract, whatever attn_act_bits says
                if bits != 1:
                    add(
                        "INV-SITE-BITS",
                        site,
                        f"bitwise attention site ran at {bits} bits; a "
                        "scores-family engagement binarizes to exactly 1",
                        "scores backends consume packed 1-bit planes — the "
                        "site must quantize with bits=1",
                    )
                expected = "uint8"
                hint = (
                    "elastic binarization stores {0,1} uint8 mantissas; "
                    "re-centering does not apply at 1 bit"
                )
            else:
                if bits != cfg.quant.attn_act_bits:
                    add(
                        "INV-SITE-BITS",
                        site,
                        f"attention act x act QMM ran at {bits} bits but "
                        f"attn_act_bits={cfg.quant.attn_act_bits}",
                        "the act x act precision is a single engine mode knob "
                        "(QuantConfig.attn_act_bits)",
                    )
                expected = "int8" if (bits or 0) > 1 else "uint8"
                hint = (
                    "Q.recenter must run before the integer attention MM "
                    "so mantissas fit the int8 MXU path"
                )
            if mdt != expected:
                add(
                    "INV-SITE-MANTISSA",
                    site,
                    f"attention site mantissa dtype {mdt}, expected {expected}",
                    hint,
                )
    return out


def verify_arch(name: str) -> List[Finding]:
    """Trace one model-zoo arch's smoke-size prefill + decode and enforce
    the packed/accum taints, the cache init-vs-write contract, and the
    per-site quantization log."""
    from repro.configs import get_config
    from repro.configs.smoke import smoke_variant
    from repro.models import model_zoo as Z

    cfg = smoke_variant(get_config(name))
    if not cfg.has_decoder:
        return []  # encoder-only: no serving step to verify (assignment rule)

    key = _sds((2,), jnp.uint32)
    sp = jax.eval_shape(
        lambda k: Z.init_serving_params(k, cfg), key
    )
    init_cache = jax.eval_shape(lambda: Z.init_cache(_B, _T, cfg))
    tokens = _sds((_B, _S), jnp.int32)
    frontend = None
    if cfg.encoder is not None:
        d_in = cfg.encoder.d_input or cfg.d_model
        frontend = _sds((_B, cfg.encoder.n_positions, d_in), jnp.float32)

    findings: List[Finding] = []

    # ---- prefill ----
    trace = f"arch:{name}:prefill"
    with site_log.recording() as sites:
        closed, out_shape = jax.make_jaxpr(
            lambda p, t, c, f: Z.prefill(p, t, cfg, c, f), return_shape=True
        )(sp, tokens, init_cache, frontend)
    walk = _TaintWalk(trace)
    walk.walk(closed.jaxpr, _seed_taints(closed.jaxpr, ()))
    findings.extend(walk.findings)
    findings.extend(_compare_cache(init_cache, out_shape[1], trace))
    findings.extend(_site_findings(sites, cfg, trace))

    # ---- decode ----
    trace = f"arch:{name}:decode"
    tok1 = _sds((_B,), jnp.int32)
    with site_log.recording() as sites:
        closed, out_shape = jax.make_jaxpr(
            lambda p, t, c: Z.decode_step(p, t, cfg, c), return_shape=True
        )(sp, tok1, init_cache)
    walk = _TaintWalk(trace)
    walk.walk(closed.jaxpr, _seed_taints(closed.jaxpr, ()))
    findings.extend(walk.findings)
    findings.extend(_compare_cache(init_cache, out_shape[1], trace))
    findings.extend(_site_findings(sites, cfg, trace))
    return findings


def verify_archs(names: Optional[Sequence[str]] = None) -> List[Finding]:
    out: List[Finding] = []
    for name in names or _default_archs():
        out.extend(verify_arch(name))
    return out


# ---------------------------------------------------------------------------
# bitwise-attention sweep (scores backend family)
# ---------------------------------------------------------------------------


def verify_binary_attention() -> List[Finding]:
    """Taint-walk the bitwise-attention path: every scores-family core, plus
    prefill/decode of the 1-bit encoder arch with ``attn.qk -> "binary"``.

    The scores family has its own calling convention (packed rank-4 planes
    in, int32 counts out), so :func:`verify_backends` cannot sweep it; and
    bit-bert-base is encoder-family (the serving arch sweep skips it), so
    the model traces run here directly.  Site assertions: every ``attn.qk``
    record must carry the binary engagement at exactly 1 bit, and the packed
    K cache must round-trip the cache contract.
    """
    import dataclasses
    import functools

    findings: List[Finding] = []

    # ---- every registered scores core keeps the packed/counts taints ----
    q_sds = _sds((1, 4, 6, 2), jnp.uint32)  # (B, H, S, dw) — dh = 48
    k_sds = _sds((1, 2, 5, 2), jnp.uint32)  # (B, G, T, dw), GQA G < H
    for name in backend_registry.backend_names(family="scores"):
        spec = backend_registry.get_backend(name)
        findings.extend(
            check_function(
                functools.partial(spec.run_scores, dh=48),
                q_sds,
                k_sds,
                name=f"scores:{name}",
            )
        )

    # ---- model traces with the binary engagement ----
    from repro.configs import get_config
    from repro.configs.smoke import smoke_variant
    from repro.models import model_zoo as Z

    base = smoke_variant(get_config("bit-bert-base"))
    cfg = dataclasses.replace(
        base,
        quant=dataclasses.replace(
            base.quant, backend_overrides=(("attn.qk", "binary"),)
        ),
    )

    key = _sds((2,), jnp.uint32)
    sp = jax.eval_shape(
        lambda k: Z.init_serving_params(k, cfg), key
    )
    init_cache = jax.eval_shape(lambda: Z.init_cache(_B, _T, cfg))

    def trace(trace_name: str, fn, *args) -> None:
        with site_log.recording() as sites:
            closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*args)
        walk = _TaintWalk(trace_name)
        walk.walk(closed.jaxpr, _seed_taints(closed.jaxpr, ()))
        findings.extend(walk.findings)
        findings.extend(_compare_cache(init_cache, out_shape[1], trace_name))
        findings.extend(_site_findings(sites, cfg, trace_name))
        qk = [s for s in sites if s.get("site") == "attn.qk"]
        path = f"jaxpr:{trace_name}"
        if not qk:
            findings.append(
                Finding(
                    rule="INV-SITE-NAME",
                    path=path,
                    line=0,
                    symbol="attn.qk",
                    message="binary-attention trace recorded no attn.qk site",
                    hint="the override did not engage — check "
                    "QuantConfig.backend_for and _binary_scores_site",
                )
            )
        for s in qk:
            if s.get("backend") != "binary" or s.get("bits") != 1:
                findings.append(
                    Finding(
                        rule="INV-SITE-BITS",
                        path=path,
                        line=0,
                        symbol="attn.qk",
                        message="attn.qk record is not the binary engagement "
                        f"(backend={s.get('backend')!r}, bits={s.get('bits')})",
                        hint="backend_overrides=(('attn.qk', 'binary'),) must "
                        "reach the site and binarize to 1 bit",
                    )
                )

    tokens = _sds((_B, _S), jnp.int32)
    trace(
        "binary-attn:prefill",
        lambda p, t, c: Z.prefill(p, t, cfg, c),
        sp,
        tokens,
        init_cache,
    )
    tok1 = _sds((_B,), jnp.int32)
    trace(
        "binary-attn:decode",
        lambda p, t, c: Z.decode_step(p, t, cfg, c),
        sp,
        tok1,
        init_cache,
    )
    return findings

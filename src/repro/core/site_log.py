"""Trace-time QMM site log — the hook the static verifier listens on.

Every serve-mode QMM site (dense ``qlinear`` projections, attention
act x act products) reports what it is about to execute: the site name,
the activation precision it quantized to, the mantissa dtype it produced,
and the backend dispatch resolved.  Recording is off by default and costs
one contextvar read per site; ``repro.analysis.verifier`` wraps its
abstract prefill/decode traces in :func:`recording` and then checks the
collected sites against the declared ``QuantConfig`` invariants (precision
per named site, mantissa-dtype contract, named-site coverage).

This lives in ``core`` (not ``analysis``) so model code never imports the
analysis package — the dependency points one way: analysis observes models.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Dict, List, Optional

__all__ = ["recording", "record", "is_recording"]

_LOG: contextvars.ContextVar[Optional[List[Dict]]] = contextvars.ContextVar(
    "qmm_site_log", default=None
)


def is_recording() -> bool:
    return _LOG.get() is not None


@contextlib.contextmanager
def recording():
    """Collect site records emitted while the block runs (trace or execute).

    Yields the list the sites append to; nested recordings shadow the outer
    one (each verifier trace sees only its own sites).
    """
    token = _LOG.set([])
    try:
        yield _LOG.get()
    finally:
        _LOG.reset(token)


def record(**fields) -> None:
    """Append one site record if a recording is active (no-op otherwise).

    Canonical fields (see verifier.check_sites):
      kind: "qlinear" | "attn"
      site: dotted site name ("ffn.up", "attn.qk", ...); "" = unnamed
      bits: activation precision the site actually used
      cfg_bits: the precision QuantConfig declares for this site class
      mantissa_dtype: str dtype of the quantized mantissa fed to the engine
      backend: resolved backend string (qlinear sites only)
      int_core: on qlinear sites, "packed" where the ``mxu`` backend's
        decode kernel read the packed weight and unpacked it in VMEM, else
        "unpacked" (``qmm.int_core``); on attention sites, "decode_attn"
        where single-token decode ran in the ``decode_attn`` kernel
        (``attention.decode_core_engages``), else "xla"
    """
    log = _LOG.get()
    if log is not None:
        log.append(dict(fields))

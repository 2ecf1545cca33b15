"""Profiler tracing: named emulation phases, host spans, run-level capture.

Two scope flavors, both no-ops when the obs layer is disabled:

* :func:`phase_scope` — ``jax.named_scope``: attaches a name to the ops
  staged under it, so xprof/Perfetto shows ``ozimmu/split``,
  ``ozimmu/group_gemm``, ``ozimmu/product_reduce``, ``ozimmu/ladder``,
  ``ozimmu/scale_accum`` blocks inside the compiled program.  Pure
  metadata: the lowered HLO computes the same values in the same order,
  which keeps the bitwise contract (tests assert identity with obs on vs
  off).  Works for both the XLA path and the fused Pallas pipeline —
  the kernel calls are staged under the same scopes.
* :func:`span` — ``jax.profiler.TraceAnnotation``: brackets *host* work
  (the serving runtime's round and its phases, ``serve/*``) on the host
  plane of the profiler's trace, on the device timeline's clock.  Integer
  counts given as keyword arguments become the span's stats, recorded at
  the same boundary as the span.  Nested spans on one thread nest in the
  trace; with no trace running the annotation records nothing.

Run-level capture: :func:`profile` brackets a whole run with
``jax.profiler.start_trace/stop_trace`` (the ``--profile-dir`` flag on
serve/train).  A trace that was asked for and cannot start or stop
raises: a run without the trace it was launched to capture is a failed
run, not a quiet one.  Only :func:`span` degrades silently.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

from repro.obs import registry as _registry

__all__ = ["phase_scope", "span", "profile", "PHASES"]

# The emulation pipeline's phase names (docs/observability.md): every
# phase_scope is ozimmu/<one of these>.
PHASES = ("split", "group_gemm", "product_reduce", "ladder", "scale_accum")

_NULL = contextlib.nullcontext()


def phase_scope(name: str):
    """In-graph scope naming the ops staged under it (trace-time only;
    compiled executions carry the name for free)."""
    if not _registry.enabled():
        return _NULL
    return jax.named_scope(f"ozimmu/{name}")


def span(name: str, **counts: int):
    """Host span ``name`` carrying ``counts`` as its stats (written only
    while a trace runs)."""
    if not _registry.enabled():
        return _NULL
    try:
        return jax.profiler.TraceAnnotation(name, **counts)
    except Exception:  # profiler backend unavailable
        return _NULL


@contextlib.contextmanager
def profile(trace_dir: Optional[str]):
    """Bracket a run with ``jax.profiler.start_trace/stop_trace`` when
    ``trace_dir`` is set; plain passthrough when None.  Errors from
    either call propagate."""
    if not trace_dir:
        yield
        return
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()

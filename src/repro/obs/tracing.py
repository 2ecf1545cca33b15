"""Profiler tracing: named emulation phases + run-level trace capture.

Two scope flavors, both no-ops when the obs layer is disabled:

* :func:`phase_scope` — ``jax.named_scope``: attaches a name to the ops
  staged under it, so xprof/Perfetto shows ``ozimmu/split``,
  ``ozimmu/group_gemm``, ``ozimmu/ladder``, ``ozimmu/scale_accum``
  blocks inside the compiled program.  Pure metadata: the lowered HLO
  computes the same values in the same order, which keeps the bitwise
  contract (tests assert identity with obs on vs off).  Works for both
  the XLA path and the fused Pallas pipeline — the kernel calls are
  staged under the same scopes.
* :func:`host_scope` — ``jax.profiler.TraceAnnotation``: brackets *host*
  work (splitter dispatch, cache freezes) on the profiler timeline.

Run-level capture: :func:`profile` brackets a whole run with
``jax.profiler.start_trace/stop_trace`` (the ``--profile-dir`` flag on
serve/train).  A trace that was asked for and cannot start or stop
raises: a run without the trace it was launched to capture is a failed
run, not a quiet one.  Only :func:`host_scope` degrades silently.
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax

from repro.obs import registry as _registry

__all__ = ["phase_scope", "host_scope", "profile", "PHASES"]

# The emulation pipeline's phase names (docs/observability.md): every
# scope this module emits is ozimmu/<one of these>.
PHASES = ("split", "group_gemm", "ladder", "scale_accum")

_NULL = contextlib.nullcontext()


def phase_scope(name: str):
    """In-graph scope naming the ops staged under it (trace-time only;
    compiled executions carry the name for free)."""
    if not _registry.enabled():
        return _NULL
    return jax.named_scope(f"ozimmu/{name}")


def host_scope(name: str):
    """Host-side profiler annotation (shows up on the python thread's
    timeline during an active trace)."""
    if not _registry.enabled():
        return _NULL
    try:
        return jax.profiler.TraceAnnotation(f"ozimmu/{name}")
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

"""The program's own host spans in a traced run (``serve/*``), with their
stats.

The serving runtime writes a span around each scheduler round and each of
its phases (``repro.obs.tracing.span``; docs/observability.md) on the
profiler's host plane, on the clock of the device's events; integer
counts ride on a span as its stats.  The harness's reduction
(``bench/trace.py``) keeps only the harness's own ``bench/*`` spans, and a
metric reader gets that reduction, not the file.  So the readers of the
program's spans read them here, from the trace the run wrote: the
``.xplane.pb`` under a ``bench_trace_*`` directory of the temporary
directory (where ``bench/run.py`` traces) whose ``bench/traced`` window is
the reduction's.  A program that writes no such spans, or a run whose file
cannot be found, gives no spans, and the readers then read nothing.

This module is temporary: a second reader of the trace beside
``bench/trace.py``.  It depends on what the ``View`` contract does not
state: that ``bench/run.py`` traces into ``tempfile.mkdtemp(prefix=
"bench_trace_")`` and that the file is still there when the readers run.
It goes once ``trace.reduce_profile`` keeps the ``serve/*`` spans with
their stats in the reduction; ``_find``, ``window_of`` and
``read_profile`` then have no use.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import tempfile
from typing import Any, Dict, List, Optional, Sequence, Tuple

from bench import trace as T

PREFIX = "serve/"

# spans of each trace read so far, by its traced window
_READ: Dict[Tuple[float, float], List["Span"]] = {}


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float          # ns, clipped to the window
    end: float            # ns, clipped to the window
    args: Tuple[Tuple[str, Any], ...] = ()

    @property
    def dur(self) -> float:
        return self.end - self.start

    def arg(self, name: str) -> Any:
        return dict(self.args).get(name)


def window_of(pd) -> Optional[Tuple[float, float]]:
    """The traced window of a ``ProfileData``, as ``reduce_profile``
    takes it: the first ``bench/traced`` span."""
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == T.WINDOW_SPAN:
                        return (e.start_ns, e.start_ns + e.duration_ns)
    return None


def read_profile(pd, window: Tuple[float, float]) -> List[Span]:
    """The program's spans of a ``ProfileData`` inside ``window``, clipped
    to it, in order of start."""
    lo, hi = window
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if not e.name.startswith(PREFIX):
                    continue
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if t > lo and s < hi:
                    out.append(Span(e.name, max(s, lo), min(t, hi),
                                    tuple(e.stats)))
    return sorted(out, key=lambda sp: sp.start)


def spans_of(view) -> List[Span]:
    """The program's spans of the run whose trace ``view.trace`` reduces."""
    window = tuple(view.trace.window)
    if window not in _READ:
        _READ[window] = _find(window)
    return _READ[window]


def _find(window: Tuple[float, float]) -> List[Span]:
    from jax.profiler import ProfileData
    pattern = os.path.join(tempfile.gettempdir(), "bench_trace_*", "plugins",
                           "profile", "*", "*.xplane.pb")
    for path in sorted(glob.glob(pattern), key=os.path.getmtime,
                       reverse=True):
        pd = ProfileData.from_file(path)
        if window_of(pd) == window:
            return read_profile(pd, window)
    return []


def named(spans: Sequence[Span], name: str) -> List[Span]:
    return [sp for sp in spans if sp.name == name]


def idle_during(red: "T.Reduced", spans: Sequence[Span]) -> float:
    """Seconds of the window in which device 0 ran no op while one of
    ``spans`` was open."""
    if not red.devices:
        return 0.0
    lo, hi = red.window
    idle = T.gaps([(e.start, e.end) for e in red.ops
                   if e.device == red.devices[0]], lo, hi)
    # the union of the spans: what their own gaps leave of the window
    open_ = T.gaps(T.gaps([(sp.start, sp.end) for sp in spans], lo, hi),
                   lo, hi)
    return sum(max(0.0, min(e, oe) - max(s, os_))
               for s, e in idle for os_, oe in open_
               if s < oe and os_ < e) * 1e-9

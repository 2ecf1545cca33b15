"""From a profiler trace to device busy time, kernel time and idle gaps.

The JAX profiler writes one ``.xplane.pb`` per traced stretch.  Its
device planes (``/device:TPU:<n>``) carry a line ``XLA Ops`` with one
event per operation executed on the chip and a line ``XLA Modules`` with
one event per execution of a compiled program.  The host plane carries
the harness's own spans (``bench/<name>``, written by ``Run.span`` as
``TraceAnnotation``s) on the same clock.

The reduction keeps what the metric readers need:

* the traced window: the ``bench/traced`` span;
* per device, the union of its op intervals inside the window (busy);
* op events by HLO instruction name (``group_gemm.6``; the event's own
  name is the whole instruction, ``%group_gemm.6 = s32[...]
  custom-call(...)``) and module events by program name;
* idle gaps: the stretches of the window in which no op ran on a device,
  each labelled by the innermost harness span that covers its middle.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench/traced"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
# ops whose events span the ops of their bodies (counted once in busy
# time through the union, left out of the per-op breakdown)
CONTAINER_OPS = ("while", "conditional", "call")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str             # an op's HLO instruction name, a module's name
    start: float          # ns
    end: float            # ns
    device: int = -1
    text: str = ""        # an op's whole HLO instruction

    @property
    def dur(self) -> float:
        return self.end - self.start


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Reduced:
    window: Tuple[float, float]
    devices: List[int]
    ops: List[Event]
    modules: List[Event]
    spans: List[Event]

    # -- the window and the device -----------------------------------------

    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _busy_intervals(self, device: int):
        return [(e.start, e.end) for e in self.ops if e.device == device]

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(union_length(self._busy_intervals(d))
                  for d in self.devices)
        return tot / len(self.devices) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s()

    # -- ops, kernels and programs ----------------------------------------

    def op_seconds(self, events: Sequence[Event]) -> float:
        """Device seconds of these events, averaged over the devices."""
        return sum(e.dur for e in events) * 1e-9 / max(len(self.devices), 1)

    def kernel_events(self, kernel: str) -> List[Event]:
        """A Pallas kernel's events: Mosaic custom calls whose HLO
        instruction carries the kernel's name (``group_gemm.6``)."""
        return [e for e in self.ops
                if base_name(e.name) == kernel and KERNEL_TARGET in e.text]

    def modules_named(self, prefix: str) -> List[Event]:
        return [e for e in self.modules if e.name.startswith(prefix)]

    def modules_running_kernels(self, modules: Sequence[Event]
                                ) -> List[Event]:
        """The modules in whose interval a Mosaic kernel ran."""
        starts = sorted(e.start for e in self.ops if KERNEL_TARGET in e.text)
        out = []
        for m in modules:
            i = bisect.bisect_left(starts, m.start)
            if i < len(starts) and starts[i] <= m.end:
                out.append(m)
        return out

    # -- host spans --------------------------------------------------------

    def span_label(self, t: float) -> str:
        """The innermost harness span that covers time t."""
        best = None
        for s in self.spans:
            if s.start <= t <= s.end and s.name != WINDOW_SPAN:
                if best is None or s.dur < best.dur:
                    best = s
        return best.name if best is not None else "outside spans"

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """(label, seconds) of every idle stretch on device 0's timeline."""
        if not self.devices:
            return []
        lo, hi = self.window
        return [(self.span_label((s + e) / 2), (e - s) * 1e-9)
                for s, e in gaps(self._busy_intervals(self.devices[0]),
                                 lo, hi)]

    def breakdown(self, n: int = 10) -> Dict[str, list]:
        by_op: Dict[str, float] = {}
        for e in self.ops:
            key = base_name(e.name)
            if key in CONTAINER_OPS:
                continue
            by_op[key] = by_op.get(key, 0.0) + e.dur
        top = sorted(by_op.items(), key=lambda kv: -kv[1])[:n]
        nd = max(len(self.devices), 1)
        idle = sorted(self.idle_gaps(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v * 1e-9 / nd] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


def base_name(name: str) -> str:
    """``group_gemm.6`` -> ``group_gemm``; ``fusion`` stays."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def op_name(text: str) -> str:
    """The instruction name of an op event: ``%group_gemm.6 = ...`` ->
    ``group_gemm.6``."""
    return text.split(" = ", 1)[0].lstrip("%")


def reduce_profile(pd) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    ops: List[Event] = []
    modules: List[Event] = []
    spans: List[Event] = []
    devices: List[int] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            tail = plane.name[len(DEVICE_PREFIX):]
            if not tail.isdigit():
                continue
            dev = int(tail)
            devices.append(dev)
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend(Event(op_name(e.name), e.start_ns,
                                     e.start_ns + e.duration_ns, dev,
                                     e.name) for e in line.events)
                elif line.name == MODULE_LINE:
                    modules.extend(Event(e.name, e.start_ns,
                                         e.start_ns + e.duration_ns, dev)
                                   for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns,
                                   e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith("bench/"))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[0].end)
    elif ops:
        window = (min(e.start for e in ops), max(e.end for e in ops))
    else:
        window = (0.0, 0.0)
    lo, hi = window

    def clip(events):
        return [dataclasses.replace(e, start=max(e.start, lo),
                                    end=min(e.end, hi))
                for e in events if e.end > lo and e.start < hi]

    return Reduced(window=window, devices=sorted(devices), ops=clip(ops),
                   modules=clip(modules), spans=spans)


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path))


@dataclasses.dataclass
class View:
    """What a per-layer metric reader gets."""
    trace: Reduced
    record: Dict[str, Any]
    peaks: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    chips: int


def serve_programs(red: Reduced) -> Tuple[List[Event], List[Event]]:
    """The serving runtime's decode and prefill program executions: the
    decode step is jitted as ``_decode_paged_impl``; the prefill chunk
    programs and the cache write-back are both jitted as ``impl``, and
    only the former run the emulation's kernels."""
    decode = red.modules_named("jit__decode")
    prefill = red.modules_running_kernels(red.modules_named("jit_impl("))
    return decode, prefill


def share(numerator: float, denominator: float) -> Optional[float]:
    """A percentage, or None where there is nothing to divide by."""
    if not denominator or denominator <= 0 or numerator <= 0:
        return None
    return 100.0 * numerator / denominator

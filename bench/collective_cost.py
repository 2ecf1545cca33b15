"""Bytes that the emulated GEMM's cross-chip reduction needs, from shapes;
the chip-to-chip bandwidth they are held against; and the reduction's
collective ops in a reduced trace.

The sharded ``@model/int32`` path reduces each of the G int32 group
products of an (m, p) result by a reduce-scatter over C's rows
(``repro.distributed.collectives.psum_exact_int32``).  What the algorithm
needs, each partial sent once towards the chip that keeps its rows: a
chip sends (D - 1)/D of each product, 4 bytes an entry, and receives as
much.  The roofline holds sent and received together against the chip's
whole interconnect bandwidth, both directions of every link, so that
the share cannot pass 100%.

ICI bandwidth of one TPU v5e chip: 1,600 Gbit/s (Google Cloud
documentation, "TPU v5e": inter-chip interconnect bandwidth per chip).

In the trace the reduction is the collective ops whose result is int32
(the digit grids' ``pmax`` is float32).  A synchronous collective is one
op event; an asynchronous one runs from its ``-start`` event to the end
of its ``-done`` event, which names the start as its operand.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

from bench import trace as T

ICI_BYTES_PER_S = {"TPU v5 lite": 1600e9 / 8}

COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
               "collective-permute", "all-to-all")
_OP = re.compile(r"^%?\S+ = (.*?) ([a-z][a-z0-9-]*)\((%[\w.-]+)?")


def reduce_scatter_bytes_sent(m: int, p: int, groups: int,
                              chips: int) -> float:
    """int32 bytes one chip sends to reduce-scatter ``groups`` (m, p)
    products over ``chips``."""
    return float(groups * m * p * 4 * (chips - 1) // chips)


def link_share(nbytes_moved: float, seconds: float, bytes_per_s: float):
    """Percent of the interconnect's bandwidth that moving
    ``nbytes_moved`` (sent and received) in ``seconds`` reaches, or None
    without bytes or time."""
    if seconds <= 0 or nbytes_moved <= 0:
        return None
    return 100.0 * nbytes_moved / bytes_per_s / seconds


def _collective(text: str):
    """(kind, operand) of an int32 collective op's HLO text, kind one of
    "sync", "start", "done"; None for any other op."""
    hit = _OP.match(text)
    if not hit or "s32[" not in hit.group(1):
        return None
    op = hit.group(2)
    for base in COLLECTIVES:
        if op == base:
            return "sync", None
        if op == base + "-start":
            return "start", None
        if op == base + "-done":
            return "done", (hit.group(3) or "").lstrip("%")
    return None


def reduction_intervals(red: "T.Reduced") -> Dict[int, List[Tuple[float,
                                                                  float]]]:
    """Per device, the intervals (ns) in which an int32 collective of the
    reduction runs; devices without one are left out."""
    out: Dict[int, List[Tuple[float, float]]] = {}
    starts: Dict[Tuple[int, str], float] = {}
    for e in sorted(red.ops, key=lambda e: e.start):
        kind = _collective(e.text)
        if kind is None:
            continue
        if kind[0] == "start":
            starts[(e.device, e.name)] = e.start
            continue
        begin = starts.pop((e.device, kind[1]), e.start) \
            if kind[0] == "done" else e.start
        out.setdefault(e.device, []).append((begin, e.end))
    return out


def exposed_ns(red: "T.Reduced", device: int,
               intervals: List[Tuple[float, float]]) -> float:
    """Nanoseconds of the window in which ``device`` runs a collective of
    the reduction (``intervals``) and no other op."""
    lo, hi = red.window
    others = [(e.start, e.end) for e in red.ops if e.device == device
              and _collective(e.text) is None
              and T.base_name(e.name) not in T.CONTAINER_OPS]
    free = T.gaps(others, lo, hi)
    busy = T.gaps(T.gaps(intervals, lo, hi), lo, hi)   # their union
    return sum(max(0.0, min(e, fe) - max(s, fs))
               for s, e in busy for fs, fe in free)

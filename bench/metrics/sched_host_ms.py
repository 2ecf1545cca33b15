"""sched_host_ms: the serving scheduler's host time per round.

The program's spans ``serve/admit`` and the ``prepare`` and ``commit``
phases of prefill and decode (admission, block allocation and
copy-on-write, the host arrays of a call, the scheduler's updates after
it), summed over the traced window and divided by the ``serve/step``
spans there (``bench/program_spans.py``).
"""
from bench import program_spans as P

PHASES = ("serve/admit", "serve/prefill/prepare", "serve/prefill/commit",
          "serve/decode/prepare", "serve/decode/commit")


def read(view):
    spans = P.spans_of(view)
    steps = P.named(spans, "serve/step")
    if not steps:
        return None
    ns = sum(sp.dur for sp in spans if sp.name in PHASES)
    return ns * 1e-6 / len(steps)

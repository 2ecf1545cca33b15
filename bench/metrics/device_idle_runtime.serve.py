"""device_idle_runtime.serve: share of the traced window in which no op
ran on the chip while the serving runtime's round (the program's
``serve/step`` span, ``bench/program_spans.py``) was open; the rest of
``device_idle.serve`` lies outside the runtime."""
from bench import program_spans as P


def read(view):
    steps = P.named(P.spans_of(view), "serve/step")
    if not steps or view.trace.window_s() <= 0:
        return None
    return 100.0 * P.idle_during(view.trace, steps) / view.trace.window_s()

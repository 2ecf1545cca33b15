"""decode_dispatch_ms: host time to launch one decode call.

Mean duration of the program's ``serve/decode/dispatch`` spans
(``bench/program_spans.py``): staging the call's inputs, the page tables
and the jitted call returning.
"""
from bench import program_spans as P


def read(view):
    spans = P.named(P.spans_of(view), "serve/decode/dispatch")
    if not spans:
        return None
    return sum(sp.dur for sp in spans) * 1e-6 / len(spans)

"""int8_gemms_per_decode_step: slice-pair products of one decode call.

Mean of the ``int8_gemms`` argument of the program's
``serve/decode/dispatch`` spans (``bench/program_spans.py``): the obs
counters of one execution of the decode program, every layer and the
head.
"""
from bench import program_spans as P


def read(view):
    counts = [sp.arg("int8_gemms") for sp in
              P.named(P.spans_of(view), "serve/decode/dispatch")]
    counts = [c for c in counts if c is not None]
    if not counts:
        return None
    return sum(counts) / len(counts)

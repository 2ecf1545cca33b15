"""gemm_mfu: the emulated GEMM's share of the chip's int8 peak.

2*m*n*p of every call in the traced window, over the window's length,
the chips and the int8 peak.  The work is fixed by the shape and not by
the implementation, so a kernel fused away still leaves this bound on a
claim; it is at most 1/int8_gemms_per_call of the peak by construction.
"""
from bench.trace import share


def read(view):
    rec = view.record
    rate = rec["flops"] / view.trace.window_s()
    return share(rate, view.chips * view.peaks["int8_ops_per_s"])

"""scale_accum_roofline: the df32 epilogue kernel's roofline share.

It is bound by HBM bytes (``bench/kernel_cost.py``): every call in the
traced window, over the device time of the kernel's events.
"""
from bench import kernel_cost as K


def read(view):
    rec = view.record
    events = view.trace.kernel_events("scale_accum")
    if not events:
        return None
    m, _, p = rec["shape"]
    nbytes = K.scale_accum_bytes(m, p, len(events))
    return K.roofline_share(0.0, nbytes, view.trace.op_seconds(events),
                            view.peaks["int8_ops_per_s"],
                            view.peaks["hbm_bytes_per_s"])

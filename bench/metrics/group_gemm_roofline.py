"""group_gemm_roofline: the int8 group GEMM kernel's roofline share.

Operations and bytes from the shapes (``bench/kernel_cost.py``) for every
call in the traced window, over the device time of the kernel's events.
"""
from bench import kernel_cost as K


def read(view):
    rec = view.record
    events = view.trace.kernel_events("group_gemm")
    if not events or not rec.get("int8_gemms_per_call"):
        return None
    m, n, p = rec["shape"]
    pairs = rec["int8_gemms_per_call"] * rec["calls"]
    ops = K.group_gemm_ops(m, n, p, pairs)
    nbytes = K.group_gemm_bytes(m, n, p, pairs, len(events))
    return K.roofline_share(ops, nbytes, view.trace.op_seconds(events),
                            view.peaks["int8_ops_per_s"],
                            view.peaks["hbm_bytes_per_s"])

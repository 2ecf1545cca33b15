"""product_reduce_roofline: the exact int32 reduction's share of the
chip's interconnect bandwidth.

The bytes one chip sends and receives to reduce-scatter the int32 group
products of every call in the traced window (``bench/collective_cost.py``,
from the traffic's shape and the group count of the traced call), over
the device time of the reduction's collective ops averaged over the
chips, against the ICI bandwidth of the device.
"""
from bench import collective_cost as C
from bench import trace as T


def read(view):
    rec = view.record
    per_device = C.reduction_intervals(view.trace)
    if not per_device or not rec.get("groups_per_call"):
        return None
    t = view.traffic
    sent = C.reduce_scatter_bytes_sent(int(t["m"]), int(t["p"]),
                                       int(rec["groups_per_call"]),
                                       view.chips)
    seconds = sum(T.union_length(iv) for iv in per_device.values()) \
        * 1e-9 / len(view.trace.devices)
    return C.link_share(2 * sent * rec["calls"], seconds,
                        C.ICI_BYTES_PER_S[rec["device_kind"]])

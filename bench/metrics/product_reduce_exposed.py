"""product_reduce_exposed: share of the traced window in which a chip runs
a collective op of the exact int32 reduction and no other op, averaged
over the chips (``bench/collective_cost.py``): the reduction's time that
no computation hides."""
from bench import collective_cost as C


def read(view):
    red = view.trace
    per_device = C.reduction_intervals(red)
    if not per_device or red.window_s() <= 0:
        return None
    ns = sum(C.exposed_ns(red, dev, iv) for dev, iv in per_device.items())
    return 100.0 * ns * 1e-9 / len(red.devices) / red.window_s()

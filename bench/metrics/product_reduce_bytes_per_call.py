"""product_reduce_bytes_per_call: int32 bytes one chip sends into the
exact cross-chip reduction of one emulated GEMM's group products.

The obs counter ``emulation.product_reduce_bytes`` over the traced
(compiling) call of set-up: (D-1)/D x G x m x p x 4 under the
reduce-scatter, 25,769,803,776 at 32768^3 on four chips (G = 8).
"""


def read(view):
    value = view.record.get("product_reduce_bytes_per_call")
    return value if value else None

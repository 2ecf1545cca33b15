"""int8_gemms_per_call: slice-pair products of one emulated GEMM.

The obs counter ``emulation.int8_gemms`` over the traced (compiling)
call of set-up: k(k+1)/2 for ``ozimmu_h``, 21 at k = 6 and 15 at k = 5.
"""


def read(view):
    value = view.record.get("int8_gemms_per_call")
    return value if value else None

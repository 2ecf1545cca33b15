"""Operations and bytes that the emulated GEMM's kernels need, from shapes.

The roofline share of a kernel is the least time the chip could take for
its work, the larger of operations over the peak rate and bytes over the
HBM bandwidth, divided by the device time of its events.  The work counted
here is what the algorithm needs: each operand read once and each result
written once, whatever the tiling re-reads.

* ``group_gemm``: one call sums G slice-pair products of an (m, n) by
  (n, p) int8 GEMM into an int32 (m, p) tile: 2*m*n*p*G int8 operations,
  G*(m*n + n*p) bytes in, 4*m*p bytes out.  Over one emulated GEMM the G
  of its calls add up to the number of slice pairs, so only the number of
  calls enters the bytes.
* ``scale_accum``: one call reads the int32 products (4 B an element) and
  the df32 accumulator (hi and lo, 8 B), writes the accumulator back (8 B)
  and reads the row and column scales: 20*m*p + 4*(m + p) bytes.  Its few
  vector operations an element never bound it on a chip whose HBM moves
  819 GB/s, so it is counted by bytes alone.
"""
from __future__ import annotations


def group_gemm_ops(m: int, n: int, p: int, pairs: int) -> float:
    return 2.0 * m * n * p * pairs


def group_gemm_bytes(m: int, n: int, p: int, pairs: int,
                     calls: int) -> float:
    return float(pairs * (m * n + n * p) + calls * 4 * m * p)


def scale_accum_bytes(m: int, p: int, calls: int) -> float:
    return float(calls * (20 * m * p + 4 * (m + p)))


def roofline_share(ops: float, nbytes: float, seconds: float,
                   ops_peak: float, bytes_peak: float):
    """Percent of the roofline reached, or None without device time."""
    if seconds <= 0:
        return None
    least = max(ops / ops_peak if ops_peak else 0.0, nbytes / bytes_peak)
    return 100.0 * least / seconds

"""Pallas TPU kernel: INT8 group GEMM with INT32 VMEM accumulation.

The TPU-native realization of Alg. 6/7 (group-wise error-free accumulation):
all slice-pair products of an anti-diagonal group share one power-of-two
exponent, so their sum

    C32[b] = sum_{g=1..G} A8[b, g] @ B8[b, g]   (exact in INT32 while G <= r)

is performed INSIDE the matmul unit's accumulator.  Here the accumulator is
an explicit (bm, bp) INT32 VMEM tile that lives across the whole reduction
(grid axes g and n), i.e. the group sum costs ZERO extra passes over HBM —
the paper's entire point, expressed in the TPU memory hierarchy.

Grid: (B, m/bm, p/bp, G, n/bn) — the leading axis is the *batch* axis (one
independent GEMM per batched contraction element, e.g. attention heads or
MoE experts); the last two axes are reduction axes.  The output block
index_map ignores the reduction axes, so Pallas keeps the C tile resident in
VMEM while g and kn iterate (TPU grid order is sequential, minor-to-major
last axis fastest); it DOES depend on the batch axis, so each batch element
gets a fresh accumulator (init fires at g == kn == 0 for every b).

MXU alignment: bm/bp multiples of 128, bn a multiple of 128 (int8 lane
tiling is (32, 128); 128 keeps both operand tiles aligned).

Operands are int8, or bf16 for the sign-magnitude digits (values up to
255, which the int8 MXU path cannot hold and Mosaic has no int16 matmul
for): a bf16 block product accumulates in f32, exact while
bn * 255^2 < 2^24 (``ops`` caps bn at 256), and converts to int32 before
it joins the resident tile — the same integers as the int8 path.  Callers pick
tiles from the planner's static-shape autotune table
(``repro.core.plan.kernel_blocks`` via ``repro.kernels.ops.group_gemm``);
the DEFAULT_* here are only the bare-kernel fallbacks.

Rank-3 ``(G, m, n)`` operands are accepted as the unbatched special case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BM = 128
DEFAULT_BP = 128
DEFAULT_BN = 512


def _group_gemm_kernel(a_ref, b_ref, c_ref):
    """One (bm, bn) x (bn, bp) int8 MAC into the resident int32 C tile."""
    g = pl.program_id(3)
    kn = pl.program_id(4)

    @pl.when((g == 0) & (kn == 0))
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    a, b = a_ref[0, 0], b_ref[0, 0]
    dn = (((1,), (0,)), ((), ()))
    if a.dtype == jnp.bfloat16:
        prod = jax.lax.dot_general(a, b, dn,
                                   preferred_element_type=jnp.float32)
        c_ref[0] += prod.astype(jnp.int32)
    else:
        c_ref[0] += jax.lax.dot_general(a, b, dn,
                                        preferred_element_type=jnp.int32)


@functools.partial(jax.jit, static_argnames=("bm", "bp", "bn", "interpret"))
def group_gemm(a8: jax.Array, b8: jax.Array, *, bm: int = DEFAULT_BM,
               bp: int = DEFAULT_BP, bn: int = DEFAULT_BN,
               interpret: bool = False) -> jax.Array:
    """sum_g a8[..., g, :, :] @ b8[..., g, :, :] -> int32.

    a8: (B, G, m, n) or (G, m, n) int8; b8: (B, G, n, p) or (G, n, p) int8
    (or both bf16 holding sign-magnitude digits, with bn <= 256).
    m/n/p must be multiples of the tiles (ops.py pads).  Caller guarantees
    G <= r (eq. 12) so INT32 cannot overflow — the sum is exact.  Returns
    (B, m, p) (or (m, p) for rank-3 inputs).
    """
    if a8.ndim == 3:
        return group_gemm(a8[None], b8[None], bm=bm, bp=bp, bn=bn,
                          interpret=interpret)[0]
    B, G, m, n = a8.shape
    B2, G2, n2, p = b8.shape
    assert B == B2 and G == G2 and n == n2, (a8.shape, b8.shape)
    assert m % bm == 0 and p % bp == 0 and n % bn == 0, (a8.shape, bm, bp, bn)
    grid = (B, m // bm, p // bp, G, n // bn)
    return pl.pallas_call(
        _group_gemm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bm, bn), lambda b, i, j, g, kn: (b, g, i, kn)),
            pl.BlockSpec((1, 1, bn, bp), lambda b, i, j, g, kn: (b, g, kn, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bp), lambda b, i, j, g, kn: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, m, p), jnp.int32),
        interpret=interpret,
    )(a8, b8)

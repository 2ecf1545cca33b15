"""Pallas TPU kernel: INT8 group GEMM with INT32 VMEM accumulation.

The TPU-native realization of Alg. 6/7 (group-wise error-free accumulation):
all slice-pair products of an anti-diagonal group share one power-of-two
exponent, so their sum

    C32[b] = sum_{g=1..G} A[ia[g], b] @ B[ib[g], b]   (exact in INT32 while G <= r)

is performed INSIDE the matmul unit's accumulator.  Here the accumulator is
an explicit (bm, bp) INT32 VMEM tile that lives across the whole reduction
(grid axes g and n), i.e. the group sum costs ZERO extra passes over HBM —
the paper's entire point, expressed in the TPU memory hierarchy.

Operands are the split's own digit stacks, ``A: (k, B, m, n)`` and
``B: (k, B, n, p)``, and the group's slice pairs arrive as two int32
vectors ``ia, ib`` of length G (0-indexed slices), prefetched into SMEM
(``PrefetchScalarGridSpec``).  The index maps pick the pair's slices,
``(ia[g], b, i, kn)`` and ``(ib[g], b, kn, j)``, so no gathered copy of
the slices is ever made: each block is DMA'd from the stack itself.

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

Rank-3 ``(k, m, n)`` operands are accepted as the unbatched special case.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BP = 128
DEFAULT_BN = 512


def _group_gemm_kernel(ia_ref, ib_ref, a_ref, b_ref, c_ref):
    """One (bm, bn) x (bn, bp) int8 MAC into the resident int32 C tile.
    ``ia_ref``/``ib_ref`` (the slice indices) are read by the index maps
    only."""
    del ia_ref, ib_ref
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
def group_gemm(ia: jax.Array, ib: jax.Array, a: jax.Array, b: jax.Array, *,
               bm: int = DEFAULT_BM, bp: int = DEFAULT_BP,
               bn: int = DEFAULT_BN, interpret: bool = False) -> jax.Array:
    """sum_g a[ia[g], ...] @ b[ib[g], ...] -> int32.

    ia, ib: (G,) int32 slice indices (0-indexed) of the G slice pairs.
    a: (k, B, m, n) or (k, m, n) int8; b: (k, B, n, p) or (k, n, p) int8
    (or both bf16 holding sign-magnitude digits, with bn <= 256).
    m/n/p must be multiples of the tiles (ops.py pads).  Caller guarantees
    G <= r (eq. 12) so INT32 cannot overflow — the sum is exact.  Returns
    (B, m, p) (or (m, p) for rank-3 inputs).
    """
    if a.ndim == 3:
        return group_gemm(ia, ib, a[:, None], b[:, None], bm=bm, bp=bp,
                          bn=bn, interpret=interpret)[0]
    _, B, m, n = a.shape
    _, B2, n2, p = b.shape
    G = ia.shape[0]
    assert B == B2 and n == n2 and ib.shape == (G,), \
        (a.shape, b.shape, ia.shape, ib.shape)
    assert m % bm == 0 and p % bp == 0 and n % bn == 0, (a.shape, bm, bp, bn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, m // bm, p // bp, G, n // bn),
        in_specs=[
            pl.BlockSpec((1, 1, bm, bn),
                         lambda b, i, j, g, kn, ia, ib: (ia[g], b, i, kn)),
            pl.BlockSpec((1, 1, bn, bp),
                         lambda b, i, j, g, kn, ia, ib: (ib[g], b, kn, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bp),
                               lambda b, i, j, g, kn, ia, ib: (b, i, j)),
    )
    return pl.pallas_call(
        _group_gemm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, m, p), jnp.int32),
        interpret=interpret,
    )(ia, ib, a, b)

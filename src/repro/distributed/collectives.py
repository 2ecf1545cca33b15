"""Error-free cross-device reductions for the sharded Ozaki emulation.

When a contraction is sharded over a mesh axis, every device holds a
*partial* product and the cross-device sum is exactly the kind of
"high-precision matrix addition" whose count the paper's Alg. 6/7 works to
minimize.  Doing that sum as a plain f32 ``psum`` throws away the accuracy
the scheme just paid for (the reduction rounds at 2^-24 while the
accumulator carries ~2^-48 or better).  This module provides the two
reductions that keep the scheme's invariants (see docs/distributed.md):

  * :func:`psum_exact_int32` — sum each INT32 slice/group partial across
    devices *before* any float conversion, as a reduce-scatter over C's
    rows (each device keeps the sum of its own rows) or, where the rows
    do not divide the axis, a ``psum``.  Bit-exact: each device's
    partial over its n_i local contraction columns is bounded by
    ``n_i * (2^beta - 1)^2`` and the partials sum to the unsharded product,
    so every intermediate stays under the same ``n * (2^beta - 1)^2 < 2^31``
    bound that eq. (4)/(12) of the paper guarantees for the unsharded GEMM
    — integer addition is associative, no overflow, no rounding.

  * :func:`psum_df32` / :func:`psum_compensated` — TwoSum-compensated
    reduction of partial high-precision accumulators (the ``partial=True``
    output of ``matmul_naive`` / ``matmul_group_ef``).  One collective for
    the whole GEMM instead of one per slice product; error-free in the
    two-float representation (each pairwise merge is a Dekker add whose
    rounding error is captured in the ``lo`` limb), with a single rounding
    at the final ``to_float``.

All functions must be called *inside* ``shard_map`` (they use named-axis
collectives).  The gather-then-fold formulation makes the reduction order
deterministic and identical on every device — the device index, not the
reduction topology, orders the fold.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.accumulate import DF32, df32_add_df, _two_sum

__all__ = ["psum_exact_int32", "psum_df32", "psum_compensated",
           "pmax_scales"]


def pmax_scales(v: jax.Array, axis_name: str) -> jax.Array:
    """Elementwise max of per-row/col |a| maxima across the mesh axis.

    Used as the splitters' ``rowmax_reduce`` hook so every shard of a
    contraction-sharded operand extracts digits on the SAME power-of-two
    grid as the unsharded run — the precondition for summing INT32
    partials exactly (and for bitwise equality with the unsharded path).
    """
    return lax.pmax(v, axis_name)


def psum_exact_int32(p: jax.Array, axis_name: str,
                     scatter_dimension: Optional[int] = None) -> jax.Array:
    """Exact cross-device sum of one INT32 partial slice/group product.

    The sharded engine path calls it once per group, as soon as the
    group's product exists, so the ``(G, *batch, m, p)`` stack of all
    products is never built.  With ``scatter_dimension`` (C's row axis)
    it is a reduce-scatter: each device receives the sum of its own block
    of rows, and C stays row-sharded; without it, a ``psum`` that leaves
    the whole sum on every device.  The no-overflow argument requires
    that the *global* contraction length was used for beta (eq. 4) and r
    (eq. 12) — the sharded engine path does this — so the sum of
    partials equals the unsharded INT32 product bit for bit.
    """
    if p.dtype != jnp.int32:
        raise TypeError(f"psum_exact_int32 needs int32 partials, got "
                        f"{p.dtype}; float partials lose exactness")
    if scatter_dimension is None:
        return lax.psum(p, axis_name)
    return lax.psum_scatter(p, axis_name, scatter_dimension=scatter_dimension,
                            tiled=True)


def psum_df32(c: DF32, axis_name: str) -> DF32:
    """Error-free ``psum`` of a DF32 (two-float) partial accumulator.

    All-gathers both limbs over the axis and folds the per-device partials
    with compensated (TwoSum) double-float addition in device order —
    deterministic and identical on every member of the axis.  The result
    stays unevaluated (hi, lo); round once, at the very end, via
    ``.to_float``.
    """
    his = lax.all_gather(c.hi, axis_name)   # (D, *shape)
    los = lax.all_gather(c.lo, axis_name)
    acc = DF32(his[0], los[0])
    for i in range(1, his.shape[0]):
        acc = df32_add_df(acc, DF32(his[i], los[i]))
    return acc


def psum_compensated(x: jax.Array, axis_name: str) -> jax.Array:
    """Compensated ``psum`` of a plain float partial accumulator.

    For ``f64``/``f32`` partial accumulators: all-gather, then a Neumaier
    fold — the running error term absorbs what each addition rounds away,
    and is added back once at the end.  Strictly no less accurate than
    ``lax.psum`` and deterministic across devices; use ``psum_df32`` when
    the partials are already two-float pairs.
    """
    parts = lax.all_gather(x, axis_name)    # (D, *shape)
    s = parts[0]
    e = jnp.zeros_like(s)
    for i in range(1, parts.shape[0]):
        s, err = _two_sum(s, parts[i])
        e = e + err
    return s + e

"""jit'd public wrappers around the Pallas kernels.

Handles: padding to tile multiples, row-scale preparation, slice-pair
index maps for group GEMMs, batch flattening onto the kernels' leading grid
axis, and the choice between compiled and interpreted kernels.  Block
sizes come from the planner's static-shape autotune table
(``repro.core.plan.kernel_blocks``), aligned per kernel with
``plan.tile``.

Compiled or interpreted
-----------------------
Every kernel call goes through :func:`_pallas`, which stages BOTH forms
under ``lax.platform_dependent`` and lets lowering keep the one for the
platform the program is compiled for: Mosaic on a TPU, the Pallas
interpreter on the CPU (the test suite's correctness reference path —
bit-identical to the compiled kernel for the integer/exact-float
arithmetic used here).  Any other platform has no branch and fails to
lower.  A kernel that Mosaic refuses raises at compile time; nothing
retries it interpreted or falls back to the XLA path.  Specs that no
compiled kernel can run (an f64 accumulator under ``:fused``) are refused
earlier, by ``repro.core.ozimmu.parse_spec`` on a TPU backend.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import plan
from repro.core.splitting import (Split, _geo_scales, _pow2_ceil,
                                  _pow2_floor, _rowmax, sm_decode)
from repro.kernels import group_gemm as _gg
from repro.kernels import scale_accum as _sa
from repro.kernels import split_fused as _sf
from repro.obs import tracing as _tracing

# int8 blocks tile as (32, 128) on the TPU: row tiles of int8 outputs
# align to 32 rows, f32/int32 blocks to 8
_INT8_ROWS = 32
# sign-magnitude digits reach 255, so their slice products run on the MXU
# in bf16 with f32 accumulation; a contraction block of at most 256
# keeps every partial sum below 2^24 (256 * 255^2 < 2^24), i.e. exact
_SM_MAX_BN = 256


def _pallas(kernel: Callable, *args, **static):
    """Run one Pallas kernel, compiled by Mosaic when the program lowers
    for a TPU and interpreted when it lowers for the CPU (see the module
    docstring).  The decision is made per lowering, so it follows the
    device the call is placed on, not the process's default backend."""
    def compiled(*operands):
        # Mosaic lowers 32-bit grid/index arithmetic only, and under x64
        # the BlockSpec index maps would trace to int64.  64-bit operands
        # are traced as they are (x64 off would silently narrow them), so
        # Mosaic refuses them when a TPU program is compiled; both
        # branches are traced on every backend, so no error here.
        if any(x.dtype.itemsize > 4 for x in operands):
            return kernel(*operands, interpret=False, **static)
        with jax.enable_x64(False):
            return kernel(*operands, interpret=False, **static)

    return jax.lax.platform_dependent(
        *args,
        cpu=functools.partial(kernel, interpret=True, **static),
        tpu=compiled)


def _pad_to(x: jax.Array, mults: Sequence[int]) -> jax.Array:
    pads = []
    for dim, mult in zip(x.shape, mults):
        rem = (-dim) % mult
        pads.append((0, rem))
    if all(p == (0, 0) for p in pads):
        return x
    return jnp.pad(x, pads)


def split_fused(a: jax.Array, k: int, beta: int, *, mode: str = "rn_const",
                axis: int = 0,
                rowmax_reduce: Optional[Callable] = None) -> Split:
    """Pallas-accelerated splitting (Alg. 3 'bitmask' / Alg. 8 'rn_const' /
    the oz2 constant-grid modes 'oz2_bitmask' / 'oz2_rn' / their
    improved-scaling fast2 twins 'oz2_bitmask_fast2' / 'oz2_rn_fast2').

    Returns the same :class:`Split` contract as the pure-jnp splitters —
    bit-identical digits and scales, in ``a``'s own dtype (f64 inputs stay
    f64 in the interpreter; Mosaic takes f32 only — ``parse_spec``
    refuses f64 ``:fused`` specs on a TPU).  ``a`` is
    ``(*batch, m, n)``: splitting is row/column-local, so batch and row
    dims flatten together onto the kernel grid.  axis=1 (column scales,
    for B) transposes the trailing two axes in and out of the row kernel.
    ``rowmax_reduce`` widens the row maxima before grids are derived
    (the mesh-axis pmax hook) exactly as in the library splitters.

    The oz2 modes derive ONE grid per batch element from the global |a|
    maximum; without batch dims the kernel runs in its const-grid mode
    (a (1, 1) scalar operand instead of an (m, 1) streamed vector), with
    batch dims the scalar broadcasts onto the flattened row grid —
    bit-identical either way.  The fast2 modes keep the PER-ROW grids of
    their per-row twins (the equilibrated digits are bitwise the per-row
    splitter's — no global broadcast, no extra pass) and attach the
    constant equilibrated-grid base ``gbase = 2`` exactly as
    ``splitting.split_oz2_fast2`` / ``split_oz2_bitmask_fast2`` do.
    """
    if axis == 1:
        sp = split_fused(jnp.swapaxes(a, -1, -2), k, beta, mode=mode,
                         axis=0, rowmax_reduce=rowmax_reduce)
        return Split(jnp.swapaxes(sp.digits, -1, -2), sp.scale, sp.base,
                     beta, 1, gbase=sp.gbase, signmag=sp.signmag)
    rowmax = _rowmax(a, 0)                              # (*batch, m)
    if rowmax_reduce is not None:
        rowmax = rowmax_reduce(rowmax)
    gbase = None
    if mode in ("oz2_rn", "oz2_bitmask"):
        rowmax = jnp.broadcast_to(
            jnp.max(rowmax, axis=-1, keepdims=True), rowmax.shape)
    if mode in ("bitmask", "oz2_bitmask", "oz2_bitmask_fast2"):
        base = 2.0 * _pow2_floor(rowmax)
        invgrid = (2.0 ** beta) / base  # 1/grid_1, grid_1 = base*2^-beta
        kmode = "bitmask"
    elif mode in ("rn_const", "oz2_rn", "oz2_rn_fast2"):
        mu = _pow2_ceil(rowmax) * (2.0 ** (1 - beta))
        base = mu * (2.0 ** beta)
        invgrid = 1.0 / mu
        kmode = "rn_const"
    elif mode == "sm":
        # sign-magnitude: leading grid = anchor * 2^(1-beta) with the
        # strict anchor 2*2^floor(log2 rowmax) > rowmax; the stored base
        # is 2*anchor so scale[s] = base * 2^(-beta*s) (splitting.split_sm)
        anchor = 2.0 * _pow2_floor(rowmax)
        base = 2.0 * anchor
        invgrid = (2.0 ** (beta - 1)) / anchor
        kmode = "sm"
    else:
        raise ValueError(f"fused splitting supports bitmask/rn_const/sm/"
                         f"oz2_bitmask/oz2_rn/oz2_bitmask_fast2/"
                         f"oz2_rn_fast2, got {mode!r}")
    if mode in ("oz2_rn", "oz2_bitmask"):
        gbase = base[..., 0]
    elif mode in ("oz2_rn_fast2", "oz2_bitmask_fast2"):
        # the equilibrated constant grid: per-row digits, scalar base 2
        # (splitting._with_fast2_gbase's contract)
        gbase = jnp.full(base.shape[:-1], 2.0, base.dtype)
    batch = a.shape[:-2]
    m, n = a.shape[-2:]
    rows = math.prod(batch, start=m)
    a2 = a.reshape((rows, n))
    # fast2 keeps per-row grids (streamed), so only the plain oz2 modes
    # qualify for the kernel's const-grid scalar operand
    const_grid = mode in ("oz2_rn", "oz2_bitmask") and not batch
    inv2 = (invgrid[:1, None] if const_grid
            else invgrid.reshape((rows, 1)))
    bm_pref, bn_pref, _ = plan.kernel_blocks(rows, n)
    bm = plan.tile(rows, bm_pref, _INT8_ROWS)
    bn = plan.tile(n, bn_pref, 128)
    a_p = _pad_to(a2, (bm, bn))
    inv_p = inv2 if const_grid else _pad_to(inv2, (bm, 1))
    with _tracing.phase_scope("kernel/split_fused"):
        digits = _pallas(_sf.split_fused, a_p, inv_p, k=k, beta=beta,
                         mode=kmode, bm=bm, bn=bn,
                         const_grid=const_grid)[:, :rows, :n]
    digits = digits.reshape((k,) + batch + (m, n))
    return Split(digits, _geo_scales(base, beta, k), base, beta, 0,
                 gbase=gbase, signmag=(mode == "sm"))


def group_gemm_fn(sa: Split, sb: Split
                  ) -> Callable[[Sequence[Tuple[int, int]]], jax.Array]:
    """The ``group_gemm_fn`` hook of one contraction: ``pairs -> int32``,
    sum over the slice pairs of A_s @ B_t via the Pallas kernel.

    The digit stacks are prepared once, here, for every group the hook
    runs: batched digits ``(k, *batch, m, n)`` flatten onto the kernel's
    leading batch grid axis (a free reshape), and each stack is padded
    once to the tile multiples.  Each call then hands the kernel the
    group's slice indices and the kernel's index maps read the pairs
    straight from the stacks: no per-group gather, transpose or pad.
    Output is ``(*batch, m, p)``.
    """
    # sign-magnitude digits decode to [-128, 255] once over the stack; the
    # MXU has no int16 path, so they enter the kernel as bf16 (exact for
    # these integers) and each block product accumulates exactly in f32
    # (see _SM_MAX_BN) before joining the int32 tile
    signmag = sa.signmag or sb.signmag
    da = sm_decode(sa.digits) if sa.signmag else sa.digits
    db = sm_decode(sb.digits) if sb.signmag else sb.digits
    if signmag:
        da, db = da.astype(jnp.bfloat16), db.astype(jnp.bfloat16)
    batch = da.shape[1:-2]
    m, n = da.shape[-2:]
    p = db.shape[-1]
    bm_pref, bn_pref, bp_pref = plan.kernel_blocks(m, n, p)
    bm = plan.tile(m, bm_pref, 128)
    bn = plan.tile(n, min(bn_pref, _SM_MAX_BN) if signmag else bn_pref,
                   128)
    bp = plan.tile(p, bp_pref, 128)
    a = _pad_to(da.reshape((da.shape[0], -1, m, n)), (1, 1, bm, bn))
    b = _pad_to(db.reshape((db.shape[0], -1, n, p)), (1, 1, bn, bp))

    def gemm(pairs: Sequence[Tuple[int, int]]) -> jax.Array:
        ia = jnp.asarray([s - 1 for s, _ in pairs], jnp.int32)
        ib = jnp.asarray([t - 1 for _, t in pairs], jnp.int32)
        with _tracing.phase_scope("kernel/group_gemm"):
            out = _pallas(_gg.group_gemm, ia, ib, a, b, bm=bm, bp=bp, bn=bn)
        return out[:, :m, :p].reshape(batch + (m, p))

    return gemm


def group_gemm(sa: Split, sb: Split, pairs: Sequence[Tuple[int, int]]
               ) -> jax.Array:
    """sum over slice pairs of A_s @ B_t in int32 via the Pallas kernel:
    one group of :func:`group_gemm_fn`.  A contraction with several groups
    builds the hook once instead, so its stacks are prepared once."""
    return group_gemm_fn(sa, sb)(pairs)


def operand_copy_bytes(k: int, batch: int, m: int, n: int, p: int, *,
                       signmag: bool) -> int:
    """Bytes of digit copies :func:`group_gemm_fn` makes for the kernel in
    one contraction of ``batch`` (m, n) @ (n, p) products split into k
    slices: the bf16 decode of sign-magnitude stacks.  Int8 stacks are
    read by the kernel as they are (beyond the one pad of each stack to
    the tile multiples), so nothing is gathered, transposed or padded per
    group."""
    if not signmag:
        return 0
    return k * batch * (m * n + n * p) * jnp.dtype(jnp.bfloat16).itemsize


def _epilogue_operands(p32: jax.Array, srow: jax.Array, scol: jax.Array,
                       *accs: jax.Array):
    """Flatten batch, pad to the planned tiles; returns padded operands,
    the (bm, bp) tiles, and an unpad closure."""
    batch = p32.shape[:-2]
    m, p = p32.shape[-2:]
    B = math.prod(batch, start=1)
    bm_pref, bp_pref, _ = plan.kernel_blocks(m, p)
    bm = plan.tile(m, bm_pref, 8)
    bp = plan.tile(p, bp_pref, 128)
    p32_p = _pad_to(p32.reshape((B, m, p)), (1, bm, bp))
    srow_p = _pad_to(srow.reshape((B, m, 1)), (1, bm, 1))
    scol_p = _pad_to(scol.reshape((B, 1, p)), (1, 1, bp))
    accs_p = [_pad_to(c.reshape((B, m, p)), (1, bm, bp)) for c in accs]

    def unpad(x):
        return x[:, :m, :p].reshape(batch + (m, p))

    return p32_p, srow_p, scol_p, accs_p, bm, bp, unpad


def scale_accum(p32: jax.Array, srow: jax.Array, scol: jax.Array,
                c_hi: jax.Array, c_lo: jax.Array):
    """Fused df32 epilogue; p32/c_hi/c_lo ``(*batch, m, p)``,
    srow ``(*batch, m)``, scol ``(*batch, p)``."""
    p32_p, srow_p, scol_p, (hi_p, lo_p), bm, bp, unpad = \
        _epilogue_operands(p32, srow, scol, c_hi, c_lo)
    with _tracing.phase_scope("kernel/scale_accum"):
        hi, lo = _pallas(_sa.scale_accum, p32_p, srow_p, scol_p, hi_p,
                         lo_p, bm=bm, bp=bp)
    return unpad(hi), unpad(lo)


def scale_accum_plain(p32: jax.Array, srow: jax.Array, scol: jax.Array,
                      c: jax.Array):
    """Fused plain-accumulator epilogue (f64/f32), batched like
    :func:`scale_accum`."""
    p32_p, srow_p, scol_p, (c_p,), bm, bp, unpad = \
        _epilogue_operands(p32, srow, scol, c)
    with _tracing.phase_scope("kernel/scale_accum"):
        out = _pallas(_sa.scale_accum_plain, p32_p, srow_p, scol_p, c_p,
                      bm=bm, bp=bp)
    return unpad(out)


def scale_accum_update(prod: jax.Array, srow: jax.Array, scol: jax.Array,
                       acc):
    """``scale_accum_fn`` hook for ``accumulate.matmul_naive`` /
    ``matmul_group_ef``: one fused convert+scale+add epilogue step through
    the Pallas kernel (df32 pair or plain accumulator, by ``acc``'s type).
    Bit-identical to the inline jnp epilogue — see kernels/scale_accum.py.
    """
    from repro.core.accumulate import DF32  # local: avoid import cycle
    if isinstance(acc, DF32):
        hi, lo = scale_accum(prod, srow, scol, acc.hi, acc.lo)
        return DF32(hi, lo)
    return scale_accum_plain(prod, srow, scol, acc)


def _oz2_epilogue_operands(word: jax.Array, s: jax.Array, *accs: jax.Array):
    """Const-scale analogue of :func:`_epilogue_operands`: flatten batch,
    pad to tiles, reshape the per-batch scalar to (B, 1, 1)."""
    batch = word.shape[:-2]
    m, p = word.shape[-2:]
    B = math.prod(batch, start=1)
    bm_pref, bp_pref, _ = plan.kernel_blocks(m, p)
    bm = plan.tile(m, bm_pref, 8)
    bp = plan.tile(p, bp_pref, 128)
    word_p = _pad_to(word.reshape((B, m, p)), (1, bm, bp))
    s_p = s.reshape((B, 1, 1))
    accs_p = [_pad_to(c.reshape((B, m, p)), (1, bm, bp)) for c in accs]

    def unpad(x):
        return x[:, :m, :p].reshape(batch + (m, p))

    return word_p, s_p, accs_p, bm, bp, unpad


def oz2_scale_accum(word: jax.Array, s: jax.Array, c_hi: jax.Array,
                    c_lo: jax.Array):
    """Fused oz2 df32 epilogue: ``(c_hi, c_lo) += s * float(word)``,
    compensated; word ``(*batch, m, p)`` int32, s ``(*batch,)`` f32."""
    word_p, s_p, (hi_p, lo_p), bm, bp, unpad = \
        _oz2_epilogue_operands(word, s, c_hi, c_lo)
    with _tracing.phase_scope("kernel/scale_accum"):
        hi, lo = _pallas(_sa.scale_accum_const, word_p, s_p, hi_p, lo_p,
                         bm=bm, bp=bp)
    return unpad(hi), unpad(lo)


def oz2_scale_accum_plain(word: jax.Array, s: jax.Array, c: jax.Array):
    """Fused oz2 plain epilogue (f64/f32 accumulator; word may be the
    int64 ladder word in f64/x64 mode)."""
    word_p, s_p, (c_p,), bm, bp, unpad = _oz2_epilogue_operands(word, s, c)
    with _tracing.phase_scope("kernel/scale_accum"):
        out = _pallas(_sa.scale_accum_const_plain, word_p, s_p, c_p,
                      bm=bm, bp=bp)
    return unpad(out)


def oz2_scale_accum_update(word: jax.Array, s: jax.Array, acc):
    """``scale_accum_fn`` hook for ``accumulate.matmul_oz2``: one fused
    ladder-window convert+scale+add through the const-grid Pallas kernels
    (bit-identical to the inline jnp epilogue)."""
    from repro.core.accumulate import DF32  # local: avoid import cycle
    if isinstance(acc, DF32):
        hi, lo = oz2_scale_accum(word, s, acc.hi, acc.lo)
        return DF32(hi, lo)
    return oz2_scale_accum_plain(word, s, acc)


def oz2_unscale(x: jax.Array, ra: jax.Array, rb: jax.Array) -> jax.Array:
    """Fused fast2 post-ladder unscale: ``diag(ra) @ x @ diag(rb)`` per
    batch element in one Pallas pass.  x ``(*batch, m, p)`` float;
    ra ``(*batch, m)`` / rb ``(*batch, p)`` power-of-two equilibration
    factors — exact, bit-identical to ``accumulate._oz2_unscale``."""
    batch = x.shape[:-2]
    m, p = x.shape[-2:]
    B = math.prod(batch, start=1)
    bm_pref, bp_pref, _ = plan.kernel_blocks(m, p)
    bm = plan.tile(m, bm_pref, 8)
    bp = plan.tile(p, bp_pref, 128)
    x_p = _pad_to(x.reshape((B, m, p)), (1, bm, bp))
    ra_p = _pad_to(ra.reshape((B, m, 1)).astype(x.dtype), (1, bm, 1))
    rb_p = _pad_to(rb.reshape((B, 1, p)).astype(x.dtype), (1, 1, bp))
    with _tracing.phase_scope("kernel/unscale"):
        out = _pallas(_sa.unscale, x_p, ra_p, rb_p, bm=bm, bp=bp)
    return out[:, :m, :p].reshape(batch + (m, p))


def oz2_unscale_update(acc, ra: jax.Array, rb: jax.Array):
    """``unscale_fn`` hook for ``accumulate.matmul_oz2`` (fast2): the
    two-sided power-of-two unscale through the Pallas kernel — hi and lo
    limbs separately for a df32 accumulator (a common exact scale
    preserves the pair invariant)."""
    from repro.core.accumulate import DF32  # local: avoid import cycle
    if isinstance(acc, DF32):
        return DF32(oz2_unscale(acc.hi, ra, rb),
                    oz2_unscale(acc.lo, ra, rb))
    return oz2_unscale(acc, ra, rb)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window=None,
                    qc: int = 256, kc: int = 512, q_offset: int = 0):
    """jit'd wrapper for the fused flash-attention forward kernel.

    q (B, Lq, H, D); k, v (B, Lk, KV, D/Dv).  Pads L to tile multiples,
    flattens (B, H) into the kernel's grid-major axis, maps GQA groups in
    the BlockSpec (no K/V expansion), and slices the padding back off.
    """
    from repro.kernels import flash_attention as _fa
    B, Lq, H, D = q.shape
    _, Lk, KV, Dv = v.shape
    group = H // KV
    qc = min(qc, max(8, Lq))
    kc = min(kc, max(8, Lk))
    Lq_p = -(-Lq // qc) * qc
    Lk_p = -(-Lk // kc) * kc
    qt = jnp.pad(q, ((0, 0), (0, Lq_p - Lq), (0, 0), (0, 0)))
    kt = jnp.pad(k, ((0, 0), (0, Lk_p - Lk), (0, 0), (0, 0)))
    vt = jnp.pad(v, ((0, 0), (0, Lk_p - Lk), (0, 0), (0, 0)))
    qt = qt.transpose(0, 2, 1, 3).reshape(B * H, Lq_p, D)
    kt = kt.transpose(0, 2, 1, 3).reshape(B * KV, Lk_p, D)
    vt = vt.transpose(0, 2, 1, 3).reshape(B * KV, Lk_p, Dv)
    o, _ = _pallas(_fa.flash_attention_fwd, qt, kt, vt, group=group,
                   causal=causal, window=window, qc=qc, kc=kc,
                   q_offset=q_offset, lk=Lk)
    o = o.reshape(B, H, Lq_p, Dv).transpose(0, 2, 1, 3)
    return o[:, :Lq]

"""Slice-product evaluation + accumulation for the Ozaki scheme.

Three evaluation strategies — two from the source paper, plus the
Ozaki-II constant-scaling path (``matmul_oz2``, see its docstring and
docs/algorithms.md#ozaki-scheme-ii), which requires the shared-grid
splits of ``splitting.split_oz2``/``split_oz2_bitmask`` and folds every
slice-pair scale into one scalar exponent ladder per contraction.

Two evaluation strategies from the paper:

  * ``matmul_naive``    — Alg. 4: one INT8 GEMM per slice pair (s, t) with
    s+t <= k+1, each converted to high precision, scaled, and added.
    k(k+1)/2 high-precision matrix additions.
  * ``matmul_group_ef`` — Alg. 6/7 (proposed): all pairs on an anti-diagonal
    group g = s+t share the exponent 2^(-beta*g), so they are summed
    *inside the integer matmul unit*.  On TPU we realize this by
    concatenating the group's slices along the contraction axis and issuing
    ONE int8 GEMM with inner dimension (g-1)*n — the MXU's INT32 accumulator
    performs the group reduction as part of the contraction (error-free for
    group sizes <= r, eq. (12); larger groups are chunked, reproducing
    Alg. 6's ``q == r`` flush).  k (or w, eq. for chunking) high-precision
    additions total.

High-precision accumulator modes:

  * ``f64``  — faithful to the paper (FP64 accumulation).  On TPU this is
    software-emulated; used for CPU validation and the DGEMM-emulation bench.
  * ``f32``  — plain f32 accumulation (sufficient for emulating f32 GEMMs
    when combined with EF grouping).
  * ``df32`` — double-float (two-float compensated) accumulation: TPU-native
    high-precision mode, ~2^-48 effective significand.  INT32 products are
    converted to an exact (hi, lo) f32 pair, scaled by powers of two
    (exact), and accumulated with Knuth TwoSum.  This is our beyond-paper
    replacement for FP64 accumulation on hardware without FP64 units.

Return contract (the distributed hooks):

The matmuls take optional hooks for mesh-sharded contractions
(see repro/distributed/collectives.py and docs/distributed.md):

  * ``product_reduce`` — applied to EACH ``(*batch, m, p)`` INT32
    slice/group product as soon as it is made, *before* any conversion or
    scaling; no stack of all products is built.  With an exact int32 sum
    over the mesh axis this makes a contraction-sharded evaluation
    bit-identical to the unsharded one (integer addition is associative;
    the overflow bound is the global-n bound).  A reduce-scatter returns
    only this device's block of rows; ``row_select`` then picks the same
    rows of A's per-row scales, and the epilogue and the result cover
    those rows alone.  Identity when None.
  * ``partial=True`` — return the UNROUNDED accumulator instead of an
    array in ``out_dtype``: a :class:`DF32` (hi, lo) pair for
    ``accum="df32"``, the raw f64/f32 accumulator otherwise.  The caller
    owns the single final rounding — e.g. after an error-free cross-device
    reduction of per-shard partials.

Fused-epilogue hook (``scale_accum_fn``):

Both matmuls also accept a ``scale_accum_fn(prod, srow, scol, acc) -> acc``
hook that performs one convert+scale+add step — ``acc`` is the running
accumulator (:class:`DF32` or a plain f64/f32 array), ``prod`` the INT32
product, ``srow``/``scol`` the per-row/col power-of-two scales (any 2^e
group exponent already folded into ``srow``; exact).  The default hook is
the inline jnp epilogue below; ``repro.kernels.ops.scale_accum_update``
substitutes the one-HBM-pass Pallas kernel (the ``use_pallas="fused"``
path), which performs the bit-identical operation sequence.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.splitting import Split, compute_r, sm_decode_slice
from repro.obs import tracing as _tracing

__all__ = [
    "int8_gemm",
    "gemm_slice",
    "matmul_naive",
    "matmul_group_ef",
    "matmul_oz2",
    "DF32",
    "num_highprec_adds",
    "oz2_num_pairs",
    "oz2_num_highprec_adds",
    "oz2_num_chunks",
    "ladder_width",
]


def int8_gemm(a8: jax.Array, b8: jax.Array) -> jax.Array:
    """(*batch, m, n) int8 @ (*batch, n, p) int8 -> (*batch, m, p) int32.

    Exact barring overflow.  Leading axes are true ``dot_general`` batch
    dimensions, so batched GEMMs hit the MXU as one batched contraction
    instead of a python loop or a reshape-to-2D.
    """
    nb = a8.ndim - 2
    dims = (((a8.ndim - 1,), (nb,)), (tuple(range(nb)), tuple(range(nb))))
    return jax.lax.dot_general(a8, b8, dims,
                               preferred_element_type=jnp.int32)


def gemm_slice(sp: Split, i: int) -> jax.Array:
    """Slice ``i`` (0-indexed) of a split, widened for the integer GEMM.

    Signed-digit splits feed the int8 array straight through; the
    sign-magnitude storage convention (``Split.signmag``) widens to int16
    values first (slice 0 signed, the rest un-wrapped to [0, 2^beta - 1])
    — ``int8_gemm``'s int32 contraction is dtype-generic, and the
    no-overflow bound of ``compute_beta_sm`` covers the wider digits.
    """
    d = sp.digits[i]
    return sm_decode_slice(d, i) if sp.signmag else d


# ---------------------------------------------------------------------------
# double-float (two-float) arithmetic — the TPU-native high-precision path
# ---------------------------------------------------------------------------

class DF32(NamedTuple):
    """Unevaluated sum hi + lo of two f32 arrays, |lo| <= ulp(hi)/2."""

    hi: jax.Array
    lo: jax.Array

    def to_float(self, dtype=jnp.float64) -> jax.Array:
        return self.hi.astype(dtype) + self.lo.astype(dtype)


def _two_sum(a: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Knuth TwoSum: a + b = s + e exactly."""
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def df32_zero(shape, dtype=jnp.float32) -> DF32:
    z = jnp.zeros(shape, dtype)
    return DF32(z, z)


def df32_add(c: DF32, x: jax.Array) -> DF32:
    """c += x with compensated two-float accumulation."""
    hi, e = _two_sum(c.hi, x)
    lo = c.lo + e
    # cheap renormalization (fast-two-sum; hi dominates lo)
    hi2, e2 = _two_sum(hi, lo)
    return DF32(hi2, e2)


def df32_add_df(c: DF32, x: DF32) -> DF32:
    hi, e = _two_sum(c.hi, x.hi)
    lo = c.lo + e + x.lo
    hi2, e2 = _two_sum(hi, lo)
    return DF32(hi2, e2)


def int32_to_df32(p: jax.Array) -> DF32:
    """Exact int32 -> (hi, lo) f32 pair (f32 holds only 24 bits).

    Integer split: hi = p with the low 8 bits cleared (a multiple of 256 with
    <= 23 significant bits — exact in f32), lo = the low 8 bits.  Pure integer
    ops; no f64 round-trip, so it is TPU-native.
    """
    hi_int = (p >> 8) << 8
    lo_int = p - hi_int  # in [0, 255]
    return DF32(hi_int.astype(jnp.float32), lo_int.astype(jnp.float32))


# ---------------------------------------------------------------------------
# scaling helpers
# ---------------------------------------------------------------------------

def _outer_scale(p: jax.Array, sa: jax.Array, sb: jax.Array) -> jax.Array:
    """diag(sa) @ p @ diag(sb) per batch element; scales are powers of two
    (exact in fp).  p (*batch, m, p); sa (*batch, m); sb (*batch, p)."""
    return p * sa[..., :, None] * sb[..., None, :]


def _term_pairs(k: int) -> Sequence[Tuple[int, int]]:
    """Fast-mode slice pairs (1-indexed): s + t <= k + 1."""
    return [(s, g - s) for g in range(2, k + 2) for s in range(1, g)]


# ---------------------------------------------------------------------------
# per-term convert+scale+add — the default (inline jnp) epilogue hooks
# ---------------------------------------------------------------------------

def _scale_accum_df32(prod: jax.Array, srow: jax.Array, scol: jax.Array,
                      acc: DF32) -> DF32:
    """One df32 epilogue step: ``acc += srow * float(prod) * scol``,
    compensated.  ``srow``/``scol`` are f32 powers of two (any group
    exponent 2^e folded into ``srow`` — exact)."""
    term = int32_to_df32(prod)
    term = DF32(_outer_scale(term.hi, srow, scol),
                _outer_scale(term.lo, srow, scol))
    return df32_add_df(acc, term)


def _scale_accum_plain(prod: jax.Array, srow: jax.Array, scol: jax.Array,
                       acc: jax.Array) -> jax.Array:
    """One plain-accumulator epilogue step in ``acc.dtype`` (f64/f32)."""
    return acc + _outer_scale(prod.astype(acc.dtype), srow, scol)


def num_highprec_adds(k: int, r: int, group_ef: bool) -> int:
    """Number of high-precision matrix additions (paper's accounting)."""
    if not group_ef:
        return k * (k + 1) // 2
    total = 0
    for g in range(2, k + 2):
        total += -(-(g - 1) // r)  # ceil((g-1)/r) chunks for group g
    return total


# ---------------------------------------------------------------------------
# Alg. 4 — naive accumulation
# ---------------------------------------------------------------------------

def _reduced(prod: jax.Array, product_reduce: Optional[Callable]
             ) -> jax.Array:
    """One INT32 product through ``product_reduce`` (as it is made)."""
    if product_reduce is None:
        return prod
    with _tracing.phase_scope("product_reduce"):
        reduced = product_reduce(prod)
    if reduced.dtype != jnp.int32:
        raise TypeError(f"product_reduce must preserve int32 exactness, "
                        f"returned {reduced.dtype}")
    return reduced


def _rows(row_select: Optional[Callable], v: jax.Array) -> jax.Array:
    """The rows of A's per-row vector ``v`` (trailing axis) that the
    reduced products cover: all of them unless ``row_select`` is given."""
    return v if row_select is None else row_select(v)


def matmul_naive(sa: Split, sb: Split, *, accum: str = "f64",
                 out_dtype=None, partial: bool = False,
                 product_reduce: Optional[Callable] = None,
                 row_select: Optional[Callable] = None,
                 scale_accum_fn: Optional[Callable] = None,
                 pair_gemm_fn: Optional[Callable] = None
                 ) -> Union[jax.Array, DF32]:
    """One INT8 GEMM + one high-precision scaled add per slice pair.

    Batched: digits may be ``(k, *batch, m, n)`` / ``(k, *batch, n, p)``;
    every slice-pair product is then ONE batched int8 ``dot_general``.
    ``pair_gemm_fn(s, t) -> int32`` overrides the per-pair GEMM (1-indexed
    slice pair; the Pallas hook of ``use_pallas``).  ``partial`` /
    ``product_reduce`` / ``row_select`` / ``scale_accum_fn``: see the
    module docstring.
    """
    assert sa.axis == 0 and sb.axis == 1, "A needs row scales, B column scales"
    k = sa.digits.shape[0]
    assert sb.digits.shape[0] == k
    scale_a = _rows(row_select, sa.scale)
    out_shape = scale_a.shape[1:] + (sb.digits.shape[-1],)
    out_dtype = out_dtype or sa.scale.dtype
    gemm = pair_gemm_fn or (
        lambda s, t: int8_gemm(gemm_slice(sa, s - 1), gemm_slice(sb, t - 1)))

    def products():
        for s, t in _term_pairs(k):
            with _tracing.phase_scope("group_gemm"):
                prod = gemm(s, t)
            yield s, t, _reduced(prod, product_reduce)

    if accum == "df32":
        fn = scale_accum_fn or _scale_accum_df32
        acc = df32_zero(out_shape)
        for s, t, prod in products():
            with _tracing.phase_scope("scale_accum"):
                acc = fn(prod, scale_a[s - 1].astype(jnp.float32),
                         sb.scale[t - 1].astype(jnp.float32), acc)
        return acc if partial else acc.to_float(out_dtype)

    acc_dtype = {"f64": jnp.float64, "f32": jnp.float32}[accum]
    fn = scale_accum_fn or _scale_accum_plain
    c = jnp.zeros(out_shape, acc_dtype)
    for s, t, prod in products():
        with _tracing.phase_scope("scale_accum"):
            c = fn(prod, scale_a[s - 1].astype(acc_dtype),
                   sb.scale[t - 1].astype(acc_dtype), c)
    return c if partial else c.astype(out_dtype)


# ---------------------------------------------------------------------------
# Alg. 6/7 — group-wise error-free accumulation
# ---------------------------------------------------------------------------

def _group_chunks(k: int, r: int):
    """Yield (g, [(s, t), ...]) chunks of size <= r per anti-diagonal group."""
    for g in range(2, k + 2):
        pairs = [(s, g - s) for s in range(1, g)]
        for i in range(0, len(pairs), r):
            yield g, pairs[i:i + r]


def group_gemm_concat(sa: Split, sb: Split, pairs) -> jax.Array:
    """sum_{(s,t) in pairs} A_s @ B_t as ONE int8 GEMM via contraction-axis
    concatenation — the TPU-native realization of Alg. 6's INT32 group sum.
    Batched digits concatenate along the trailing contraction axis.
    Sign-magnitude splits widen per slice first (``gemm_slice``)."""
    a_cat = jnp.concatenate([gemm_slice(sa, s - 1) for s, _ in pairs],
                            axis=-1)
    b_cat = jnp.concatenate([gemm_slice(sb, t - 1) for _, t in pairs],
                            axis=-2)
    return int8_gemm(a_cat, b_cat)


def matmul_group_ef(sa: Split, sb: Split, *, accum: str = "f64",
                    out_dtype=None, r: Optional[int] = None,
                    group_gemm_fn=None, partial: bool = False,
                    product_reduce: Optional[Callable] = None,
                    row_select: Optional[Callable] = None,
                    scale_accum_fn: Optional[Callable] = None
                    ) -> Union[jax.Array, DF32]:
    """Group-wise error-free accumulation (Alg. 6; Alg. 7 when r >= k).

    Requires geometric slice scales (``base`` present): the combined scale of
    every pair in group g is ``baseA (x) baseB * 2^(-beta*g)``.
    ``partial`` / ``product_reduce`` / ``row_select``: see the module
    docstring — when the contraction axis is sharded, pass ``r`` computed
    from the GLOBAL contraction length so the per-group INT32 partials
    stay summable without overflow across devices.
    """
    assert sa.axis == 0 and sb.axis == 1
    if sa.base is None or sb.base is None:
        raise ValueError("group-EF accumulation needs geometric slice scales "
                         "(bitmask or rn_const splitting); got adaptive RN")
    k = sa.digits.shape[0]
    beta = sa.beta
    n = sa.digits.shape[-1]
    base_a = _rows(row_select, sa.base)
    out_shape = base_a.shape + (sb.digits.shape[-1],)
    out_dtype = out_dtype or sa.scale.dtype
    if r is None:
        r = compute_r(n, beta)
    gg = group_gemm_fn or (lambda pairs: group_gemm_concat(sa, sb, pairs))

    def products():
        for g, pairs in _group_chunks(k, r):
            with _tracing.phase_scope("group_gemm"):
                prod = gg(pairs)
            yield g, _reduced(prod, product_reduce)

    # The 2^(-beta*g) group exponent folds into the row scale (exact:
    # powers of two), matching the fused kernel's srow contract.
    if accum == "df32":
        fn = scale_accum_fn or _scale_accum_df32
        acc = df32_zero(out_shape)
        base_a = base_a.astype(jnp.float32)
        base_b = sb.base.astype(jnp.float32)
        for g, prod in products():
            with _tracing.phase_scope("scale_accum"):
                e = jnp.asarray(2.0 ** (-beta * g), jnp.float32)
                acc = fn(prod, base_a * e, base_b, acc)
        return acc if partial else acc.to_float(out_dtype)

    acc_dtype = {"f64": jnp.float64, "f32": jnp.float32}[accum]
    fn = scale_accum_fn or _scale_accum_plain
    c = jnp.zeros(out_shape, acc_dtype)
    base_a = base_a.astype(acc_dtype)
    base_b = sb.base.astype(acc_dtype)
    for g, prod in products():
        with _tracing.phase_scope("scale_accum"):
            e = jnp.asarray(2.0 ** (-beta * g), acc_dtype)
            c = fn(prod, base_a * e, base_b, c)
    return c if partial else c.astype(out_dtype)


# ---------------------------------------------------------------------------
# Ozaki-II — constant scaling + exponent-ladder accumulation
# ---------------------------------------------------------------------------

def _clog2(x: int) -> int:
    return max(0, (int(x) - 1).bit_length())


def oz2_groups(k: int, fast):
    """Anti-diagonal group indices g = s + t evaluated by the oz2 modes.

    Full mode keeps every group of the k x k pair square (g = 2..2k) — the
    complete product of the two k-slice fixed-point approximations.  Fast
    mode (``fast`` truthy: ``True`` or ``"fast2"``) keeps the diagonal
    band g <= k + 1 only: on the shared grid the dropped pairs all lie at
    least ``beta * k`` bits below the (global, or per-row for fast2)
    product magnitude, i.e. at the splitting-truncation level itself.
    """
    return range(2, (k + 1 if fast else 2 * k) + 1)


def _oz2_group_pairs(k: int, g: int):
    return [(s, g - s) for s in range(max(1, g - k), min(k, g - 1) + 1)]


def oz2_num_pairs(k: int, fast: bool) -> int:
    """INT8 slice-pair GEMM count: k(k+1)/2 (fast band) or k^2 (full)."""
    return k * (k + 1) // 2 if fast else k * k


def _oz2_chunks(k: int, r: int, fast: bool):
    """Yield (g, [(s, t), ...]) chunks of size <= r, ascending g."""
    for g in oz2_groups(k, fast):
        pairs = _oz2_group_pairs(k, g)
        for i in range(0, len(pairs), r):
            yield g, pairs[i:i + r]


def ladder_width(n: int, k: int, beta: int, digit_bits: int,
                 word_bits: int) -> int:
    """How many consecutive anti-diagonal groups fold into ONE integer word.

    On the shared oz2 grid, group g's INT32 sum S_g carries the scalar
    exponent 2^(-beta*g), so c consecutive groups combine exactly as

        word = sum_j S_(g+j) << (beta * (c - 1 - j))

    |S_g| <= k * n * (2^digit_bits)^2 per group, hence the word needs
    ``clog2(k) + clog2(n) + 2*digit_bits + beta*(c-1) + 1`` bits.  The
    budget ``word_bits`` is 52 for an int64 word that must convert to f64
    exactly, 31 for an int32 word (the df32/f32 accumulators).
    """
    head = 1 + _clog2(k) + _clog2(n) + 2 * digit_bits
    return 1 + max(0, (word_bits - head) // beta)


def _ladder_windows(chunks, c: int):
    """Pack the ascending-g chunk list into windows spanning <= c groups."""
    windows = []
    for idx, (g, _) in enumerate(chunks):
        if windows and g - windows[-1][0][1] < c:
            windows[-1].append((idx, g))
        else:
            windows.append([(idx, g)])
    return windows


def oz2_num_highprec_adds(k: int, r: int, beta: int, n: int, fast: bool,
                          digit_bits: int, word_bits: int = 52) -> int:
    """High-precision adds of the oz2 path = number of ladder windows."""
    chunks = list(_oz2_chunks(k, r, fast))
    return len(_ladder_windows(chunks, ladder_width(n, k, beta, digit_bits,
                                                    word_bits)))


def oz2_num_chunks(k: int, r: int, fast: bool) -> int:
    """INT32 group-GEMM outputs the ladder folds (perf-model accounting:
    each is one product-tensor read in the accumulation pass)."""
    return sum(1 for _ in _oz2_chunks(k, r, fast))


def _oz2_scale(gbase_a: jax.Array, gbase_b: jax.Array, beta: int, g: int,
               dtype) -> jax.Array:
    """(*batch,) combined scalar scale ``gbaseA * gbaseB * 2^(-beta*g)``.

    The group exponent is split evenly over the two bases before the
    product so neither factor underflows on its own (2^(-beta*g) alone
    leaves the f32 range for full-mode g at large k); every factor is a
    power of two, so the arithmetic stays exact.
    """
    ea = jnp.asarray(2.0 ** (-beta * (g // 2)), dtype)
    eb = jnp.asarray(2.0 ** (-beta * (g - g // 2)), dtype)
    return (gbase_a.astype(dtype) * ea) * (gbase_b.astype(dtype) * eb)


def _oz2_accum_df32(word: jax.Array, scale: jax.Array, acc: DF32) -> DF32:
    """One ladder-window df32 step: ``acc += scale * float(word)`` with the
    exact low-8-bit int32 split (word is int32 in df32 mode)."""
    term = int32_to_df32(word)
    s = scale[..., None, None]
    return df32_add_df(acc, DF32(term.hi * s, term.lo * s))


def _oz2_accum_plain(word: jax.Array, scale: jax.Array,
                     acc: jax.Array) -> jax.Array:
    """One ladder-window plain step in ``acc.dtype`` (f64: the int64 word
    converts exactly by the ``word_bits <= 52`` budget)."""
    return acc + word.astype(acc.dtype) * scale[..., None, None]


def _oz2_unscale(acc, ra: jax.Array, rb: jax.Array):
    """The fast2 epilogue: ``C = diag(ra) C_hat diag(rb)``.

    ``ra``/``rb`` are the exact power-of-two equilibration factors
    ``base / gbase`` of the fast2 splits, so both multiplies are exact;
    for a df32 accumulator hi and lo scale by the same power of two,
    preserving the ``|lo| <= ulp(hi)/2`` invariant.  This is the default
    (inline jnp) implementation of ``matmul_oz2``'s ``unscale_fn`` hook
    (the fused path substitutes ``repro.kernels.ops.oz2_unscale_update``,
    bit-identical).
    """
    if isinstance(acc, DF32):
        ra32 = ra.astype(jnp.float32)
        rb32 = rb.astype(jnp.float32)
        return DF32(_outer_scale(acc.hi, ra32, rb32),
                    _outer_scale(acc.lo, ra32, rb32))
    return _outer_scale(acc, ra.astype(acc.dtype), rb.astype(acc.dtype))


def matmul_oz2(sa: Split, sb: Split, *, accum: str = "f64",
               out_dtype=None, fast: Union[bool, str] = False,
               r: Optional[int] = None,
               n_total: Optional[int] = None,
               digit_bits: Optional[int] = None, group_gemm_fn=None,
               partial: bool = False,
               product_reduce: Optional[Callable] = None,
               row_select: Optional[Callable] = None,
               scale_accum_fn: Optional[Callable] = None,
               unscale_fn: Optional[Callable] = None
               ) -> Union[jax.Array, DF32]:
    """Ozaki-II evaluation on constant-scaling splits.

    Needs ``Split.gbase`` (the scalar shared-grid base of
    ``splitting.split_oz2`` / ``split_oz2_bitmask``).  Every slice pair in
    anti-diagonal group g carries the SCALAR scale
    ``gbaseA * gbaseB * 2^(-beta*g)``, so (i) groups are summed inside the
    INT32 matmul unit exactly as in Alg. 6/7 (concat GEMMs, chunked by r),
    and (ii) consecutive groups additionally fold into one integer word by
    exact shifts — the exponent ladder — before a SINGLE high-precision
    convert+scale+add per window (``ladder_width`` groups at a time).
    Fast mode (``fast=True``) evaluates the g <= k+1 band (k(k+1)/2
    pairs, the classic count); full mode all k^2 pairs.

    ``fast="fast2"`` selects the improved fast-mode scaling (Kawakami &
    Takahashi): the same g <= k+1 band, but on the fast2 splits
    (``splitting.split_oz2_fast2`` / ``split_oz2_bitmask_fast2``) whose
    shared grid is the equilibrated constant ``gbase = 2`` — the ladder
    computes ``C_hat = A_hat B_hat`` of the row/column-equilibrated
    operands, and the exact power-of-two factors ``ra = base_A / gbase``
    / ``rb = base_B / gbase`` are applied as one final two-sided
    diagonal unscale ``C = diag(ra) C_hat diag(rb)`` (exact, so it
    commutes with ``partial`` reduction and rounding).  ``unscale_fn(acc,
    ra, rb)`` overrides that epilogue (the fused Pallas hook
    ``repro.kernels.ops.oz2_unscale_update``; bit-identical).

    ``partial`` / ``product_reduce`` / ``row_select`` follow the module
    contract: the cross-device sum applies to each int32 chunk product
    BEFORE the ladder fold, so the int32 mesh strategy stays
    bit-identical.
    ``scale_accum_fn(word, scale, acc)`` is the oz2 fused-epilogue hook
    (``repro.kernels.ops.oz2_scale_accum_update``): ``word`` the folded
    int32/int64 window, ``scale`` the ``(*batch,)`` scalar power of two.
    ``digit_bits`` is the slice digit magnitude (beta for truncation
    splits, beta - 1 for RN — sizes r and the ladder windows); ``n_total``
    the GLOBAL contraction length when the operands are shards.
    """
    assert sa.axis == 0 and sb.axis == 1
    if sa.gbase is None or sb.gbase is None:
        raise ValueError("oz2 accumulation needs constant-scaling splits "
                         "(split_oz2 / split_oz2_bitmask); got per-row "
                         "scales")
    fast2 = fast == "fast2"
    if fast2 and (sa.base is None or sb.base is None):
        raise ValueError("fast2 needs the per-row bases of the fast2 "
                         "splits (split_oz2_fast2 / "
                         "split_oz2_bitmask_fast2)")
    k = sa.digits.shape[0]
    assert sb.digits.shape[0] == k
    beta = sa.beta
    n = n_total if n_total is not None else sa.digits.shape[-1]
    out_shape = _rows(row_select, sa.scale[0]).shape + (sb.digits.shape[-1],)
    out_dtype = out_dtype or sa.scale.dtype
    if digit_bits is None:
        digit_bits = beta  # conservative: truncation digits span ±(2^beta-1)
    if r is None:
        r = compute_r(n, beta, digit_bits)
    use_i64 = accum == "f64" and jax.config.jax_enable_x64
    word_dtype = jnp.int64 if use_i64 else jnp.int32
    word_bits = 52 if use_i64 else 31
    c = ladder_width(n, k, beta, digit_bits, word_bits)

    gg = group_gemm_fn or (lambda pairs: group_gemm_concat(sa, sb, pairs))
    chunks = list(_oz2_chunks(k, r, fast))
    windows = _ladder_windows(chunks, c)

    def fold(window):
        """The window's chunk products, each reduced as it is made, folded
        into one integer word."""
        g_hi = window[-1][1]
        word = None
        for idx, g in window:
            with _tracing.phase_scope("group_gemm"):
                prod = gg(chunks[idx][1])
            t = _reduced(prod, product_reduce)
            with _tracing.phase_scope("ladder"):
                t = t.astype(word_dtype)
                if g_hi != g:
                    t = jnp.left_shift(t, beta * (g_hi - g))
                word = t if word is None else word + t
        return word, g_hi

    def unscale(acc):
        """The fast2 epilogue (identity otherwise): exact two-sided
        power-of-two unscale by the equilibration factors base/gbase."""
        if not fast2:
            return acc
        ra = _rows(row_select, sa.base) * (1.0 / sa.gbase[..., None])
        rb = sb.base * (1.0 / sb.gbase[..., None])
        return (unscale_fn or _oz2_unscale)(acc, ra, rb)

    if accum == "df32":
        fn = scale_accum_fn or _oz2_accum_df32
        acc = df32_zero(out_shape)
        for window in windows:
            word, g_hi = fold(window)
            with _tracing.phase_scope("scale_accum"):
                acc = fn(word, _oz2_scale(sa.gbase, sb.gbase, beta, g_hi,
                                          jnp.float32), acc)
        with _tracing.phase_scope("scale_accum"):
            acc = unscale(acc)
        return acc if partial else acc.to_float(out_dtype)

    acc_dtype = {"f64": jnp.float64, "f32": jnp.float32}[accum]
    fn = scale_accum_fn or _oz2_accum_plain
    acc = jnp.zeros(out_shape, acc_dtype)
    for window in windows:
        word, g_hi = fold(window)
        with _tracing.phase_scope("scale_accum"):
            acc = fn(word, _oz2_scale(sa.gbase, sb.gbase, beta, g_hi,
                                      acc_dtype), acc)
    with _tracing.phase_scope("scale_accum"):
        acc = unscale(acc)
    return acc if partial else acc.astype(out_dtype)

"""Public API: high-precision GEMM emulation on integer matmul units.

The four named method variants of the paper, plus the two Ozaki-II
constant-scaling variants (see docs/algorithms.md#ozaki-scheme-ii):

  ===============  ================  =====================  ====================
  name             splitting         accumulation           paper
  ===============  ================  =====================  ====================
  ``ozimmu``       bitmask (Alg3)    naive (Alg4)           Ootomo et al. (base)
  ``ozimmu_rn``    RN adapt (Alg5)   naive (Alg4)           proposed §3.1
  ``ozimmu_ef``    bitmask (Alg3)    group-EF (Alg6/7)      proposed §3.2
  ``ozimmu_h``     RN const (Alg8)   group-EF (Alg6/7)      proposed §3.3
  ``ozimmu_sm_b``  sign-magnitude    naive (Alg4)           cuBLASDx DGEMM-emu
  ``ozimmu_sm_h``  sign-magnitude    group-EF (Alg6/7)      cuBLASDx DGEMM-emu
  ``oz2_b``        oz2 trunc (const) exponent ladder        OS-II (Uchino et al.)
  ``oz2_h``        oz2 RN (const)    exponent ladder        OS-II fast-mode line
  ===============  ================  =====================  ====================

The sign-magnitude variants slice into UNSIGNED beta-bit magnitudes with
the sign carried only by the leading slice (``splitting.split_sm``):
no bit is reserved per digit for a sign, so beta reaches 8 and k slices
cover 8k-1 mantissa bits versus the signed splitters' 7k — the planner's
``auto`` picks a strictly smaller k (fewer int8 GEMMs, fewer
high-precision adds) at equal ``target_eps``.  Digit storage is int8 mod
2^8; accumulation widens through ``splitting.sm_decode`` (the
``accumulate.gemm_slice`` hook), and all scale folds stay pow2-exact, so
``:fused``, ``@mesh/int32`` and ``rhs_presplit`` remain bitwise
identical to the XLA path.

The oz2 variants share ONE power-of-two digit grid per matrix (constant
scaling), so all slice-pair scales collapse to a scalar exponent ladder:
full mode evaluates every k^2 slice pair, ``:fast`` mode only the
anti-diagonal band s + t <= k + 1, and consecutive groups fold into one
integer word before each high-precision add
(``accumulate.matmul_oz2``) — strictly fewer high-precision adds than the
group-EF path at equal k.  ``:fast2`` keeps the fast-mode band and cost
but runs it on the improved per-row equilibrated scaling (Kawakami &
Takahashi): each operand is exactly rescaled row/column-wise onto a
constant shared grid and the power-of-two factors are unscaled after the
ladder, anchoring the truncation error per row — near-full-mode accuracy
at fast-mode GEMM/add counts.

Two entry points:

  * ``ozimmu_matmul(a, b, cfg)`` — the paper's rank-2 ``(m,n)@(n,p)`` GEMM.
  * ``ozimmu_dot_general(a, b, dimension_numbers, cfg)`` — a drop-in
    emulated ``jax.lax.dot_general``: arbitrary batch dimensions and
    contraction axes.  Batch dims stay true batch dims all the way into the
    int8 slice GEMMs (no reshape-to-2D), which is what batched attention
    scores, MoE expert GEMMs and vmapped training steps need.

Both are differentiable (custom VJP written against general dimension
numbers: the cotangent contractions run through the same emulation),
jit/vmap/shard-compatible (everything is plain lax), and support f64
(paper-faithful DGEMM emulation) and f32 inputs with ``f64``/``f32``/``df32``
accumulators.

Mesh-native mode (``OzimmuConfig.mesh_axis`` / spec suffix ``@model``):
when a mesh is installed and the contraction length divides the named
axis, the contraction runs sharded under ``shard_map`` with the
cross-device accumulation kept inside the scheme — an exact INT32
reduce-scatter of each group's product that leaves C row-sharded
(bit-identical to the unsharded emulation) or, with
``mesh_reduce="df32"``, a TwoSum-compensated reduction of the partial
accumulators with one final rounding.  See docs/distributed.md.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from functools import partial
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import accumulate, splitting
from repro.obs import registry as _obs
from repro.obs import tracing as _tracing

__all__ = ["OzimmuConfig", "VARIANTS", "ozimmu_matmul", "ozimmu_dot_general",
           "parse_spec", "canonical_rhs", "variant_name"]

DimensionNumbers = Tuple[Tuple[Tuple[int, ...], Tuple[int, ...]],
                         Tuple[Tuple[int, ...], Tuple[int, ...]]]


@dataclasses.dataclass(frozen=True)
class OzimmuConfig:
    k: int = 8                      # number of slices (fixed-k configs)
    split: str = "rn_const"         # bitmask | rn | rn_const |
                                    # oz2_rn | oz2_bitmask (constant grid)
    accumulate: str = "group_ef"    # naive | group_ef | oz2 (exponent
                                    # ladder; needs an oz2_* split)
    fast: Union[bool, str] = False  # oz2 only: ``True`` (spec token
                                    # ``:fast``) evaluates the s+t <= k+1
                                    # band instead of all k^2 slice pairs;
                                    # ``"fast2"`` (token ``:fast2``) the
                                    # same band under the improved per-row
                                    # equilibrated scaling (the *_fast2
                                    # splits + exact two-sided unscale)
    accum_dtype: str = "f64"        # f64 | f32 | df32
    use_pallas: Union[bool, str] = False
                                    # False: XLA everywhere.  True: group
                                    # GEMMs through the Pallas kernel.
                                    # "fused" (spec token ``:fused``): the
                                    # whole one-HBM-pass pipeline — fused
                                    # k-slice extraction, Pallas group
                                    # GEMMs, fused convert+scale+add
                                    # epilogue (see core/plan.py docs).
    auto_k: bool = False            # spec token ``auto``: per-contraction
                                    # accuracy-driven k (core/plan.py)
    target_eps: Optional[float] = None
                                    # auto-k error target; None = the
                                    # planner default (~f64-faithful)
    target_eps_mode: str = "deterministic"
                                    # "deterministic" (worst-case eq.18
                                    # bit model) | "probabilistic" (spec
                                    # token ``:prob``: the 2506.11277
                                    # concentration model — smaller
                                    # auto-k at failure probability
                                    # target_delta; core/plan.py)
    target_delta: Optional[float] = None
                                    # probabilistic-mode per-entry failure
                                    # probability; None = the analysis
                                    # default (2^-20); <= 0 recovers the
                                    # deterministic planner exactly
    mesh_axis: Optional[str] = None  # mesh-native contraction sharding axis
    mesh_reduce: str = "int32"      # int32 (exact product psum) | df32
                                    # (compensated partial-accumulator psum)

    def with_(self, **kw) -> "OzimmuConfig":
        return dataclasses.replace(self, **kw)

    def local(self) -> "OzimmuConfig":
        """This config without the mesh-native reduction (single-device
        semantics; used inside shard_map bodies that already own the mesh
        axes — nested shard_maps are not a thing)."""
        return self.with_(mesh_axis=None) if self.mesh_axis else self


VARIANTS = {
    "ozimmu": OzimmuConfig(split="bitmask", accumulate="naive"),
    "ozimmu_rn": OzimmuConfig(split="rn", accumulate="naive"),
    "ozimmu_ef": OzimmuConfig(split="bitmask", accumulate="group_ef"),
    "ozimmu_h": OzimmuConfig(split="rn_const", accumulate="group_ef"),
    "ozimmu_sm_b": OzimmuConfig(split="sm", accumulate="naive"),
    "ozimmu_sm_h": OzimmuConfig(split="sm", accumulate="group_ef"),
    "oz2_b": OzimmuConfig(split="oz2_bitmask", accumulate="oz2"),
    "oz2_h": OzimmuConfig(split="oz2_rn", accumulate="oz2"),
}

_SPLITTERS = {
    "bitmask": splitting.split_bitmask,
    "rn": splitting.split_rn,
    "rn_const": splitting.split_rn_const,
    "sm": splitting.split_sm,
    "oz2_rn": splitting.split_oz2,
    "oz2_bitmask": splitting.split_oz2_bitmask,
    "oz2_rn_fast2": splitting.split_oz2_fast2,
    "oz2_bitmask_fast2": splitting.split_oz2_bitmask_fast2,
}


def canonical_fast2(cfg: "OzimmuConfig") -> "OzimmuConfig":
    """Tie ``cfg.fast == "fast2"`` and the ``*_fast2`` split names
    together (they are one mode; ``parse_spec`` emits them jointly, but a
    hand-built config may set only one half).  The split name is what
    keys the split cache and the presplit-compatibility check, so the
    normalization must happen before either looks at the config."""
    if cfg.fast == "fast2" and not cfg.split.endswith("_fast2"):
        return cfg.with_(split=cfg.split + "_fast2")
    if cfg.split.endswith("_fast2") and cfg.fast != "fast2":
        return cfg.with_(fast="fast2")
    return cfg

def digit_bits(cfg: "OzimmuConfig", beta: int) -> int:
    """Slice digit magnitude bits under ``cfg.split`` (sizes r / ladders);
    delegates to :func:`repro.core.splitting.digit_bits`."""
    return splitting.digit_bits(cfg.split, beta)


_VARIANT_NAMES = {(v.split, v.accumulate): name
                  for name, v in VARIANTS.items()}


def variant_name(cfg: "OzimmuConfig") -> str:
    """The ``VARIANTS`` name this config's (split, accumulate) pair maps
    back to (``*_fast2`` splits resolve to their base variant; unknown
    hand-built pairs fall back to ``split/accumulate``)."""
    split = cfg.split[:-len("_fast2")] if cfg.split.endswith("_fast2") \
        else cfg.split
    return _VARIANT_NAMES.get((split, cfg.accumulate),
                              f"{cfg.split}/{cfg.accumulate}")


def _record_emulation(cfg: "OzimmuConfig", a, p: int,
                      presplit: bool, devices: int, scatter: bool,
                      spec_cfg: "OzimmuConfig") -> None:
    """Mirror one resolved contraction into the metrics registry.

    Called from ``_bmm_impl`` after the config is fully canonical (fast2
    tied, accumulator downgraded, auto-k resolved to a concrete k), so
    the recorded counts are exactly what executes; the ``mesh`` label
    names the mesh axis only when the sharded path runs over ``devices``
    > 1, so a mesh-native spec that fell back to the local path shows
    ``mesh="none"``.  Host-side only: runs
    once per eager call or per jit *trace* — a compiled step that traced
    through here replays the same contraction on every execution, so
    trace-time counts are per-execution counts; a scan body, traced once
    for all its steps, counts ``registry.repetitions()`` times (the
    ``registry.repeated`` scopes around the scan).  Costs come from the
    same ``Plan`` accounting the planner uses, which is what makes
    observed == planned a testable invariant (tests/test_obs.py).
    Shapes/dtypes only — never touches values, so tracers stay clean and
    outputs are bitwise-identical with obs on or off.

    ``emulation.int8_gemms_planned`` prices the same contraction with the
    planner's static (shape-only) plan under ``spec_cfg``, the config
    before k was fixed: in a jitted program, where auto-k resolves
    statically and a frozen split carries the k it froze, it must equal
    ``emulation.int8_gemms``.

    ``emulation.kernel_operand_copy_bytes`` (Pallas paths only) counts the
    digit copies the group-GEMM wrapper makes for its kernel
    (``kernels.ops.operand_copy_bytes``): 0 for int8 digits, whose slice
    pairs the kernel reads in place; the bf16 decode for sign-magnitude.

    ``emulation.product_reduce_bytes`` counts the int32 bytes one device
    sends into the exact cross-device reduction of the slice/group
    products (``mesh_reduce="int32"``): (D-1)/D of every product under
    the reduce-scatter (``scatter``), twice that under a ``psum``; 0 on
    one device and under the df32 strategy, which reduces accumulators.
    """
    from repro.core import plan as _plan
    m, n = a.shape[-2], a.shape[-1]
    # canonical operands share batch dims; b is (*batch, n, p)
    batch = int(np.prod(a.shape[:-2], dtype=np.int64)) if a.ndim > 2 else 1
    pl = _plan.plan_contraction(cfg, m, n, p)
    static = _plan.plan_contraction(spec_cfg, m, n, p, a=a, _record=False)
    labels = dict(
        variant=variant_name(cfg), k=cfg.k,
        path=("fused" if cfg.use_pallas == "fused"
              else "pallas" if cfg.use_pallas else "xla"),
        mesh=cfg.mesh_axis if devices > 1 else "none",
        presplit=int(presplit))
    reg = _obs.get_registry()
    # inside the trace of a scan body, one trace stands for every step
    r = _obs.repetitions()
    reg.inc("emulation.calls", r, **labels)
    reg.inc("emulation.int8_gemms", r * batch * pl.int8_gemms, **labels)
    reg.inc("emulation.int8_gemms_planned", r * batch * static.int8_gemms,
            **labels)
    reg.inc("emulation.highprec_adds", r * batch * pl.highprec_adds,
            **labels)
    sent = 0
    if devices > 1 and cfg.mesh_reduce == "int32":
        products = (accumulate.oz2_num_chunks(cfg.k, pl.r, cfg.fast)
                    if cfg.accumulate == "oz2" else pl.highprec_adds)
        sent = ((1 if scatter else 2) * (devices - 1) * products * batch
                * m * p * 4 // devices)
    reg.inc("emulation.product_reduce_bytes", r * sent, **labels)
    itemsize = np.dtype(a.dtype).itemsize
    split_elems = batch * m * n            # A is always split in-call;
    if not presplit:                       # B only when no frozen Split
        split_elems += batch * n * p
    reg.inc("emulation.split_bytes", r * split_elems * itemsize, **labels)
    if cfg.use_pallas:
        from repro.kernels import ops as kops  # lazy: kernels are optional
        reg.inc("emulation.kernel_operand_copy_bytes",
                r * kops.operand_copy_bytes(
                    cfg.k, batch, m, n, p,
                    signmag=splitting.is_signmag(cfg.split)), **labels)


_MESH_REDUCES = ("int32", "df32")


def parse_spec(spec: str) -> OzimmuConfig:
    """Parse ``"ozimmu_h-8"`` / ``"oz2_h-auto:fast"`` style strings.

    Full grammar (docs/engine.md):
    ``variant["-"k][":"opt]*["@"mesh_axis["/"mesh_reduce]]`` where ``k`` is
    an integer or ``auto`` (per-contraction accuracy-driven slice count,
    core/plan.py) and each ``:opt`` is an accumulator dtype
    (``f64``/``f32``/``df32``), ``fused`` (the one-HBM-pass Pallas
    pipeline), ``prob`` (auto-k specs only, any variant: resolve k under
    the probabilistic eps model — ``target_eps_mode="probabilistic"``,
    core/plan.py), or — for the ``oz2_*`` variants only — ``fast``
    (evaluate the anti-diagonal band s + t <= k + 1 instead of all k^2
    slice pairs) or ``fast2`` (the same band under the improved per-row
    equilibrated scaling — near-full-mode accuracy at fast-mode cost;
    mutually exclusive with ``fast``).
    E.g. ``"ozimmu_h-auto:df32:fused@model"`` runs the fused pipeline,
    contraction-sharded over the ``model`` mesh axis with the exact int32
    cross-device reduction, with auto-planned k; ``"oz2_h-auto:fast"``
    runs the Ozaki-II fast mode with auto-planned k against the oz2 error
    model; ``"oz2_h-auto:fast2"`` the improved-scaling fast mode;
    ``"...@model/df32"`` selects the compensated
    partial-accumulator reduction instead (see docs/distributed.md).
    """
    full_spec = spec
    mesh_axis, mesh_reduce = None, "int32"
    if "@" in spec:
        spec, mesh = spec.split("@", 1)
        mesh_axis, _, reduce_str = mesh.partition("/")
        if reduce_str:
            mesh_reduce = reduce_str
        if not mesh_axis or not mesh_axis.isidentifier():
            raise ValueError(f"bad mesh axis {mesh_axis!r} in engine spec")
        if mesh_reduce not in _MESH_REDUCES:
            raise ValueError(f"unknown mesh reduce {mesh_reduce!r}; "
                             f"options: {_MESH_REDUCES}")
    accum_dtype, use_pallas, fast, prob = "f64", False, False, False
    spec, *opts = spec.split(":")
    seen_accum = False
    for opt in opts:
        if opt in ("f64", "f32", "df32"):
            if seen_accum:
                raise ValueError(f"duplicate accumulator dtype {opt!r} "
                                 f"in engine spec")
            accum_dtype, seen_accum = opt, True
        elif opt == "fused":
            if use_pallas == "fused":
                raise ValueError("duplicate 'fused' token in engine spec")
            use_pallas = "fused"
        elif opt == "prob":
            if prob:
                raise ValueError("duplicate 'prob' token in engine spec")
            prob = True
        elif opt in ("fast", "fast2"):
            if fast == (opt if opt == "fast2" else True):
                raise ValueError(f"duplicate {opt!r} token in engine spec")
            if fast:
                raise ValueError(f"conflicting fast-mode tokens in engine "
                                 f"spec: {opt!r} after "
                                 f"{'fast2' if fast == 'fast2' else 'fast'!r}"
                                 f" (pick one)")
            fast = "fast2" if opt == "fast2" else True
        else:
            raise ValueError(f"unknown engine spec option {opt!r}; "
                             f"options: f64, f32, df32, fused, fast, "
                             f"fast2, prob")
    name, _, kstr = spec.partition("-")
    if name not in VARIANTS:
        raise ValueError(f"unknown ozimmu variant {name!r}; "
                         f"options: {sorted(VARIANTS)}")
    auto_k = kstr == "auto"
    if kstr and not auto_k and (not kstr.isdigit() or int(kstr) < 1):
        raise ValueError(f"bad slice count {kstr!r} in engine spec "
                         f"(an integer >= 1, or 'auto')")
    cfg = VARIANTS[name]
    if fast and cfg.accumulate != "oz2":
        token = "fast2" if fast == "fast2" else "fast"
        raise ValueError(f"the {token!r} token applies to the oz2_* "
                         f"variants only (the ozimmu family always "
                         f"evaluates the fast-mode band); got {name!r}")
    if prob and not auto_k:
        raise ValueError(f"the 'prob' token (probabilistic "
                         f"target_eps_mode) applies to auto-k specs only "
                         f"— a fixed slice count leaves the planner "
                         f"nothing to resolve; got {name!r} with "
                         f"k={kstr or cfg.k}, want e.g. {name}-auto:prob")
    if use_pallas == "fused" and accum_dtype == "f64" \
            and jax.default_backend() == "tpu":
        raise ValueError(
            f"engine spec {full_spec!r} cannot run on a TPU: its ':fused' "
            f"kernels would need an f64 accumulator (and, for oz2, an "
            f"int64 ladder word), which Mosaic does not compile; use "
            f"':df32' or drop ':fused'")
    return canonical_fast2(cfg.with_(
        k=cfg.k if (auto_k or not kstr) else int(kstr),
        auto_k=auto_k, accum_dtype=accum_dtype,
        use_pallas=use_pallas, fast=fast,
        target_eps_mode="probabilistic" if prob else "deterministic",
        mesh_axis=mesh_axis, mesh_reduce=mesh_reduce))


def split_operands(a: jax.Array, b: jax.Array, cfg: OzimmuConfig, *,
                   n_total: Optional[int] = None, rowmax_reduce=None,
                   rhs_presplit: Optional[splitting.Split] = None):
    """Step (i)+(ii): slice A row-wise and B column-wise.

    a (*batch, m, n), b (*batch, n, p) — scales are per batch element.
    ``n_total`` overrides the contraction length used for beta (eq. 4) when
    ``a``/``b`` are per-device shards of a longer contraction;
    ``rowmax_reduce`` (e.g. a mesh-axis ``pmax``) then makes the digit
    grids globally agreed — see docs/distributed.md.

    ``rhs_presplit`` short-circuits the B side entirely: a frozen
    column-scale :class:`~repro.core.splitting.Split` (from
    ``repro.core.split_cache``) is used as-is and only A is split — the
    serving-time path where B is a static weight matrix.  ``b`` may then
    be ``None``.

    With ``cfg.use_pallas == "fused"`` the extraction runs through the
    one-HBM-pass Pallas kernel (``kernels.ops.split_fused``) for the
    geometric strategies; the adaptive RN strategy needs a fresh row-max
    per slice and keeps the library splitter (its k re-reads are the
    point the paper's Alg. 8 removes).  Digits and scales are
    bit-identical either way.
    """
    n = n_total if n_total is not None else a.shape[-1]
    beta = splitting.beta_for(cfg.split, n)
    sa = _split_side(a, cfg, beta, 0, rowmax_reduce)
    if rhs_presplit is not None:
        return sa, rhs_presplit
    return sa, _split_side(b, cfg, beta, 1, rowmax_reduce)


def _split_side(x: jax.Array, cfg: OzimmuConfig, beta: int, axis: int,
                rowmax_reduce=None) -> splitting.Split:
    """One operand of :func:`split_operands`: A row-wise (``axis=0``), B
    column-wise (``axis=1``)."""
    if cfg.use_pallas == "fused" and cfg.split != "rn":
        # every constant-ratio strategy fuses: per-row grids (bitmask,
        # rn_const) and the oz2 shared constant grids alike
        from repro.kernels import ops as kops  # lazy: kernels are optional
        return kops.split_fused(x, cfg.k, beta, mode=cfg.split, axis=axis,
                                rowmax_reduce=rowmax_reduce)
    return _SPLITTERS[cfg.split](x, cfg.k, beta=beta, axis=axis,
                                 rowmax_reduce=rowmax_reduce)


def _bmm_local(a: jax.Array, b: jax.Array, cfg: OzimmuConfig, *,
               n_total: Optional[int] = None, rowmax_reduce=None,
               product_reduce=None, partial: bool = False,
               rhs_presplit: Optional[splitting.Split] = None):
    """Single-device emulated batched matmul (the shard-local body of the
    mesh-native path when the distributed hooks are given).

    ``cfg.use_pallas``: ``True`` routes the group GEMMs through the Pallas
    kernel; ``"fused"`` additionally replaces the per-slice splitter loop
    (``split_operands`` above) and the convert→scale→add epilogue with the
    one-HBM-pass kernels — every stage bit-identical to the XLA path, so
    the distributed hooks and ``partial`` compose unchanged.
    ``rhs_presplit`` (serving): B's frozen Split; the B-side splitter is
    skipped entirely and ``b`` may be ``None``.
    """
    with _tracing.phase_scope("split"):
        sa, sb = split_operands(a, b, cfg, n_total=n_total,
                                rowmax_reduce=rowmax_reduce,
                                rhs_presplit=rhs_presplit)
    return _contract(sa, sb, cfg, a.dtype, n_total=n_total,
                     product_reduce=product_reduce, partial=partial)


def _contract(sa: splitting.Split, sb: splitting.Split, cfg: OzimmuConfig,
              out_dtype, *, n_total: Optional[int] = None,
              product_reduce=None, row_select=None, partial: bool = False):
    """Steps (iii)+(iv) on split operands: the int8 slice-pair GEMMs and
    their high-precision accumulation (``accumulate``'s hooks
    ``product_reduce``, ``row_select`` and ``partial`` passed through)."""
    group_gemm_fn = scale_accum_fn = pair_gemm_fn = unscale_fn = None
    if cfg.use_pallas:
        from repro.kernels import ops as kops  # lazy: kernels are optional
        # the kernel's digit stacks are prepared once per contraction
        gemm = kops.group_gemm_fn(sa, sb)
        if cfg.accumulate == "naive":
            # naive accumulation has no groups; each slice pair runs as a
            # G=1 Pallas GEMM (bit-identical to the XLA dot_general)
            pair_gemm_fn = lambda s, t: gemm([(s, t)])
        else:
            group_gemm_fn = gemm
        if cfg.use_pallas == "fused":
            scale_accum_fn = (kops.oz2_scale_accum_update
                              if cfg.accumulate == "oz2"
                              else kops.scale_accum_update)
            unscale_fn = kops.oz2_unscale_update
    hooks = dict(accum=cfg.accum_dtype, out_dtype=out_dtype, partial=partial,
                 product_reduce=product_reduce, row_select=row_select,
                 scale_accum_fn=scale_accum_fn)
    if cfg.accumulate == "naive":
        return accumulate.matmul_naive(sa, sb, pair_gemm_fn=pair_gemm_fn,
                                       **hooks)
    n = n_total if n_total is not None else sa.digits.shape[-1]
    if cfg.accumulate == "oz2":
        return accumulate.matmul_oz2(
            sa, sb, fast=cfg.fast, n_total=n,
            digit_bits=digit_bits(cfg, sa.beta), group_gemm_fn=group_gemm_fn,
            unscale_fn=unscale_fn, **hooks)
    r = splitting.compute_r(n, sa.beta)
    return accumulate.matmul_group_ef(sa, sb, r=r,
                                      group_gemm_fn=group_gemm_fn, **hooks)


def _scatters(cfg: OzimmuConfig, m: int, devices: int,
              presplit: bool) -> bool:
    """Whether the sharded path reduces each int32 product by a
    reduce-scatter over C's ``m`` rows, leaving C row-sharded: under the
    int32 strategy, when the rows divide the axis and B is split in-call.
    Otherwise (and for the frozen B of serving) a ``psum`` leaves the whole
    C on every device."""
    return (cfg.mesh_reduce == "int32" and not presplit
            and m % devices == 0)


# C's columns are contracted in blocks only where each block is a whole
# number of the kernels' 128-lane column tiles
_LANES = 128

# Splits whose digit grid is one power of two for the whole matrix
# (``splitting._global_base``): a block of B's columns split on its own
# would get a grid of its own, so B is contracted whole.
_WHOLE_MATRIX_GRIDS = ("oz2_rn", "oz2_bitmask")


def _column_blocks(cfg: OzimmuConfig, p: int, devices: int) -> int:
    """How many column blocks of C the reduce-scatter path contracts one
    after another: ``devices`` where each is whole lane tiles and B's
    digit grid is per column, else 1.  A block's int32 group partial,
    (m, p/devices) on each device, is then no larger than the device's
    own rows of C; the whole (m, p) partial of a large product would not
    fit beside the operands and their digits."""
    if cfg.split in _WHOLE_MATRIX_GRIDS or p % (devices * _LANES):
        return 1
    return devices


@functools.lru_cache(maxsize=256)
def _sharded_fn(cfg: OzimmuConfig, mesh, nb: int, m: int, n_total: int,
                p: int, out_dtype, presplit_meta=None) -> "callable":
    """The jitted shard_map callable for one (config, mesh, shape) cell.

    Cached so repeated *eager* mesh-native contractions reuse one
    compiled entry instead of re-wrapping a fresh closure in ``jax.jit``
    per call (which would defeat jit's own cache).  Inside an outer jit
    it inlines for free.

    Under the int32 strategy each group's int32 partial product is
    reduced as soon as it exists, by one reduce-scatter over C's rows
    (``_scatters``): each device ends with the exact sum of its own m/D
    rows, runs the high-precision epilogue on those rows alone, and C
    leaves the shard_map row-sharded, ``P(axis, None)``; a caller that
    needs C replicated gets it by an all-gather from jit/GSPMD.  B's
    columns are split and contracted in ``_column_blocks`` blocks, one
    after another, so that no whole (m, p) partial is held where B's
    digit grid is per column.  Where the
    rows do not divide the axis, or B is frozen, each product is
    ``psum``'d instead and C is replicated.

    ``presplit_meta`` (serving): ``(beta, has_base, has_gbase, signmag)``
    of a frozen B-side Split — the callable then takes ``(a, (digits,
    scale, base, gbase))`` with the cached digit slices sharded along
    their contraction axis (they "live pre-sharded": splitting is
    elementwise given the grid, so the shard of the full-matrix digits
    equals the pmax-agreed shard-local split) and skips the B splitter
    entirely.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import collectives

    axis = cfg.mesh_axis
    devices = dict(mesh.shape)[axis]
    scatter = _scatters(cfg, m, devices, presplit_meta is not None)
    a_spec = P(*((None,) * (nb + 1) + (axis,)))
    out_specs = P(*((None,) * nb + ((axis, None) if scatter
                                    else (None, None))))
    local_cfg = cfg.local()
    rowmax_reduce = lambda v: collectives.pmax_scales(v, axis)

    if presplit_meta is None:
        in_specs = (a_spec, P(*((None,) * nb + (axis, None))))
        unpack = lambda operand: (operand, None)
    else:
        beta, has_base, has_gbase, signmag = presplit_meta
        # digits (k, *batch, n, p) shard on n; scales/bases replicated
        in_specs = (a_spec,
                    (P(*((None,) * (nb + 1) + (axis, None))), P(),
                     P() if has_base else None,
                     P() if has_gbase else None))

        def unpack(operand):
            digits, scale, base, gbase = operand
            return None, splitting.Split(digits, scale, base, beta, 1,
                                         gbase=gbase, signmag=signmag)

    if scatter:
        rows = m // devices
        blocks = _column_blocks(local_cfg, p, devices)
        width = p // blocks
        beta = splitting.beta_for(cfg.split, n_total)

        def body(al, bl):
            first = jax.lax.axis_index(axis) * rows
            hooks = dict(
                n_total=n_total,
                product_reduce=lambda q: collectives.psum_exact_int32(
                    q, axis, scatter_dimension=nb),
                row_select=lambda v: jax.lax.dynamic_slice_in_dim(
                    v, first, rows, axis=-1))
            with _tracing.phase_scope("split"):
                sa = _split_side(al, local_cfg, beta, 0, rowmax_reduce)
            out = []
            for c in range(blocks):
                with _tracing.phase_scope("split"):
                    sb = _split_side(bl[..., c * width:(c + 1) * width],
                                     local_cfg, beta, 1, rowmax_reduce)
                out.append(_contract(sa, sb, local_cfg, al.dtype, **hooks))
            return jnp.concatenate(out, axis=-1) if blocks > 1 else out[0]
    elif cfg.mesh_reduce == "int32":
        def body(al, operand):
            bl, sb = unpack(operand)
            return _bmm_local(
                al, bl, local_cfg, n_total=n_total,
                rowmax_reduce=rowmax_reduce,
                product_reduce=lambda q: collectives.psum_exact_int32(
                    q, axis),
                rhs_presplit=sb)
    else:
        def body(al, operand):
            bl, sb = unpack(operand)
            part = _bmm_local(al, bl, local_cfg, n_total=n_total,
                              partial=True, rhs_presplit=sb)
            if isinstance(part, accumulate.DF32):
                return collectives.psum_df32(part, axis).to_float(out_dtype)
            return collectives.psum_compensated(part, axis).astype(out_dtype)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, axis_names={axis},
                                 check_vma=False))


def _bmm_sharded(a: jax.Array, b: jax.Array, cfg: OzimmuConfig, mesh,
                 rhs_presplit: Optional[splitting.Split] = None) -> jax.Array:
    """Mesh-native emulated batched matmul: contraction axis sharded over
    ``cfg.mesh_axis``, cross-device accumulation inside the scheme.

    Strategy ``int32`` (default): row/col maxima are agreed across shards
    (one ``pmax``), each INT32 slice/group product is summed exactly over
    the axis as soon as it exists (one reduce-scatter over C's rows per
    group, C returned row-sharded; a ``psum`` per group where the rows do
    not divide the axis or B is frozen), and the high-precision
    accumulation runs on the already-global products — bit-identical to
    the unsharded emulation.  Strategy ``df32``: each shard accumulates
    its local partial (local scales, no pmax pre-pass), and the partial
    accumulators are merged with a TwoSum-compensated reduction — one
    all-gather for the whole GEMM, error-free in the two-float
    representation, with the single final rounding after the merge.

    With ``rhs_presplit`` the cached B digits enter the shard_map sharded
    along their contraction axis; bit-identity with the unsharded presplit
    path is preserved for the int32 strategy (the cached full-matrix grid
    IS the pmax-agreed grid).  Under the df32 strategy the cached B grid
    is the globally-agreed one (computed from the full matrix) rather
    than each shard's local grid — a valid splitting either way; the
    compensated merge semantics are unchanged.
    """
    nb = a.ndim - 2
    m, n, p = a.shape[-2], a.shape[-1], b.shape[-1]
    if rhs_presplit is None:
        return _sharded_fn(cfg, mesh, nb, m, n, p, a.dtype)(a, b)
    sp = rhs_presplit
    meta = (int(sp.beta), sp.base is not None, sp.gbase is not None,
            bool(sp.signmag))
    fn = _sharded_fn(cfg, mesh, nb, m, n, p, a.dtype, meta)
    return fn(a, (sp.digits, sp.scale, sp.base, sp.gbase))


def _mesh_for(cfg: OzimmuConfig, n: int):
    """The installed mesh if the mesh-native path applies, else None
    (mesh absent, axis missing or trivial, or contraction indivisible —
    the caller falls back to the single-device emulation under GSPMD)."""
    if cfg.mesh_axis is None:
        return None
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or cfg.mesh_axis not in mesh.axis_names:
        return None
    size = dict(mesh.shape)[cfg.mesh_axis]
    if size <= 1 or n % size != 0:
        return None
    return mesh


def _check_presplit(a: jax.Array, b_shape, cfg: OzimmuConfig,
                    sp: splitting.Split) -> None:
    """Static consistency checks between a frozen B split and the call."""
    n = a.shape[-1]
    beta = splitting.beta_for(cfg.split, n)
    if sp.axis != 1:
        raise ValueError(f"rhs_presplit must carry column scales (axis=1), "
                         f"got axis={sp.axis}")
    # strategy mismatch first: a signed-vs-signmag disagreement also skews
    # beta, and the actionable diagnosis is the digit convention
    if bool(sp.signmag) != splitting.is_signmag(cfg.split):
        raise ValueError(
            f"rhs_presplit signmag={bool(sp.signmag)} does not match the "
            f"config's split {cfg.split!r}; sign-magnitude digits decode "
            f"differently from signed digits — re-freeze under the "
            f"current spec")
    if sp.beta != beta:
        raise ValueError(f"rhs_presplit beta={sp.beta} disagrees with the "
                         f"contraction's beta={beta} (n={n}); the split was "
                         f"frozen for a different contraction length")
    if tuple(sp.digits.shape[1:]) != tuple(b_shape):
        raise ValueError(f"rhs_presplit digits {sp.digits.shape} do not "
                         f"match the canonical rhs {tuple(b_shape)}")
    if sp.digits.shape[0] != cfg.k:
        raise ValueError(f"rhs_presplit has k={sp.digits.shape[0]} slices, "
                         f"config wants k={cfg.k}; re-freeze under the "
                         f"current spec")
    if cfg.accumulate == "oz2" and sp.gbase is None:
        raise ValueError("oz2 accumulation needs a constant-scaling "
                         "presplit (gbase); the cached split was frozen "
                         "under a per-row strategy")
    if cfg.accumulate == "group_ef" and sp.base is None:
        raise ValueError("group-EF accumulation needs geometric slice "
                         "scales; the cached split was frozen under the "
                         "adaptive RN strategy")
    if sp.scale.dtype != a.dtype:
        raise ValueError(f"rhs_presplit scales are {sp.scale.dtype}, the "
                         f"contraction computes in {a.dtype}; freeze the "
                         f"weight in the engine's compute dtype")


def _bmm_impl(a: jax.Array, b: jax.Array, cfg: OzimmuConfig,
              rhs_presplit: Optional[splitting.Split] = None) -> jax.Array:
    """Emulated batched matmul on canonical operands:
    (*batch, m, n) @ (*batch, n, p) -> (*batch, m, p)."""
    if a.ndim < 2 or b.ndim < 2 or a.shape[-1] != b.shape[-2] or \
            a.shape[:-2] != b.shape[:-2]:
        raise ValueError(f"bad batched GEMM shapes {a.shape} @ {b.shape}")
    cfg = canonical_fast2(cfg)
    if cfg.accum_dtype == "f64" and not jax.config.jax_enable_x64:
        # without x64 mode JAX truncates f64 to f32 anyway; downgrade
        # explicitly (the documented footgun — see docs/engine.md) instead
        # of emitting one truncation warning per accumulation step
        cfg = cfg.with_(accum_dtype="f32")
    spec_cfg = cfg
    if cfg.auto_k:
        if rhs_presplit is not None:
            # the cache resolved auto-k at freeze time with the static
            # mantissa-coverage plan (split_cache.resolved_k) — the same
            # plan a jitted call resolves to; adopt the frozen k so the
            # two paths agree bitwise.
            cfg = cfg.with_(k=int(rhs_presplit.digits.shape[0]),
                            auto_k=False)
        else:
            # accuracy-driven slice count (core/plan.py): probes concrete
            # operands eagerly; inside a jit trace it resolves to the
            # static mantissa-coverage plan.  Resolved BEFORE the mesh
            # dispatch so the jitted sharded entry is cached on the
            # concrete k.
            from repro.core import plan as _plan
            cfg = cfg.with_(k=_plan.auto_k(a, b, cfg), auto_k=False)
    if rhs_presplit is not None:
        _check_presplit(a, b.shape, cfg, rhs_presplit)
    mesh = _mesh_for(cfg, a.shape[-1])
    if _obs.enabled():
        presplit = rhs_presplit is not None
        devices = dict(mesh.shape)[cfg.mesh_axis] if mesh is not None else 1
        _record_emulation(cfg, a, b.shape[-1], presplit, devices,
                          _scatters(cfg, a.shape[-2], devices, presplit),
                          spec_cfg=spec_cfg)
    if mesh is not None:
        return _bmm_sharded(a, b, cfg, mesh, rhs_presplit)
    return _bmm_local(a, b, cfg.local(), rhs_presplit=rhs_presplit)


# ---------------------------------------------------------------------------
# general dot_general: canonicalization + implementation
# ---------------------------------------------------------------------------

def _canonicalize_dnums(dimension_numbers) -> DimensionNumbers:
    """Nested tuples (hashable: dimension_numbers is a nondiff VJP arg)."""
    (ac, bc), (ab, bb) = dimension_numbers
    return ((tuple(map(int, ac)), tuple(map(int, bc))),
            (tuple(map(int, ab)), tuple(map(int, bb))))


def _remaining(ndim: int, *exclude: Sequence[int]):
    ex = set()
    for e in exclude:
        ex.update(e)
    return [i for i in range(ndim) if i not in ex]


def _ranges_like(*seqs):
    start = 0
    out = []
    for s in seqs:
        out.append(list(range(start, start + len(s))))
        start += len(s)
    return out


def _argsort(seq):
    return sorted(range(len(seq)), key=seq.__getitem__)


def canonical_rhs(b: jax.Array, dnums: DimensionNumbers):
    """The rhs of ``dot_general(a, b, dnums)`` in the canonical batched
    layout ``(*batch, n, p)`` the emulation contracts, plus the total
    contraction length n.  This is the exact transpose/reshape
    ``_dot_general_impl`` performs — the layout a frozen B-side Split
    (``repro.core.split_cache``) must be computed against."""
    (_, bc), (_, bb) = dnums
    b_free = _remaining(b.ndim, bc, bb)
    batch_shape = tuple(b.shape[i] for i in bb)
    n = math.prod(b.shape[i] for i in bc)
    p = math.prod(b.shape[i] for i in b_free)
    b3 = jnp.transpose(b, list(bb) + list(bc) + b_free).reshape(
        batch_shape + (n, p))
    return b3, n


def _dot_general_impl(a: jax.Array, b: jax.Array,
                      dnums: DimensionNumbers, cfg: OzimmuConfig,
                      rhs_presplit: Optional[splitting.Split] = None
                      ) -> jax.Array:
    """Normalize to the canonical batched form and run the emulation.

    Layout convention matches ``jax.lax.dot_general``: output is
    (*batch [lhs order], *lhs free [ascending], *rhs free [ascending]).
    Multiple contraction axes are flattened into one inner dimension (beta /
    r are computed from the TOTAL contraction length, so the INT32
    no-overflow guarantees still hold); free axes flatten into m / p and are
    restored afterwards — batch axes are never flattened away.

    With ``rhs_presplit`` the canonical ``b3`` is only used for static
    shape checks and the emulation consumes the frozen digits instead (the
    transpose/reshape of ``b`` is dead code XLA eliminates).
    """
    (ac, bc), (ab, bb) = dnums
    if len(ac) != len(bc) or len(ab) != len(bb):
        raise ValueError(f"mismatched dimension numbers {dnums}")
    for i, j in zip(ac, bc):
        if a.shape[i] != b.shape[j]:
            raise ValueError(
                f"contraction size mismatch {a.shape} @ {b.shape}: {dnums}")
    for i, j in zip(ab, bb):
        if a.shape[i] != b.shape[j]:
            raise ValueError(
                f"batch size mismatch {a.shape} @ {b.shape}: {dnums}")
    a_free = _remaining(a.ndim, ac, ab)
    batch_shape = tuple(a.shape[i] for i in ab)
    m_shape = tuple(a.shape[i] for i in a_free)
    p_shape = tuple(b.shape[i] for i in _remaining(b.ndim, bc, bb))
    m = math.prod(m_shape)
    n = math.prod(a.shape[i] for i in ac)
    # (*batch, m, n) with contraction axes in pairing order (ac[i] <-> bc[i])
    a3 = jnp.transpose(a, list(ab) + a_free + list(ac)).reshape(
        batch_shape + (m, n))
    b3, _ = canonical_rhs(b, dnums)
    out = _bmm_impl(a3, b3, cfg, rhs_presplit=rhs_presplit)
    return out.reshape(batch_shape + m_shape + p_shape)


# ---------------------------------------------------------------------------
# custom VJP against general dimension numbers
# ---------------------------------------------------------------------------

def _transpose_operand(g, other, target_ndim: int, dnums: DimensionNumbers,
                       cfg: OzimmuConfig, swap_ans: bool):
    """Cotangent of the lhs of ``dot_general(x, y, dnums)`` (mirror of
    jax._src.lax's ``_dot_general_transpose_lhs``, with the contraction
    itself emulated).  For the rhs cotangent, call with the roles of x and y
    swapped in ``dnums`` and ``swap_ans=True``."""
    (xc, yc), (xb, yb) = dnums
    x_kept = _remaining(target_ndim, xc, xb)
    y_kept = _remaining(other.ndim, yc, yb)
    if swap_ans:
        g_batch, g_y_kept, _ = _ranges_like(xb, y_kept, x_kept)
    else:
        g_batch, _, g_y_kept = _ranges_like(xb, x_kept, y_kept)
    dims = ((tuple(g_y_kept), tuple(y_kept)), (tuple(g_batch), tuple(yb)))
    dx = _dot_general_impl(g, other, _canonicalize_dnums(dims), cfg)
    xc_sorted_by_yc = [xc[i] for i in _argsort(yc)]
    out_axes = _argsort(list(xb) + x_kept + xc_sorted_by_yc)
    return jnp.transpose(dx, out_axes)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _oz_dot_general(a: jax.Array, b: jax.Array, dnums: DimensionNumbers,
                    cfg: OzimmuConfig) -> jax.Array:
    return _dot_general_impl(a, b, dnums, cfg)


def _fwd(a, b, dnums, cfg):
    return _dot_general_impl(a, b, dnums, cfg), (a, b)


def _bwd(dnums, cfg, res, g):
    a, b = res
    (ac, bc), (ab, bb) = dnums
    # Cotangents through the same emulated contraction (transposed dims are
    # free re-slices; no precision leaves the scheme).
    da = _transpose_operand(g, b, a.ndim, dnums, cfg, swap_ans=False)
    db = _transpose_operand(g, a, b.ndim, ((bc, ac), (bb, ab)), cfg,
                            swap_ans=True)
    return da, db


_oz_dot_general.defvjp(_fwd, _bwd)


# --- presplit variant: B's frozen Split rides along as a (nondifferentiable)
# pytree of arrays.  The cotangent contractions re-slice transposed operands
# under different dimension numbers, so the frozen B split never applies to
# the backward pass — both cotangents run the regular emulation, identical
# to `_bwd` above.

def _rebuild_split(arrays, beta: int, cfg: OzimmuConfig) -> splitting.Split:
    digits, scale, base, gbase = arrays
    return splitting.Split(digits, scale, base, beta, 1, gbase=gbase,
                           signmag=splitting.is_signmag(cfg.split))


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _oz_dot_general_presplit(a, b, presplit_arrays, dnums, cfg, beta):
    return _dot_general_impl(a, b, dnums, cfg,
                             rhs_presplit=_rebuild_split(presplit_arrays,
                                                         beta, cfg))


def _presplit_fwd(a, b, presplit_arrays, dnums, cfg, beta):
    out = _dot_general_impl(a, b, dnums, cfg,
                            rhs_presplit=_rebuild_split(presplit_arrays,
                                                        beta, cfg))
    return out, (a, b, jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), presplit_arrays))


def _zero_cotangent(aval):
    import numpy as np
    if jnp.issubdtype(aval.dtype, jnp.floating):
        return jnp.zeros(aval.shape, aval.dtype)
    return np.zeros(aval.shape, jax.dtypes.float0)  # int digits


def _presplit_bwd(dnums, cfg, beta, res, g):
    a, b, presplit_avals = res
    (ac, bc), (ab, bb) = dnums
    da = _transpose_operand(g, b, a.ndim, dnums, cfg, swap_ans=False)
    db = _transpose_operand(g, a, b.ndim, ((bc, ac), (bb, ab)), cfg,
                            swap_ans=True)
    return da, db, jax.tree.map(_zero_cotangent, presplit_avals)


_oz_dot_general_presplit.defvjp(_presplit_fwd, _presplit_bwd)


def ozimmu_dot_general(a: jax.Array, b: jax.Array, dimension_numbers,
                       cfg: OzimmuConfig = VARIANTS["ozimmu_h"],
                       rhs_presplit: Optional[splitting.Split] = None
                       ) -> jax.Array:
    """Emulated ``jax.lax.dot_general`` via k-slice INT8 GEMMs.

    ``dimension_numbers`` is the standard lax contract,
    ``((lhs_contract, rhs_contract), (lhs_batch, rhs_batch))``; the output
    layout is lax's (batch dims, lhs free dims, rhs free dims).  Batch
    dimensions are carried natively through splitting (per-batch row/col
    scales) and the int8 ``dot_general``s.  Differentiable: the custom VJP
    evaluates both cotangents with the same emulation under the transposed
    dimension numbers.

    ``rhs_presplit`` (serving fast path): a frozen column-scale
    :class:`~repro.core.splitting.Split` of the canonical rhs — from
    :class:`repro.core.split_cache.SplitCache` — makes the call skip the
    B-side splitter entirely, bit-identical to the uncached path (the
    splitter is deterministic; freezing merely hoists it).  The split
    must have been frozen for these exact dimension numbers, contraction
    length, spec, and compute dtype (checked statically).  Gradients
    still flow to both operands through the regular emulated cotangent
    contractions (the frozen split only accelerates the forward).

    Example — batched attention-score-like contraction::

        out = ozimmu_dot_general(q, k, (((2,), (2,)), ((0,), (0,))), cfg)
        # q (B, Lq, D), k (B, Lk, D)  ->  out (B, Lq, Lk)
    """
    dnums = _canonicalize_dnums(dimension_numbers)
    if rhs_presplit is None:
        return _oz_dot_general(a, b, dnums, cfg)
    sp = rhs_presplit
    # beta is a static property of the TOTAL contraction length (eq. 4) —
    # recomputed here rather than read off the Split because a Split
    # passed through a jit boundary carries its int fields as tracers.
    # SplitCache freezes with exactly this beta; a concrete mismatch is
    # rejected, a traced one is unobservable (same construction).
    beta = splitting.beta_for(cfg.split,
                              math.prod(b.shape[i] for i in dnums[0][1]))
    if isinstance(sp.signmag, bool) and \
            sp.signmag != splitting.is_signmag(cfg.split):
        raise ValueError(
            f"rhs_presplit signmag={sp.signmag} does not match the "
            f"config's split {cfg.split!r}; sign-magnitude digits decode "
            f"differently from signed digits — re-freeze under the "
            f"current spec")
    if isinstance(sp.beta, int) and sp.beta != beta:
        raise ValueError(f"rhs_presplit beta={sp.beta} disagrees with the "
                         f"contraction's beta={beta}")
    return _oz_dot_general_presplit(
        a, b, (sp.digits, sp.scale, sp.base, sp.gbase), dnums, cfg, beta)


def ozimmu_matmul(a: jax.Array, b: jax.Array,
                  cfg: OzimmuConfig = VARIANTS["ozimmu_h"]) -> jax.Array:
    """Emulated high-precision ``a @ b`` via k-slice INT8 GEMMs.

    a: (m, n), b: (n, p), both f32 or f64.  Returns (m, p) in a.dtype.
    The rank-2 special case of :func:`ozimmu_dot_general`.
    """
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise ValueError(f"bad GEMM shapes {a.shape} @ {b.shape}")
    return ozimmu_dot_general(a, b, (((1,), (0,)), ((), ())), cfg)

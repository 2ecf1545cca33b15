"""MatmulEngine — the pluggable GEMM backend every model layer contracts
through.

Specs (CLI flag ``--matmul_engine``):

  * ``bf16`` / ``f32`` / ``f64``      — native XLA dot in that compute dtype
  * ``ozimmu[-k]``, ``ozimmu_rn[-k]``, ``ozimmu_ef[-k]``, ``ozimmu_h[-k]``
    optionally ``:f64|:f32|:df32``    — Ozaki-scheme emulation (paper).
    ``k`` may be ``auto``: the execution planner (``repro.core.plan``)
    picks the smallest slice count meeting ``OzimmuConfig.target_eps``
    from the operands' probed exponent ranges (eager calls) or the
    static mantissa-coverage plan (inside jit).  ``...:prob`` (auto-k
    specs only, every variant) plans under the probabilistic eps model
    instead of the worst-case one — same target, failure probability
    ``target_delta`` (default 2^-20), strictly-no-larger (typically
    smaller) resolved k — see
    docs/algorithms.md#the-probabilistic-planner-prob.
  * ``ozimmu_sm_b[-k]``, ``ozimmu_sm_h[-k]`` — sign-magnitude slicing:
    unsigned magnitude digits with the sign folded into the leading
    slice, so trailing slices spend no sign bit and the grid widens to
    a full 8 bits (``splitting.compute_beta_sm``).  At equal target_eps
    the planner resolves a strictly smaller k (fewer int8 GEMMs) than
    ``ozimmu_h``; composes with ``:fused``/``@mesh``/presplit weights
    bit-identically — docs/algorithms.md#the-sign-magnitude-family-ozimmu_sm_.
  * ``oz2_b[-k]``, ``oz2_h[-k]`` optionally ``:fast`` or ``:fast2`` —
    Ozaki-II constant-scaling emulation: one shared digit grid per
    matrix, all slice-pair scales folded into a scalar exponent ladder
    (``core/accumulate.matmul_oz2``); ``:fast`` evaluates only the
    s + t <= k + 1 band; ``:fast2`` runs the same band with improved
    per-row power-of-two equilibration onto the shared grid (near
    full-mode accuracy on wide-dynamic-range operands, same int8 GEMM
    count — docs/algorithms.md#improved-fast-mode-scaling-fast2).  The
    two tokens are mutually exclusive and reject non-oz2 variants.
    Auto-k plans against the OS-II error model.
  * ``...:fused``                     — the one-HBM-pass Pallas pipeline:
    fused k-slice extraction, VMEM-resident group GEMMs, and the fused
    convert+scale+add epilogue; bit-identical to the XLA path and
    composable with every other token (e.g. ``ozimmu_h-auto:df32:fused``).
  * ``...@mesh_axis[/int32|/df32]``   — mesh-native sharded emulation: the
    contraction axis is sharded over the named mesh axis and the
    cross-device accumulation stays inside the scheme's exactness
    invariants (error-free int32 product psum by default, compensated
    df32 partial-accumulator reduction with ``/df32``) — see
    docs/distributed.md.  Ignored gracefully when no mesh is installed.

The engine is a small immutable object passed through model configs.  Two
entry points:

  * ``engine(x, w)`` — contract the last axis of ``x`` with the first axis
    of ``w`` (the shape every model projection reduces to).  Leading axes of
    ``x`` are free dims of a single ``dot_general``; nothing is reshaped to
    2-D on the way in.
  * ``engine.dot_general(lhs, rhs, dimension_numbers)`` — arbitrary batched
    contraction (attention scores, MoE expert GEMMs, ...).  For ozimmu
    specs this is :func:`repro.core.ozimmu.ozimmu_dot_general`: batch dims
    ride natively through the INT8 slice GEMMs and gradients flow through
    the emulated custom VJP.

Accumulator-dtype footgun (documented in docs/engine.md): an ozimmu spec
with ``accum_dtype="f64"`` only computes in f64 when ``jax_enable_x64`` is
on; otherwise the engine *silently* downgrades the compute dtype to f32
(f64 constants would be truncated by JAX anyway — doing it explicitly keeps
the emulation's exactness invariants intact).  Use ``:df32`` for
high-precision accumulation that does not depend on x64 mode.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import ozimmu, splitting

__all__ = ["MatmulEngine", "make_engine", "PresplitWeight"]

_NATIVE = {"bf16": jnp.bfloat16, "f32": jnp.float32, "f64": jnp.float64}
# the f32/f64 specs are references: on a TPU the default precision would
# run them as one-pass bf16 products, so they ask for full precision
_NATIVE_PRECISION = {"bf16": None, "f32": jax.lax.Precision.HIGHEST,
                     "f64": jax.lax.Precision.HIGHEST}


class PresplitWeight:
    """A weight array bundled with its frozen Ozaki Split (serving).

    Registered as a pytree whose children are ``(array, digits, scale,
    base, gbase)``, so it rides through ``jit`` / ``lax.scan`` xs /
    ``vmap`` like any parameter leaf: a stacked wrapper (digits stored
    with the stack axes LEADING, ``(*stack, k, n, p)``) slices down to
    the per-layer wrapper automatically when the layer scan slices its
    leaves.  Model code passes it to the engine unchanged; the engine
    consumes the frozen split when the contraction matches the pattern
    the split was frozen for (``x[..., n] @ w[n, p]`` — the projection
    shape every model layer reduces to) and falls back to ``array``
    otherwise, so wrapping is always safe.

    Built by ``repro.serving.presplit.wrap_params`` from a
    ``repro.core.split_cache.SplitCache``.
    """

    __slots__ = ("array", "digits", "scale", "base", "gbase", "beta",
                 "split", "k")

    def __init__(self, array, digits, scale, base, gbase, beta: int,
                 split: str, k: int):
        self.array, self.digits, self.scale = array, digits, scale
        self.base, self.gbase = base, gbase
        self.beta, self.split, self.k = beta, split, k

    # array-facade so existing shape asserts keep working
    @property
    def shape(self):
        return self.array.shape

    @property
    def ndim(self):
        return self.array.ndim

    @property
    def dtype(self):
        return self.array.dtype

    def tree_flatten(self):
        return ((self.array, self.digits, self.scale, self.base,
                 self.gbase), (self.beta, self.split, self.k))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def usable_split(self, lhs, dimension_numbers, compute_dtype,
                     cfg) -> Optional[splitting.Split]:
        """The frozen Split iff it applies to this contraction, else None
        (the engine then uses ``array`` — e.g. a stacked wrapper consumed
        before the layer scan sliced it, or an unexpected dnums)."""
        (ac, bc), (ab, bb) = dimension_numbers
        simple = (tuple(ac) == (lhs.ndim - 1,) and tuple(bc) == (0,)
                  and not ab and not bb)
        if not (simple and self.array.ndim == 2 and self.digits.ndim == 3):
            return None
        if self.split != cfg.split or self.scale.dtype != compute_dtype:
            return None
        if not cfg.auto_k and self.k != cfg.k:
            return None
        n = self.array.shape[0]
        if self.beta != splitting.beta_for(self.split, n):
            return None
        return splitting.Split(self.digits, self.scale, self.base,
                               self.beta, 1, gbase=self.gbase,
                               signmag=splitting.is_signmag(self.split))


jax.tree_util.register_pytree_node(
    PresplitWeight,
    lambda w: w.tree_flatten(),
    PresplitWeight.tree_unflatten)


# Trace-time consumption counters: every engine contraction that received
# a PresplitWeight records whether the frozen split applied or fell back
# to re-splitting.  Incremented while TRACING (or on eager calls), so a
# compiled step that used the split at trace time uses it on every
# execution — the serving runtime turns the delta into the measured
# weight-split hit rate the bench gate checks (a hardcoded 1.0 would go
# vacuous the moment `usable_split` started silently falling back).
_PRESPLIT_COUNTS = {"used": 0, "fallback": 0}


def presplit_trace_counts() -> dict:
    return dict(_PRESPLIT_COUNTS)


@dataclasses.dataclass(frozen=True)
class MatmulEngine:
    spec: str = "bf16"

    @property
    def is_ozimmu(self) -> bool:
        return self.spec.split("@")[0].split("-")[0].split(":")[0] \
            not in _NATIVE

    @property
    def native_precision(self) -> Optional[jax.lax.Precision]:
        """``lax`` precision of a native spec's dots: HIGHEST for the
        ``f32``/``f64`` references, None (backend default) for ``bf16``
        and for ozimmu specs."""
        return _NATIVE_PRECISION.get(self.spec)

    @property
    def ozimmu_config(self) -> Optional[ozimmu.OzimmuConfig]:
        return ozimmu.parse_spec(self.spec) if self.is_ozimmu else None

    def local(self) -> "MatmulEngine":
        """This engine without the ``@mesh_axis`` suffix — single-device
        semantics, for use inside shard_map bodies (e.g. the all-to-all MoE
        dispatch) that already own the mesh axes."""
        return MatmulEngine(self.spec.split("@")[0]) if "@" in self.spec \
            else self

    def dot_general(self, lhs: jax.Array, rhs: jax.Array, dimension_numbers,
                    out_dtype=None) -> jax.Array:
        """Contract ``lhs`` with ``rhs`` under standard lax dimension
        numbers.  Returns ``lhs.dtype`` unless ``out_dtype`` is given (e.g.
        f32 attention scores feeding an online softmax).

        ``rhs`` may be a :class:`PresplitWeight` (serving): when the
        contraction matches the frozen split's pattern, the B-side
        splitter is skipped (bit-identical — see
        ``repro.core.split_cache``); otherwise the wrapped array is used
        like any weight."""
        if isinstance(lhs, PresplitWeight):
            lhs = lhs.array
        presplit = None
        if isinstance(rhs, PresplitWeight):
            rhs, presplit = rhs.array, rhs
        out_dtype = out_dtype or lhs.dtype
        if not self.is_ozimmu:
            dt = _NATIVE[self.spec]
            # accumulate in f32, except for the f64 reference spec — its
            # whole point is full f64 accumulation
            acc = jnp.float64 if dt == jnp.float64 else jnp.float32
            out = jax.lax.dot_general(
                lhs.astype(dt), rhs.astype(dt), dimension_numbers,
                precision=self.native_precision, preferred_element_type=acc)
            return out.astype(out_dtype)

        cfg = self.ozimmu_config
        # f64 accumulation needs x64 mode; otherwise downgrade (see module
        # docstring — the "silent f64 -> f32" footgun).
        compute_dtype = jnp.float64 if cfg.accum_dtype == "f64" and \
            jax.config.jax_enable_x64 else jnp.float32
        sp = None
        if presplit is not None:
            sp = presplit.usable_split(lhs, dimension_numbers,
                                       jnp.dtype(compute_dtype), cfg)
            _PRESPLIT_COUNTS["used" if sp is not None
                             else "fallback"] += 1
        out = ozimmu.ozimmu_dot_general(
            lhs.astype(compute_dtype), rhs.astype(compute_dtype),
            dimension_numbers, cfg, rhs_presplit=sp)
        return out.astype(out_dtype)

    def __call__(self, x: jax.Array, w: jax.Array) -> jax.Array:
        """Contract x[..., n] with w[n, ...] -> out[..., ...]."""
        assert w.shape[0] == x.shape[-1], (x.shape, w.shape)
        return self.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())))


def make_engine(spec: str) -> MatmulEngine:
    eng = MatmulEngine(spec)
    if eng.is_ozimmu:
        ozimmu.parse_spec(spec)  # validate eagerly
    elif spec not in _NATIVE:
        # a native dtype with ozimmu-only decorations, e.g. "bf16@model"
        raise ValueError(f"native engine specs take no suffixes: {spec!r}")
    return eng

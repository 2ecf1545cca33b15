"""Persistent weight split-cache for emulated GEMMs.

At inference the B operand of almost every emulated contraction is a
*static* weight matrix, yet the scheme re-runs the splitter on it every
decode step — re-deriving identical int8 digit slices, identical
power-of-two scales, and (for the oz2 variants) the identical shared
grid.  The :class:`SplitCache` freezes a static operand into its
spec-resolved :class:`~repro.core.splitting.Split` ONCE, keyed by
``(array identity, spec, dimension_numbers, mesh)``, and the
``rhs_presplit=`` path of :func:`repro.core.ozimmu.ozimmu_dot_general`
then skips the B-side splitter entirely — bit-identical to the uncached
path (the splitters are deterministic, rounding-exact float arithmetic;
freezing just hoists the identical computation out of the step).

Memory model (docs/serving.md): the cached entry holds the ``k`` int8
digit slices plus the scale vectors — ``k * bytes(B) / 8`` for f64
weights (``k/8`` of the operand), ``k/4`` for f32.  Re-splitting instead
costs a read of B plus a write of the same ``k`` slices *per call*, so
the cache pays for itself after a single decode step and eliminates the
B-side split phase from every step after.

Keying / invalidation:

* identity is ``id(array)`` guarded by a ``weakref`` — when the weight
  array is deleted (donated, updated by an optimizer step, re-wrapped),
  its entries drop out of the cache automatically, so a recycled ``id``
  can never alias a stale split.  Arrays that do not support weak
  references are kept alive by a strong reference instead (correct, but
  such entries only leave the cache via :meth:`clear`).
* the spec key carries everything the digits/scales depend on: the
  splitting strategy, the *resolved* slice count k, beta (from the
  global contraction length), and the operand dtype.  Same weights under
  a different spec are a miss by construction.
* the mesh key (axis names x sizes of the installed abstract mesh) keeps
  entries from leaking across mesh contexts.  The cached Split itself is
  mesh-independent — it is computed from the full operand, and the
  mesh-native path shards the cached digits along the contraction axis
  inside ``shard_map`` (the per-shard digits equal what the
  ``rowmax_reduce`` pmax-agreed shard-local splitter would produce, so
  the ``@mesh`` path stays bitwise identical too).

Auto-k (``...-auto`` specs) is resolved at freeze time with
:func:`resolved_k` — the *static* mantissa-coverage plan, which is
exactly what the planner resolves to inside a ``jit`` trace (serving
steps are jitted; there are no concrete operands to probe).  The frozen
k therefore matches the k the uncached jitted call would pick.
"""
from __future__ import annotations

import dataclasses
import threading
import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

from repro.core import splitting
from repro.core.splitting import Split
from repro.obs import registry as _obs

__all__ = ["SplitCache", "CacheStats", "resolved_k", "presplit_rhs",
           "split_nbytes"]


def resolved_k(cfg, n: int, dtype) -> int:
    """The slice count a serving-time (jitted) call resolves to.

    Fixed-k configs return ``cfg.k``.  ``auto`` configs resolve the
    static mantissa-coverage plan of ``repro.core.plan.choose_k`` with no
    probed operand gaps — identical to what ``plan.auto_k`` returns for
    tracers, so a cached split and the uncached jitted path agree on k.
    ``target_eps_mode`` rides along: a ``:prob`` config resolves the
    probabilistic static plan's (smaller) k, and because the resolved k
    is part of :func:`_cfg_key`, its entries never alias a deterministic
    plan's entries at a different k.
    """
    if not getattr(cfg, "auto_k", False):
        return cfg.k
    from repro.core import plan
    mantissa = plan._MANTISSA.get(np.dtype(dtype), 24)
    beta = splitting.beta_for(cfg.split, n)
    k, needed = plan.choose_k_bits(
        n, beta,
        cfg.target_eps if cfg.target_eps is not None
        else plan.DEFAULT_TARGET_EPS,
        split=cfg.split, mantissa=mantissa,
        fast=getattr(cfg, "fast", False),
        mode=getattr(cfg, "target_eps_mode", "deterministic"),
        delta=getattr(cfg, "target_delta", None))
    # m=p=0: the freeze-time resolution sees only the contraction length
    plan.record_decision(cfg, m=0, n=n, p=0, k=k, beta=beta,
                         needed=needed, probed=False,
                         source="split_cache")
    return k


def presplit_rhs(b: jax.Array, dimension_numbers, cfg) -> Split:
    """Freeze the rhs of ``dot_general(a, b, dimension_numbers)`` under
    ``cfg`` into its canonical column-scale Split.

    ``b`` must already be in the emulation's compute dtype (the engine
    casts operands before contracting; cast before freezing).  The split
    runs against the canonical ``(*batch, n, p)`` layout — the same
    transpose/reshape ``ozimmu_dot_general`` performs — with beta from
    the total contraction length, so the digits are bit-identical to
    what the in-call splitter would produce.
    """
    from repro.core import ozimmu
    b3, n = ozimmu.canonical_rhs(b, ozimmu._canonicalize_dnums(
        dimension_numbers))
    k = resolved_k(cfg, n, b3.dtype)
    beta = splitting.beta_for(cfg.split, n)
    splitter = ozimmu._SPLITTERS[cfg.split]
    return splitter(b3, k, beta=beta, axis=1)


def stack_leading(sp: Split, nstack: int) -> Split:
    """Re-layout a batched Split for the ``PresplitWeight`` wrapper: the
    ``nstack`` leading batch (layer-stack) axes move in front of the k
    axis — ``digits (*stack, k, n, p)``, ``scale (*stack, k, p)`` — so a
    ``lax.scan`` over the stacked parameter tree slices the split per
    layer.  NOTE: the result no longer follows the ``Split`` field
    contract (k is not leading); it is a storage layout for wrappers,
    not an operand for the accumulate routines."""
    if nstack == 0:
        return sp
    import jax.numpy as jnp
    return Split(jnp.moveaxis(sp.digits, 0, nstack),
                 jnp.moveaxis(sp.scale, 0, nstack),
                 sp.base, sp.beta, sp.axis, gbase=sp.gbase,
                 signmag=sp.signmag)


def split_nbytes(sp: Split) -> int:
    """Device bytes a cached Split occupies (digits + scales + bases)."""
    total = sp.digits.nbytes + sp.scale.nbytes
    if sp.base is not None:
        total += sp.base.nbytes
    if sp.gbase is not None:
        total += sp.gbase.nbytes
    return total


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    invalidations: int = 0
    cached_bytes: int = 0      # resident bytes of cached splits
    hit_bytes: int = 0         # splitter input bytes avoided (sum of
                               # operand nbytes over hits) — the "split
                               # work saved" counter of serving metrics

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self) -> Dict[str, Any]:
        return {"hits": self.hits, "misses": self.misses,
                "invalidations": self.invalidations,
                "cached_bytes": self.cached_bytes,
                "hit_bytes": self.hit_bytes,
                "hit_rate": round(self.hit_rate, 6)}


def _cfg_key(cfg, k: int, dtype) -> Tuple:
    return (cfg.split, int(k), str(np.dtype(dtype)),
            bool(getattr(cfg, "fast", False)))


def _mesh_key() -> Tuple:
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return ()
        return tuple(sorted(dict(mesh.shape).items()))
    except Exception:
        return ()


class SplitCache:
    """Freeze-once cache of spec-resolved weight splits.

    Thread-safe, weakref-invalidated (see module docstring).  ``get``
    returns the cached :class:`Split` for ``(b, dimension_numbers, cfg)``
    or computes and stores it on a miss.
    """

    def __init__(self, max_entries: Optional[int] = None):
        self._entries: Dict[Tuple, Tuple[Split, int, Any]] = {}
        self._lock = threading.Lock()
        self._max = max_entries
        self.stats = CacheStats()

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, b: jax.Array, dimension_numbers, cfg,
            dtype=None, layout: str = "k_leading") -> Split:
        """The frozen Split for ``b`` as the rhs of
        ``dot_general(·, b, dimension_numbers)`` under ``cfg``.

        ``dtype`` is the emulation's compute dtype when it differs from
        ``b.dtype`` (the engine casts operands before contracting): the
        cast happens *inside* — the entry stays keyed and
        weakref-anchored on the ORIGINAL array, so it survives across
        calls (a cast produces a throwaway array whose identity would
        otherwise invalidate the entry immediately).

        ``layout="stack_leading"`` stores (and returns) the
        :func:`stack_leading` wrapper layout instead — the cached entry
        IS the wrapper's storage, so a layer-stacked weight's digits are
        resident exactly once (a post-hoc ``moveaxis`` would keep both
        copies alive through the cache's strong reference).
        """
        if isinstance(b, jax.core.Tracer):
            raise TypeError(
                "SplitCache.get needs a concrete array: freeze weights "
                "eagerly (outside jit) and pass the Split into the "
                "jitted step via rhs_presplit / PresplitWeight")
        from repro.core import ozimmu
        if layout not in ("k_leading", "stack_leading"):
            raise ValueError(f"unknown split layout {layout!r}")
        dtype = np.dtype(b.dtype) if dtype is None else np.dtype(dtype)
        dnums = ozimmu._canonicalize_dnums(dimension_numbers)
        (_, bc), (_, bb) = dnums
        n = int(np.prod([b.shape[i] for i in bc], dtype=np.int64))
        k = resolved_k(cfg, n, dtype)
        key = (id(b), _cfg_key(cfg, k, dtype), dnums, _mesh_key(), layout)
        in_bytes = int(np.prod(b.shape, dtype=np.int64)) * dtype.itemsize
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self.stats.hits += 1
                self.stats.hit_bytes += in_bytes
                self._obs_event("hits", hit_bytes=in_bytes)
                return entry[0]
        bc_arr = b if np.dtype(b.dtype) == dtype else b.astype(dtype)
        sp = presplit_rhs(bc_arr, dnums, cfg)
        if layout == "stack_leading":
            sp = stack_leading(sp, len(bb))
        nbytes = split_nbytes(sp)
        anchor = self._anchor(b, key)
        with self._lock:
            # re-check: a concurrent miss may have inserted first — keep
            # one entry and count one miss (documented thread-safety)
            entry = self._entries.get(key)
            if entry is not None:
                self.stats.hits += 1
                self.stats.hit_bytes += in_bytes
                self._obs_event("hits", hit_bytes=in_bytes)
                return entry[0]
            if self._max is not None and len(self._entries) >= self._max:
                self._evict_one_locked()
            self._entries[key] = (sp, nbytes, anchor)
            self.stats.misses += 1
            self.stats.cached_bytes += nbytes
            self._obs_event("misses")
        return sp

    def _obs_event(self, kind: str, hit_bytes: int = 0):
        """Mirror one stats transition into the process-global registry
        (cached_bytes rides along as a gauge).  The registry lock is a
        leaf — safe under ``self._lock``."""
        if not _obs.enabled():
            return
        reg = _obs.get_registry()
        reg.inc(f"split_cache.{kind}", 1)
        if hit_bytes:
            reg.inc("split_cache.hit_bytes", hit_bytes)
        reg.gauge("split_cache.cached_bytes", self.stats.cached_bytes)

    def _anchor(self, b, key):
        """A weakref that drops the entry when the array dies; falls back
        to a strong reference for non-weakrefable arrays."""
        def _on_dead(_ref, cache=weakref.ref(self), key=key):
            c = cache()
            if c is not None:
                c._drop(key, invalidated=True)
        try:
            return weakref.ref(b, _on_dead)
        except TypeError:
            return b

    def _drop(self, key, invalidated: bool = False):
        with self._lock:
            entry = self._entries.pop(key, None)
            if entry is not None:
                self.stats.cached_bytes -= entry[1]
                if invalidated:
                    self.stats.invalidations += 1
                    self._obs_event("invalidations")

    def _evict_one_locked(self):
        key = next(iter(self._entries))
        entry = self._entries.pop(key)
        self.stats.cached_bytes -= entry[1]

    def invalidate(self, b: jax.Array) -> int:
        """Drop every entry keyed on this array (as of the snapshot taken
        under the lock); returns the count."""
        with self._lock:
            keys = [k for k in self._entries if k[0] == id(b)]
        for k in keys:
            self._drop(k, invalidated=True)
        return len(keys)

    def clear(self):
        with self._lock:
            self._entries.clear()
            self.stats.cached_bytes = 0

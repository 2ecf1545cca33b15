"""Per-slot cache operations + block-paged KV-cache pool.

Two layers:

:class:`SlotCacheOps` — family-generic *monolithic* slot operations,
driven by each model's ``cache_axes`` (the ``"cache_batch"`` logical
axis marks the slot dimension of every cache leaf, wherever it sits —
axis 1 for the dense/MoE/encdec stacks, axis 2 for the vlm group nesting
and the hybrid conv/lru states).  Used by the runtime to freeze
non-participating slots around a prefill call (functional
snapshot-select, no model changes) and to reset a slot at admission.

:class:`PagedKV` — a block-paged pool replacing the monolithic
``(layers, slots, max_len, ...)`` buffers.  Which leaves page is a
**per-family state descriptor** (:data:`STATE_DESCRIPTORS`): every cache
leaf is either

``paged``
    a sequence-indexed buffer ``(*lead, slot, seq, *tail)`` — the
    attention K/V stacks (dense/moe/vlm/hybrid), the MLA latent rows,
    the encdec decoder K/V.  These live in the pool: ``n_blocks`` blocks
    of ``block`` positions per leaf, with a host-side block table per
    slot, blocks allocated on demand as the sequence grows.

``state``
    a constant-size per-slot row with NO sequence axis — the mamba2
    conv/ssm states, the recurrentgemma conv/lru states, and the
    admission-time context caches (encdec/vlm cross-KV, computed once
    from the encoder memory / image embeds and read-only during decode).
    There is nothing to page; they stay resident ``(*lead, slots,
    *tail)``, reset from a single-slot template at admission and merged
    per active slot after each step (a mid-prefill neighbour's recurrent
    state must never take a decode step's garbage).

Pool memory scales with the sum of *live* sequence lengths (rounded up
to blocks) instead of ``slots x max_len``; a finishing request frees its
blocks immediately, and pool pressure triggers scheduler eviction
instead of OOM.

Blocks are **reference-counted**: the prefix cache
(:mod:`repro.serving.prefix_cache`) aliases a frozen prefix's blocks
into a new slot's table instead of re-running prefill, so one physical
block can appear in several tables.  All write paths go through
:meth:`PagedKV.cow_for_write` first — a shared block is copied to a
fresh private block before the write lands (copy-on-write), so aliased
readers never observe another slot's divergence.  With block-aligned
prefix lengths the hot paths never actually trigger a copy (suffix
writes start exactly at the first non-shared block); the CoW is the
safety net that makes aliasing unconditionally safe (ring-wrap writes of
windowed caches included).

The decode step still consumes a contiguous ``(…, slot, seq, …)`` view:
``gather`` materializes it from the pool (a copy — the correctness-first
realization; a paged-attention kernel reading the pool in place is the
obvious next optimization and slots behind the same interface), the
model runs unchanged, and ``scatter_rows`` writes back exactly the one
row per active slot the decode step appended.  Unallocated table entries
point at block 0; reads through them see unrelated bytes, which is safe
because attention masks every position >= the slot's current length, and
writes never go through them (decode writes only at allocated positions;
inactive slots are redirected to a dedicated trash block).
Per-token paged-vs-monolithic equivalence is asserted in
tests/test_serving.py and tests/test_prefix_cache.py for every family.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import ring_row_index

__all__ = ["SlotCacheOps", "PagedKV", "STATE_DESCRIPTORS",
           "state_descriptor"]


# -- per-family state descriptor --------------------------------------------
#
# Leaf name -> kind for every serving family.  "paged" leaves carry a
# sequence axis right of the slot axis and live in the block pool;
# "state" leaves are constant-size per-slot rows (recurrent states,
# admission-time cross-KV context) that stay resident.  A family absent
# here (or a cache leaf absent from its entry) cannot serve paged —
# ``supported()`` says so instead of mis-paging it.

STATE_DESCRIPTORS: Dict[str, Dict[str, str]] = {
    "dense":   {"k": "paged", "v": "paged"},
    "moe":     {"k": "paged", "v": "paged"},
    "mla_moe": {"latent": "paged", "k_rope": "paged"},
    "vlm":     {"k": "paged", "v": "paged",
                "cross_k": "state", "cross_v": "state"},
    "encdec":  {"k": "paged", "v": "paged",
                "cross_k": "state", "cross_v": "state"},
    "ssm":     {"conv": "state", "ssm": "state"},
    "hybrid":  {"k": "paged", "v": "paged",
                "conv": "state", "lru": "state",
                "tail_conv": "state", "tail_lru": "state"},
}


def state_descriptor(cfg) -> Dict[str, str]:
    """The family's leaf-name -> {"paged", "state"} map (KeyError for a
    family without one — then only the monolithic cache serves it)."""
    return STATE_DESCRIPTORS[cfg.family]


def _axes_tree(model, cfg):
    if getattr(model, "cache_axes", None) is None:
        return None
    return model.cache_axes(cfg)


def _pathkey(path) -> Tuple[str, ...]:
    return tuple(str(k) for k in path)


def _leaf_axes(axes_tree, cache) -> Dict[Tuple, Tuple]:
    """{stringified leaf path: logical axes tuple} for the cache tree."""
    is_ax = lambda x: isinstance(x, tuple)
    flat_cache = jax.tree_util.tree_flatten_with_path(cache)[0]
    if axes_tree is None:
        return {_pathkey(path): None for path, _ in flat_cache}
    flat_axes = jax.tree_util.tree_flatten_with_path(
        axes_tree, is_leaf=is_ax)[0]
    ax = {_pathkey(path): v for path, v in flat_axes}
    return {_pathkey(path): ax.get(_pathkey(path))
            for path, _ in flat_cache}


def _slot_axis(axes: Optional[Tuple]) -> int:
    if axes is None:
        return 1          # every family's default cache layout
    return axes.index("cache_batch")


class SlotCacheOps:
    """Family-generic per-slot select / reset on a monolithic cache."""

    def __init__(self, cfg, model):
        self.cfg, self.model = cfg, model
        self._axes = _axes_tree(model, cfg)
        self._select = jax.jit(self._select_impl)

    def _slot_axes_for(self, cache) -> List[int]:
        la = _leaf_axes(self._axes, cache)
        return [_slot_axis(v) for v in la.values()]

    def _select_impl(self, new_cache, old_cache, mask):
        """Per-slot select: leaves of ``new_cache`` where ``mask`` is set
        (along each leaf's slot axis), ``old_cache`` elsewhere — the
        functional freeze of non-participating slots."""
        axes = self._slot_axes_for(new_cache)
        flat_new, tree = jax.tree_util.tree_flatten(new_cache)
        flat_old = jax.tree_util.tree_flatten(old_cache)[0]
        out = []
        for new, old, ax in zip(flat_new, flat_old, axes):
            shape = [1] * new.ndim
            shape[ax] = mask.shape[0]
            out.append(jnp.where(mask.reshape(shape), new, old))
        return jax.tree_util.tree_unflatten(tree, out)

    def select_slots(self, new_cache, old_cache, mask: jax.Array):
        return self._select(new_cache, old_cache, mask)

    def reset_slot(self, cache, slot_idx: int, template):
        """Write a freshly initialized single-slot cache (``template``,
        from ``init_cache(cfg, 1, ...)``) into slot ``slot_idx``."""
        axes = self._slot_axes_for(cache)
        flat_c, tree = jax.tree_util.tree_flatten(cache)
        flat_t = jax.tree_util.tree_flatten(template)[0]
        out = []
        idx = jnp.asarray(slot_idx, jnp.int32)  # x64: keep s32 indices
        for leaf, one, ax in zip(flat_c, flat_t, axes):
            one = jax.lax.index_in_dim(one, 0, ax, keepdims=False)
            out.append(jax.lax.dynamic_update_index_in_dim(
                leaf, one.astype(leaf.dtype), idx, axis=ax))
        return jax.tree_util.tree_unflatten(tree, out)


class PagedKV:
    """Block-paged pool + host-side block tables (see module docstring).

    Every cache leaf is classified by the family's state descriptor:
    ``paged`` leaves (all sharing one sequence length) live in the pool,
    ``state`` leaves stay resident per slot.  The last pool block (id
    ``n_blocks``) is the write trash for inactive slots and is never
    allocated.  ``params``/``ctx``/``template`` feed the state leaves of
    the context families (encdec/vlm): ``ctx`` is the already-batched
    per-slot context for shape inference, ``template`` the concrete
    single-slot cache the state leaves are initialized and reset from.
    """

    def __init__(self, cfg, model, n_slots: int, max_len: int,
                 block: int = 16, n_blocks: Optional[int] = None,
                 params=None, ctx=None, template=None):
        self.cfg, self.model = cfg, model
        self.n_slots = n_slots
        desc = state_descriptor(cfg)
        # shapes only — materializing the monolithic cache here would
        # transiently double KV memory, the very regime paging avoids
        cache = jax.eval_shape(
            lambda: model.init_cache(cfg, n_slots, max_len,
                                     params=params, ctx=ctx))
        if not isinstance(cache, dict):
            raise ValueError("paged KV expects a flat dict cache")
        unknown = sorted(set(cache) - set(desc))
        if unknown:
            raise ValueError(f"cache leaves {unknown} missing from the "
                             f"{cfg.family!r} state descriptor")
        axes = _leaf_axes(_axes_tree(model, cfg), cache)
        self._slot_ax = {name: _slot_axis(axes[("['%s']" % name,)])
                         for name in cache}
        self.kinds = {name: desc[name] for name in cache}
        self.paged_names = sorted(n for n, k in self.kinds.items()
                                  if k == "paged")
        self.state_names = sorted(n for n, k in self.kinds.items()
                                  if k == "state")
        seqs = {cache[n].shape[self._slot_ax[n] + 1]
                for n in self.paged_names}
        if len(seqs) > 1:
            raise ValueError(f"paged KV needs one shared sequence length "
                             f"across paged leaves, got {sorted(seqs)}")
        self.seq_len = seqs.pop() if seqs else 0
        if self.seq_len % block != 0:
            raise ValueError(f"block={block} must divide the cache length "
                             f"{self.seq_len}")
        self.block = block
        self.blocks_per_slot = self.seq_len // block
        if n_blocks is None:
            n_blocks = n_slots * self.blocks_per_slot
        if not self.paged_names:
            n_blocks = 0          # pure-state family: nothing to page
        self.n_blocks = n_blocks
        # host-side tables: unallocated entries point at block 0 (read-
        # only garbage, masked by attention); trash block id = n_blocks.
        self.tables = np.zeros((n_slots, self.blocks_per_slot), np.int32)
        self.allocated = np.zeros((n_slots,), np.int32)    # blocks per slot
        self.free_blocks: List[int] = list(range(n_blocks - 1, -1, -1))
        # per-block reference counts: >1 means the block is aliased
        # (prefix cache and/or several slot tables) and must copy-on-write
        self.refcount = np.zeros((max(n_blocks, 1),), np.int32)
        self.cow_copies = 0
        self._shapes = cache
        self.pool = {}
        for name in self.paged_names:
            leaf, ax = cache[name], self._slot_ax[name]
            lead, tail = leaf.shape[:ax], leaf.shape[ax + 2:]
            self.pool[name] = jnp.zeros(
                lead + (self.n_blocks + 1, self.block) + tail, leaf.dtype)
        # resident state leaves, tiled from the single-slot template (the
        # same template admission resets a slot from — bitwise identical
        # to a batched init_cache, whose per-slot context rows repeat the
        # shared single-slot ctx)
        self.state: Dict[str, jax.Array] = {}
        self.state_template: Dict[str, jax.Array] = {}
        if self.state_names:
            if template is None:
                raise ValueError(f"family {cfg.family!r} has state leaves "
                                 f"{self.state_names}; PagedKV needs the "
                                 f"single-slot template")
            for name in self.state_names:
                ax = self._slot_ax[name]
                t = template[name]
                self.state_template[name] = t
                reps = [1] * t.ndim
                reps[ax] = n_slots
                self.state[name] = jnp.tile(t, reps)
        self._gather = jax.jit(self._gather_impl)
        self._scatter_rows = jax.jit(self._scatter_rows_impl)
        self._copy_block = jax.jit(self._copy_block_impl)
        self._reset_state = jax.jit(self._reset_state_impl)
        self._snap_state = jax.jit(self._snap_state_impl)
        self._restore_state = jax.jit(self._restore_state_impl)
        self._span_fns = {}

    # -- support probe ---------------------------------------------------

    @staticmethod
    def supported(cfg, model, max_len: int, params=None, ctx=None) -> bool:
        """Whether this (family, max_len) pair can serve paged: a state
        descriptor covering every cache leaf, and one shared sequence
        length across the paged leaves.  ``params``/``ctx`` are needed
        for the context families whose init derives cross-KV shapes."""
        desc = STATE_DESCRIPTORS.get(cfg.family)
        if desc is None:
            return False
        cache = jax.eval_shape(
            lambda: model.init_cache(cfg, 1, max_len, params=params,
                                     ctx=ctx))
        if not isinstance(cache, dict) or set(cache) - set(desc):
            return False
        axes = _leaf_axes(_axes_tree(model, cfg), cache)
        seqs = set()
        for name, leaf in cache.items():
            ax = _slot_axis(axes[("['%s']" % name,)])
            if desc[name] != "paged":
                continue
            if leaf.ndim < ax + 2:
                return False
            seqs.add(leaf.shape[ax + 1])
        return len(seqs) <= 1

    # -- device ops ------------------------------------------------------

    def _gather_impl(self, pool, tables, state):
        """(pool, (S, bps) tables, state) -> the full contiguous cache
        dict the model's decode step consumes."""
        out = dict(state)
        for name in self.paged_names:
            pleaf = pool[name]
            ax = self._slot_ax[name]
            g = jnp.take(pleaf, tables, axis=ax)  # (*lead, S, bps, blk, *tail)
            lead = pleaf.shape[:ax]
            tail = pleaf.shape[ax + 2:]
            out[name] = g.reshape(
                lead + (self.n_slots, self.seq_len) + tail)
        return out

    def _scatter_rows_impl(self, pool, tables, cache, cur_len, active,
                           state):
        """Write back what one decode step changed: the one appended row
        per active slot for paged leaves (position ``(cur_len-1) mod
        seq`` — ``layers.ring_row_index``, the same arithmetic the
        monolithic ``cache_update_row`` uses — redirected to the trash
        block for inactive slots), and a per-active-slot merge for state
        leaves (inactive and mid-prefill slots keep their old state)."""
        new_pool = dict(pool)
        if self.paged_names:
            pos = ring_row_index(cur_len, self.seq_len)
            blk_idx = pos // self.block
            off = pos % self.block
            blk = jnp.take_along_axis(tables, blk_idx[:, None],
                                      axis=1)[:, 0]
            blk = jnp.where(active, blk, self.n_blocks)  # trash if inactive
            for name in self.paged_names:
                pleaf, cleaf = pool[name], cache[name]
                ax = self._slot_ax[name]
                sl = (slice(None),) * ax
                rows = cleaf[sl + (jnp.arange(self.n_slots), pos)]
                new_pool[name] = pleaf.at[sl + (blk, off)].set(
                    rows.astype(pleaf.dtype))
        new_state = {}
        for name in self.state_names:
            ax = self._slot_ax[name]
            shape = [1] * cache[name].ndim
            shape[ax] = self.n_slots
            new_state[name] = jnp.where(
                active.reshape(shape), cache[name].astype(state[name].dtype),
                state[name])
        return new_pool, new_state

    def _copy_block_impl(self, pool, src, dst):
        """Device copy of one pool block (the copy-on-write body)."""
        out = dict(pool)
        for name in self.paged_names:
            pleaf = pool[name]
            ax = self._slot_ax[name]
            row = jax.lax.dynamic_index_in_dim(pleaf, src, axis=ax,
                                               keepdims=False)
            out[name] = jax.lax.dynamic_update_index_in_dim(
                pleaf, row, dst, axis=ax)
        return out

    def _reset_state_impl(self, state, slot_idx, template):
        out = dict(state)
        for name in self.state_names:
            ax = self._slot_ax[name]
            one = jax.lax.index_in_dim(template[name], 0, ax,
                                       keepdims=False)
            out[name] = jax.lax.dynamic_update_index_in_dim(
                state[name], one.astype(state[name].dtype), slot_idx,
                axis=ax)
        return out

    def _snap_state_impl(self, state, slot_idx):
        return {name: jax.lax.dynamic_index_in_dim(
                    state[name], slot_idx, axis=self._slot_ax[name],
                    keepdims=True)
                for name in self.state_names}

    def _restore_state_impl(self, state, slot_idx, snap):
        out = dict(state)
        for name in self.state_names:
            ax = self._slot_ax[name]
            one = jax.lax.index_in_dim(snap[name], 0, ax, keepdims=False)
            out[name] = jax.lax.dynamic_update_index_in_dim(
                state[name], one.astype(state[name].dtype), slot_idx,
                axis=ax)
        return out

    def _scatter_span_fn(self, n_span: int):
        """jitted writer of ``n_span`` consecutive blocks of one slot
        (prefill write-back, starting at block operand ``row0/block``),
        memoized per span length on the instance (a functools.lru_cache
        on the bound method would pin the pool).  Its program is named
        ``jit__write_span_impl``, apart from the prefill chunk's."""
        cached = self._span_fns.get(n_span)
        if cached is not None:
            return cached

        def _write_span_impl(pool, cache, slot_idx, block_ids, row0):
            out = dict(pool)
            for name in self.paged_names:
                pleaf, cleaf = pool[name], cache[name]
                ax = self._slot_ax[name]
                sl = (slice(None),) * ax
                span = jax.lax.dynamic_index_in_dim(
                    cleaf, slot_idx, axis=ax, keepdims=False)
                lead = cleaf.shape[:ax]
                tail = cleaf.shape[ax + 2:]
                span = jax.lax.dynamic_slice_in_dim(
                    span, row0, n_span * self.block, axis=ax)
                span = span.reshape(lead + (n_span, self.block) + tail)
                out[name] = pleaf.at[sl + (block_ids,)].set(
                    span.astype(pleaf.dtype))
            return out
        fn = self._span_fns[n_span] = jax.jit(_write_span_impl)
        return fn

    # -- host-side block management --------------------------------------

    def ensure(self, slot: int, length: int) -> bool:
        """Allocate blocks so positions [0, length) are writable; False
        when the pool is exhausted (caller evicts and retries)."""
        if not self.paged_names:
            return True           # pure-state family: nothing to allocate
        need = -(-min(length, self.seq_len) // self.block)
        if need > self.blocks_per_slot:
            raise ValueError(f"sequence length {length} exceeds the slot "
                             f"capacity {self.seq_len}")
        if need > self.n_blocks:
            # evicting every other slot could never free enough — without
            # this check the scheduler would requeue/readmit forever
            raise ValueError(f"sequence length {length} needs {need} "
                             f"blocks but the pool holds only "
                             f"{self.n_blocks}; raise page_blocks")
        while self.allocated[slot] < need:
            if not self.free_blocks:
                return False
            b = self.free_blocks.pop()
            self.tables[slot, self.allocated[slot]] = b
            self.allocated[slot] += 1
            self.refcount[b] = 1
        return True

    def free_slot(self, slot: int):
        n = int(self.allocated[slot])
        self._release(int(b) for b in self.tables[slot, :n])
        self.tables[slot, :] = 0
        self.allocated[slot] = 0

    def _release(self, blocks):
        for b in blocks:
            self.refcount[b] -= 1
            assert self.refcount[b] >= 0, f"refcount underflow on block {b}"
            if self.refcount[b] == 0:
                self.free_blocks.append(b)

    # -- prefix aliasing (repro.serving.prefix_cache) --------------------

    def adopt_blocks(self, slot: int, blocks: Sequence[int]):
        """Alias shared blocks (a frozen prefix) into the FRONT of an
        empty slot's table — prefill for those positions becomes this
        table write instead of a forward pass."""
        assert int(self.allocated[slot]) == 0, "adopt into a used slot"
        for j, b in enumerate(blocks):
            self.tables[slot, j] = int(b)
            self.refcount[int(b)] += 1
        self.allocated[slot] = len(blocks)

    def share_blocks(self, slot: int, n_blocks: int) -> List[int]:
        """Take shared references on the slot's first ``n_blocks`` blocks
        (prefix-cache publication); the caller owns the new references
        and must release_blocks() them eventually."""
        assert n_blocks <= int(self.allocated[slot])
        blocks = [int(b) for b in self.tables[slot, :n_blocks]]
        for b in blocks:
            self.refcount[b] += 1
        return blocks

    def release_blocks(self, blocks: Sequence[int]):
        """Drop shared references taken by share_blocks/adopt_blocks."""
        self._release(int(b) for b in blocks)

    def cow_for_write(self, slot: int, block_idxs) -> bool:
        """Copy-on-write: before writing through the given table indices
        of ``slot``, replace any SHARED physical block (refcount > 1)
        with a private copy.  False when the pool has no free block for
        the copy (caller frees/evicts and retries)."""
        for j in sorted({int(i) for i in block_idxs}):
            b = int(self.tables[slot, j])
            if self.refcount[b] <= 1:
                continue
            if not self.free_blocks:
                return False
            nb = self.free_blocks.pop()
            self.pool = self._copy_block(self.pool,
                                         jnp.asarray(b, jnp.int32),
                                         jnp.asarray(nb, jnp.int32))
            self.refcount[b] -= 1
            self.refcount[nb] = 1
            self.tables[slot, j] = nb
            self.cow_copies += 1
        return True

    @property
    def free_block_count(self) -> int:
        return len(self.free_blocks)

    @property
    def live_blocks(self) -> int:
        """Blocks holding at least one reference (conservation probe:
        live + free == n_blocks always)."""
        return int((self.refcount[:self.n_blocks] > 0).sum())

    def device_tables(self) -> jax.Array:
        return jnp.asarray(self.tables)

    # -- high-level ops the runtime uses ---------------------------------

    def gather(self, tables: jax.Array):
        return self._gather(self.pool, tables, self.state)

    def scatter_rows(self, tables, cache, cur_len, active):
        self.pool, self.state = self._scatter_rows(
            self.pool, tables, cache, cur_len, active, self.state)

    def set_state_from(self, cache):
        """Adopt the state leaves of a (already slot-selected) cache view
        — the prefill write-back for the non-paged leaves."""
        if self.state_names:
            self.state = {n: cache[n] for n in self.state_names}

    def reset_state_slot(self, slot: int):
        """Admission-time state reset from the single-slot template (the
        paged counterpart of SlotCacheOps.reset_slot; paged leaves need
        no reset — stale rows are masked or overwritten)."""
        if self.state_names:
            self.state = self._reset_state(
                self.state, jnp.asarray(slot, jnp.int32),
                self.state_template)

    def snapshot_state(self, slot: int) -> Dict[str, jax.Array]:
        """Single-slot copy of the state leaves (prefix-cache snapshot at
        a chunk boundary)."""
        return dict(self._snap_state(self.state,
                                     jnp.asarray(slot, jnp.int32)))

    def restore_state(self, slot: int, snap: Dict[str, jax.Array]):
        if self.state_names:
            self.state = self._restore_state(
                self.state, jnp.asarray(slot, jnp.int32), snap)

    def write_slot_prefix(self, slot: int, cache, length: int,
                          start: int = 0):
        """Persist positions [start, length) of ``slot`` from a
        contiguous cache view into the slot's allocated blocks (prefill /
        chunk write-back).  ``start`` skips blocks already persisted by
        earlier chunks (and, crucially, never rewrites ALIASED prefix
        blocks below it)."""
        if not self.paged_names:
            return
        length = min(length, self.seq_len)
        start = min(start, length)
        b0 = start // self.block
        nb_used = -(-length // self.block)
        n_span = nb_used - b0
        if n_span <= 0:
            return
        assert nb_used <= int(self.allocated[slot]), (nb_used,
                                                      self.allocated[slot])
        if not self.cow_for_write(slot, range(b0, nb_used)):
            raise RuntimeError("pool exhausted during copy-on-write "
                               "span write")   # caller sized the pool
        fn = self._scatter_span_fn(n_span)
        self.pool = fn(self.pool, cache, jnp.asarray(slot, jnp.int32),
                       jnp.asarray(self.tables[slot, b0:nb_used]),
                       jnp.asarray(b0 * self.block, jnp.int32))

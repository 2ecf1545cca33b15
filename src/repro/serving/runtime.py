"""ServingRuntime — the continuous-batching inference engine room.

Ties together the scheduler (host policy), the per-slot / paged caches,
the prefix cache, the presplit weight wrapping, and ONE family of jitted
device steps with a prefill-chunk/decode mode switch:

* ``decode``: one token for every decode-ready slot, each at its OWN
  sequence position (the per-slot ``cur_len`` vector the model families
  accept).  Free and mid-prefill slots compute garbage that either a
  ``cur == 0`` no-op (attention rows), a per-active-slot merge (paged
  state leaves), or a per-slot select (monolithic state families under
  chunking) discards — ONE compiled step serves any occupancy pattern.
* ``chunk`` (per bucket length Lb): a ``lax.scan`` of the decode step
  over Lb positions, teacher-forcing a SLICE of each participating
  prompt RIGHT-ALIGNED in the bucket, starting from ``base`` tokens
  already resident in the slot's cache (``cur = base + i - start + 1``).
  With ``prefill_chunk=None`` the slice is the whole prompt and this IS
  the PR 5 monolithic prefill; with a chunk size C, each scheduler round
  feeds at most C prompt tokens per pending slot and then decodes the
  resident slots — a long prompt no longer stalls everyone's TTFT
  (docs/serving.md derives the TTFT model).  Splitting the scan is
  bitwise-exact: the scan body is the same per-token function either
  way, and each chunk call resumes from exactly the cache the previous
  one wrote.  Slots not in the call are frozen functionally (a per-slot
  select on a cache copy — no model support needed).  The final chunk's
  last-position logits are the slot's first-token prediction.  State
  families (ssm/hybrid) bucket by exact length: their recurrent states
  integrate every fed token, so right-padding can't be masked after the
  fact.

The prefix cache (``repro.serving.prefix_cache``): with paged KV on, a
request whose prompt starts with a previously-published prefix ADOPTS
the frozen pool blocks by table aliasing (plus a state-snapshot restore
for recurrent leaves) and prefills only the suffix — bitwise-identical
to a cold prefill because the frozen blocks were written by the same
jitted chunk calls over the same tokens.  Copy-on-write in the pool
keeps aliased blocks sound if a ring-wrap write ever reaches one.

The weight split-cache: with an ozimmu engine, ``wrap_params`` freezes
every projection weight's int8 digit slices once (eagerly, through
``repro.core.split_cache.SplitCache``), and every jitted step consumes
the wrapped tree — decode-time B-side splitting drops out entirely,
bit-identical to the unwrapped path.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import use_rules
from repro.models import api
from repro.serving import presplit as presplit_mod
from repro.serving.kvcache import PagedKV, SlotCacheOps, STATE_DESCRIPTORS
from repro.obs import registry as _obs
from repro.serving.metrics import ServingMetrics
from repro.serving.prefix_cache import PrefixCache
from repro.serving.scheduler import Request, Scheduler

__all__ = ["ServingRuntime"]

_STATE_FAMILIES = ("ssm", "hybrid")


def _has_state_leaves(cfg) -> bool:
    desc = STATE_DESCRIPTORS.get(cfg.family)
    return desc is not None and "state" in desc.values()


class ServingRuntime:
    """Continuous-batching server over one model + parameter set.

    Args:
      cfg: ModelConfig (the engine spec rides inside it).
      params: model parameters (raw; wrapped internally when presplit).
      slots: decode-slot count (the compiled batch dimension).
      max_len: per-slot cache capacity (prompt + generation budget).
      page_block: positions per KV block — enables the paged pool
        (every family; pure-state families page nothing but gain the
        per-slot state machinery); None keeps the monolithic cache.
      page_blocks: pool size in blocks (default: full capacity,
        slots * max_len / page_block; smaller values exercise eviction).
      prefill_chunk: max prompt tokens fed per slot per scheduler round;
        None prefills whole prompts in one call (the PR 5 behavior).
      prefix_cache: True builds a :class:`PrefixCache` over the paged
        pool (requires ``page_block``); an existing instance bound to
        this runtime's pool is also accepted.
      presplit: freeze weight splits (default: on for ozimmu engines).
      ctx: static per-slot context for the vlm/encdec families, shaped
        for ONE slot (the runtime shares it across slots, matching the
        pre-runtime serve driver).
      now: clock (injectable for deterministic tests).
    """

    def __init__(self, cfg, params, *, slots: int = 4, max_len: int = 128,
                 page_block: Optional[int] = None,
                 page_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: Union[bool, PrefixCache] = False,
                 presplit: Optional[bool] = None, ctx=None,
                 now=time.monotonic):
        self.cfg, self.model = cfg, api.get_model(cfg)
        self.n_slots, self.max_len = slots, max_len
        self.ctx = ctx
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        engine = cfg.engine
        self.split_cache = None
        self._wrapped_bytes = 0       # weight bytes whose split is frozen
        self._avoided_split_bytes = 0  # splitter input bytes skipped so far
        use_presplit = engine.is_ozimmu if presplit is None else presplit
        if use_presplit and engine.is_ozimmu:
            self.params, self.split_cache = presplit_mod.wrap_params(
                params, engine)
            self._wrapped_bytes = presplit_mod.wrapped_weight_bytes(
                self.params, engine)
        else:
            self.params = params
        self.sched = Scheduler(
            slots, bucket="exact" if cfg.family in _STATE_FAMILIES
            else "pow2")
        self.ops = SlotCacheOps(cfg, self.model)
        self.metrics = ServingMetrics(now=now)
        self._now = now
        # trace-time emulation counts of ONE decode step (captured around
        # the first, compiling, decode call — a compiled step replays the
        # same contractions every execution).  None until a step traced
        # with obs enabled; persists across reset_metrics.
        self.decode_observed: Optional[Dict[str, float]] = None
        # prefill/decode calls whose logits of a live slot held a NaN or
        # an inf (greedy argmax would pick a token from them silently)
        self.nonfinite_logit_calls = 0

        batch_ctx = None if ctx is None else jnp.concatenate(
            [ctx] * slots, axis=0)
        # single-slot template: the admission reset source (monolithic
        # always; paged only for families with resident state leaves).
        # Built with sharding rules disabled: a batch-of-1 cache cannot
        # satisfy a `cache_batch -> data` rule (jit arg shardings need
        # exact divisibility); the replicated template scatters into the
        # sharded cache under GSPMD fine.
        self._template_full = None
        if page_block is None or _has_state_leaves(cfg):
            with use_rules(None):
                self._template_full = self.model.init_cache(
                    cfg, 1, max_len, params=self.params, ctx=ctx)
        self.paged: Optional[PagedKV] = None
        if page_block is not None:
            if not PagedKV.supported(cfg, self.model, max_len,
                                     params=self.params, ctx=ctx):
                raise ValueError(
                    f"paged KV unsupported for family {cfg.family!r} "
                    f"(see repro.serving.kvcache); use page_block=None")
            self.paged = PagedKV(cfg, self.model, slots, max_len,
                                 block=page_block, n_blocks=page_blocks,
                                 params=self.params, ctx=batch_ctx,
                                 template=self._template_full)
            self.cache = None
        else:
            self.cache = self.model.init_cache(cfg, slots, max_len,
                                               params=self.params,
                                               ctx=batch_ctx)
        self.prefix: Optional[PrefixCache] = None
        # NOT a truthiness test: an empty PrefixCache instance has
        # len() == 0 and would silently disable itself
        if isinstance(prefix_cache, PrefixCache) or prefix_cache:
            if self.paged is None:
                raise ValueError("the prefix cache aliases paged blocks; "
                                 "it requires page_block")
            self.prefix = prefix_cache if isinstance(
                prefix_cache, PrefixCache) else PrefixCache(self.paged, cfg)
            if self.prefix.paged is not self.paged:
                raise ValueError("prefix cache bound to another pool")
        # monolithic decode must freeze mid-prefill slots' recurrent
        # states under chunking (attention rows are already cur==0
        # no-ops; paged state leaves merge per active slot instead)
        self._decode_select = (prefill_chunk is not None
                               and self.paged is None
                               and cfg.family in _STATE_FAMILIES)
        # host-side per-slot decode state
        self._cur = np.ones((slots,), np.int32)
        self._last_tok = np.zeros((slots,), np.int32)
        self._decode = jax.jit(self._decode_impl)
        self._decode_paged = jax.jit(self._decode_paged_impl)
        self._prefill_fns = {}
        self._evictions_at_reset = 0
        from repro.core.engine import presplit_trace_counts
        self._presplit_counts0 = presplit_trace_counts()
        self._presplit_rate = None    # measured once steps have traced

    # ------------------------------------------------------------------
    # jitted step bodies
    # ------------------------------------------------------------------

    def _greedy(self, logits, live):
        """Greedy tokens of (slots, padded vocab) logits, and whether
        every live slot's logits are finite (idle slots compute on
        garbage and are not looked at)."""
        logits = logits[:, :self.cfg.vocab]
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, jnp.all(jnp.isfinite(logits) | ~live[:, None])

    def _step(self, params, cache, toks, cur, active):
        logits, new_cache = self.model.decode_step(params, self.cfg, cache,
                                                   toks, cur)
        nxt, finite = self._greedy(logits[:, -1], active)
        return nxt, finite, new_cache

    def _decode_impl(self, params, cache, toks, cur, active):
        # no per-slot select by default: inactive slots carry cur == 0,
        # which makes their cache-row writes no-ops
        # (layers.cache_update_row); their other leaves may take garbage,
        # but every leaf is reset from the template at admission before
        # reuse.  The exception is chunked state families (see
        # _decode_select) — a mid-prefill slot's recurrent state is live
        # and must not integrate a decode step.
        nxt, finite, new_cache = self._step(params, cache, toks, cur,
                                            active)
        if self._decode_select:
            new_cache = self.ops.select_slots(new_cache, cache, active)
        return nxt, finite, new_cache

    def _decode_paged_impl(self, params, pool, state, tables, toks, cur,
                           active):
        paged = self.paged
        cache = paged._gather(pool, tables, state)
        nxt, finite, new_cache = self._step(params, cache, toks, cur,
                                            active)
        pool, state = paged._scatter_rows(pool, tables, new_cache, cur,
                                          active, state)
        return nxt, finite, pool, state

    def _chunk_body(self, params, cache, toks, start, base, newmask):
        """scan of the decode step over the bucket; each participating
        slot's chunk is right-aligned and resumes ``base`` tokens in."""
        Lb = toks.shape[1]

        def body(c, i):
            cur = jnp.where(newmask & (i >= start), base + i - start + 1, 0)
            tok = jax.lax.dynamic_slice_in_dim(toks, i, 1, axis=1)
            logits, c = self.model.decode_step(params, self.cfg, c, tok,
                                               cur)
            return c, logits[:, -1]

        cache, logits = jax.lax.scan(body, cache, jnp.arange(Lb))
        nxt, finite = self._greedy(logits[-1], newmask)
        return nxt, finite, cache

    # per-instance memoization by bucket length (NOT functools.lru_cache
    # on the bound method — a class-level cache keyed on self would pin
    # every runtime, its params, and its cache alive for process life)
    def _prefill_fn(self, Lb: int):
        fn = self._prefill_fns.get(Lb)
        if fn is None:
            def impl(params, cache, toks, start, base, newmask):
                nxt, finite, after = self._chunk_body(
                    params, cache, toks, start, base, newmask)
                return nxt, finite, self.ops.select_slots(after, cache,
                                                          newmask)
            fn = self._prefill_fns[Lb] = jax.jit(impl)
        return fn

    def _prefill_paged_fn(self, Lb: int):
        fn = self._prefill_fns.get(("paged", Lb))
        if fn is None:
            def impl(params, pool, state, tables, toks, start, base,
                     newmask):
                cache0 = self.paged._gather(pool, tables, state)
                nxt, finite, after = self._chunk_body(
                    params, cache0, toks, start, base, newmask)
                return nxt, finite, self.ops.select_slots(after, cache0,
                                                          newmask)
            fn = self._prefill_fns[("paged", Lb)] = jax.jit(impl)
        return fn

    # ------------------------------------------------------------------
    # host loop
    # ------------------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new: int,
               eos_id: Optional[int] = None,
               arrival: Optional[float] = None) -> Request:
        plen = len(prompt)
        if plen + max_new > self.max_len and \
                self.cfg.family not in _STATE_FAMILIES and \
                not self.cfg.window:
            raise ValueError(f"prompt({plen}) + max_new({max_new}) exceeds "
                             f"max_len={self.max_len}")
        req = self.sched.submit(prompt, max_new, eos_id=eos_id,
                                arrival=self._now() if arrival is None
                                else arrival)
        self.metrics.requests_submitted += 1   # after validation
        return req

    def _pool_pressure(self, protect: int) -> bool:
        """Free pool blocks: LRU prefix entries go first (cache entries
        are cheaper to lose than live progress), then the scheduler
        preempts a slot.  False when ``protect`` itself was evicted."""
        t0 = self._now()
        try:
            if self.prefix is not None and self.prefix.release_one():
                return True
            victim = self.sched.pick_victim(protect=protect)
            if victim is None:
                victim = protect    # nothing else to take — preempt self
            self.sched.evict(victim)
            self.paged.free_slot(victim)
            return victim != protect
        finally:
            self.metrics.observe_timing("eviction", self._now() - t0)

    def _alloc_or_evict(self, slot: int, length: int) -> bool:
        """Paged block allocation with eviction pressure; False when the
        requesting slot itself was evicted."""
        if self.paged is None:
            return True
        while not self.paged.ensure(slot, length):
            if not self._pool_pressure(slot):
                return False
        return True

    def _cow_or_evict(self, slot: int, block_idxs) -> bool:
        """Copy-on-write with eviction pressure (a copy needs one free
        block); False when the requesting slot itself was evicted."""
        block_idxs = list(block_idxs)
        copies0 = self.paged.cow_copies
        t0 = self._now()
        try:
            while not self.paged.cow_for_write(slot, block_idxs):
                if not self._pool_pressure(slot):
                    return False
            return True
        finally:
            if self.paged.cow_copies > copies0:
                self.metrics.observe_timing("cow_copy", self._now() - t0)

    # -- admission -------------------------------------------------------

    def _on_admit(self, slot: int, req: Request):
        """Per-slot cache preparation at admission: template reset, or a
        prefix-cache adoption that starts the slot mid-prefill."""
        if self.paged is None:
            self.cache = self.ops.reset_slot(self.cache, slot,
                                             self._template_full)
            return
        entry = None if self.prefix is None else \
            self.prefix.lookup(req.prefill_tokens())
        if entry is not None:
            # prefill for the aliased positions is this table write
            self.sched.slots[slot].prefilled = self.prefix.adopt(slot,
                                                                 entry)
        else:
            self.paged.reset_state_slot(slot)

    # -- chunked prefill -------------------------------------------------

    def _plan_chunks(self) -> List[Tuple[int, Request, int]]:
        """One (slot, request, chunk_len) plan per pending-prefill slot.
        The chunk is the whole remaining prefill unless ``prefill_chunk``
        caps it; a publishable prompt additionally forces a boundary at
        its aligned publication length so the prefix snapshot exists."""
        plans = []
        for slot, req in self.sched.pending_prefill():
            total = len(req.prefill_tokens())
            done = self.sched.slots[slot].prefilled
            clen = total - done
            if self.prefill_chunk is not None:
                clen = min(clen, self.prefill_chunk)
            if self.prefix is not None and not req.generated:
                m_pub = self.prefix.max_publish_len(total)
                if done < m_pub:
                    clen = min(clen, m_pub - done)
            plans.append((slot, req, clen))
        return plans

    def _span_args(self, done: int, clen: int) -> Tuple[int, int]:
        """(length, start) for the pool write-back of a chunk that fed
        positions [done, done+clen): the straight span, or the whole
        ring when the chunk wrapped a windowed cache."""
        seq = self.paged.seq_len
        end = done + clen
        if done >= seq or end > seq:
            return seq, 0
        return end, done

    def _do_prefill_round(self):
        """Feed ONE chunk into every pending-prefill slot (grouped by
        chunk-length bucket so mixed lengths share compiled calls);
        final chunks produce the slot's first token."""
        plans = self._plan_chunks()
        if not plans:
            return
        for Lb, group in self.sched.chunk_groups(plans):
            # paged: allocate blocks for the chunk first (may evict
            # group members — drop those from this call), then privatize
            # any shared block the write-back span will touch
            ready = []
            for slot, req, clen in group:
                if self.sched.slots[slot].request is not req:
                    continue    # evicted by an earlier bucket this round
                done = self.sched.slots[slot].prefilled
                if not self._alloc_or_evict(slot, done + clen):
                    continue
                if self.paged is not None and self.paged.paged_names:
                    length, start = self._span_args(done, clen)
                    b0 = start // self.paged.block
                    nb = -(-length // self.paged.block)
                    if not self._cow_or_evict(slot, range(b0, nb)):
                        continue
                ready.append((slot, req, clen))
            # a later allocation may have evicted an earlier group member
            ready = [(s, r, c) for s, r, c in ready
                     if self.sched.slots[s].request is r]
            if not ready:
                continue
            toks = np.zeros((self.n_slots, Lb), np.int32)
            start = np.full((self.n_slots,), Lb, np.int32)
            base = np.zeros((self.n_slots,), np.int32)
            newmask = np.zeros((self.n_slots,), bool)
            for slot, req, clen in ready:
                done = self.sched.slots[slot].prefilled
                pt = req.prefill_tokens()
                toks[slot, Lb - clen:] = pt[done:done + clen]
                start[slot] = Lb - clen
                base[slot] = done
                newmask[slot] = True
            t0 = self._now()
            if self.paged is not None:
                fn = self._prefill_paged_fn(Lb)
                nxt, finite, after = fn(self.params, self.paged.pool,
                                        self.paged.state,
                                        self.paged.device_tables(),
                                        jnp.asarray(toks),
                                        jnp.asarray(start),
                                        jnp.asarray(base),
                                        jnp.asarray(newmask))
                for slot, req, clen in ready:
                    done = self.sched.slots[slot].prefilled
                    length, span_start = self._span_args(done, clen)
                    self.paged.write_slot_prefix(slot, after, length,
                                                 start=span_start)
                self.paged.set_state_from(after)
            else:
                fn = self._prefill_fn(Lb)
                nxt, finite, self.cache = fn(
                    self.params, self.cache, jnp.asarray(toks),
                    jnp.asarray(start), jnp.asarray(base),
                    jnp.asarray(newmask))
            nxt, finite = jax.device_get((nxt, finite))
            self.nonfinite_logit_calls += int(not finite)
            now = self._now()
            self.metrics.prefill_calls += 1
            self.metrics.observe_timing("prefill_call", now - t0)
            # every scanned position consumes every frozen weight split
            self._avoided_split_bytes += Lb * self._wrapped_bytes
            for slot, req, clen in ready:
                done = self.sched.slots[slot].prefilled
                total = len(req.prefill_tokens())
                self.metrics.prefill_tokens += clen
                if done + clen < total:
                    self.sched.on_chunk(slot, clen)
                    self.metrics.prefill_chunks += 1
                    self._maybe_publish(slot, req)
                    continue
                self.metrics.tokens_generated += 1  # the first new token
                finished = self.sched.on_prefilled(slot, int(nxt[slot]),
                                                   now)
                self._cur[slot] = self.sched.slots[slot].pos + 1 \
                    if not finished else 1
                self._last_tok[slot] = int(nxt[slot])
                if finished:
                    self._finish(slot, req, now)

    def _maybe_publish(self, slot: int, req: Request):
        """Publish the frozen prefix when a chunk boundary lands exactly
        on the prompt's aligned publication length (fresh prompts only —
        eviction resumes carry generated tokens and re-hit instead)."""
        if self.prefix is None or req.generated:
            return
        m_pub = self.prefix.max_publish_len(len(req.prompt))
        if m_pub >= self.prefix.block and \
                self.sched.slots[slot].prefilled == m_pub:
            self.prefix.publish(req.prompt, m_pub, slot)

    def _finish(self, slot: int, req: Request, now: float):
        if self.paged is not None:
            self.paged.free_slot(slot)
        self.metrics.record_finish(req, now)

    def _do_decode(self):
        active_idx = self.sched.decode_slots()
        if not active_idx:
            return
        if self.paged is not None:
            # this step writes row cur-1, so the slot needs `cur`
            # positions allocated, and the written block privatized
            survivors = []
            for slot in active_idx:
                if self.sched.slots[slot].request is None:
                    continue    # evicted back by pressure from a peer
                cur = int(self._cur[slot])
                if not self._alloc_or_evict(slot, cur):
                    continue
                if self.paged.paged_names:
                    pos = (cur - 1) % self.paged.seq_len
                    if not self._cow_or_evict(slot,
                                              [pos // self.paged.block]):
                        continue
                survivors.append(slot)
            active_idx = [s for s in survivors
                          if self.sched.slots[s].request is not None]
            if not active_idx:
                return
        active = np.zeros((self.n_slots,), bool)
        active[active_idx] = True
        # per-slot position of the token being written this step; 0 for
        # idle slots = "write nothing" (cache_update_row no-op)
        cur = np.where(active, self._cur, 0).astype(np.int32)
        toks = self._last_tok[:, None].astype(np.int32)
        cap = None
        if self.decode_observed is None and _obs.enabled():
            cap = _obs.get_registry().snapshot()
        t0 = self._now()
        if self.paged is not None:
            nxt, finite, pool, state = self._decode_paged(
                self.params, self.paged.pool, self.paged.state,
                self.paged.device_tables(), jnp.asarray(toks),
                jnp.asarray(cur), jnp.asarray(active))
            self.paged.pool, self.paged.state = pool, state
        else:
            nxt, finite, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(toks),
                jnp.asarray(cur), jnp.asarray(active))
        nxt, finite = jax.device_get((nxt, finite))
        self.nonfinite_logit_calls += int(not finite)
        now = self._now()
        if cap is not None:
            d = _obs.get_registry().snapshot().diff(cap)
            self.decode_observed = {
                "contractions": d.total("emulation.calls"),
                "int8_gemms": d.total("emulation.int8_gemms"),
                "int8_gemms_planned": d.total(
                    "emulation.int8_gemms_planned"),
                "int8_gemms_presplit": d.total("emulation.int8_gemms",
                                               presplit=1),
                "highprec_adds": d.total("emulation.highprec_adds"),
            }
        self.metrics.decode_steps += 1
        self.metrics.observe_timing("decode_step", now - t0)
        self._avoided_split_bytes += self._wrapped_bytes
        for slot in active_idx:
            req = self.sched.slots[slot].request
            self.metrics.tokens_generated += 1
            finished = self.sched.on_token(slot, int(nxt[slot]), now)
            if finished:
                self._finish(slot, req, now)
            else:
                self._cur[slot] = self.sched.slots[slot].pos + 1
                self._last_tok[slot] = int(nxt[slot])

    def step(self) -> bool:
        """One scheduler round: admit new requests, feed one prefill
        chunk per pending slot, then decode one token for every
        fully-prefilled slot.  Returns False when idle."""
        if self.sched.all_done:
            return False
        self.metrics.start()
        self.metrics.sample_queue(self.sched.queue_depth)
        for slot, req in self.sched.admit():
            self._on_admit(slot, req)
        self._do_prefill_round()
        self._do_decode()
        return True

    def run(self, max_steps: Optional[int] = None) -> Dict[str, Any]:
        """Drive the loop until every submitted request finished (or
        ``max_steps`` scheduler rounds); returns the metrics summary."""
        steps = 0
        while self.step():
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        self.metrics.stop()
        # evictions within THIS metrics window (reset_metrics snapshots)
        self.metrics.evictions = self.sched.evictions - \
            self._evictions_at_reset
        if self.prefix is not None:
            self.metrics.prefix_cache = self.prefix.summary()
        if self.split_cache is not None:
            d = self.split_cache.stats.as_dict()
            # MEASURED hit rate from the engine's trace-time consumption
            # counters: the fraction of wrapped-weight contractions whose
            # frozen split actually applied (a silent `usable_split`
            # fallback — dnums/spec/dtype drift — lowers it, which is
            # what the bench gate exists to catch).  Compiled steps count
            # once at trace time; a window with no fresh traces (warm
            # replay after reset_metrics) keeps the last measured rate.
            from repro.core.engine import presplit_trace_counts
            counts = presplit_trace_counts()
            d_used = counts["used"] - self._presplit_counts0["used"]
            d_fb = counts["fallback"] - self._presplit_counts0["fallback"]
            if d_used + d_fb:
                self._presplit_rate = d_used / (d_used + d_fb)
            rate = self._presplit_rate
            if rate is None:
                rate = 1.0 if self._wrapped_bytes else 0.0
            d.update({
                "frozen_weight_bytes": self._wrapped_bytes,
                "avoided_split_bytes": self._avoided_split_bytes,
                "weight_split_hit_rate": rate,
            })
            self.metrics.split_cache = d
        return self.metrics.summary()

    def reset_metrics(self):
        """Fresh metrics window (e.g. timing a steady-state pass after a
        warm-up replay compiled every bucket).  Scheduler, caches, jit
        caches, and prefix-cache ENTRIES are untouched — the runtime
        keeps serving; prefix hit counters restart with the window."""
        self.metrics = ServingMetrics(now=self._now)
        self._avoided_split_bytes = 0
        self._evictions_at_reset = self.sched.evictions
        if self.prefix is not None:
            self.prefix.reset_stats()

    # convenience for tests / examples ---------------------------------

    def generate(self, prompts: List[np.ndarray], max_new: int,
                 eos_id: Optional[int] = None) -> List[np.ndarray]:
        """Submit a batch and run to completion; returns prompt+generated
        per request, in submission order."""
        reqs = [self.submit(p, max_new, eos_id=eos_id) for p in prompts]
        self.run()
        return [np.concatenate([r.prompt,
                                np.asarray(r.generated, np.int32)])
                for r in reqs]

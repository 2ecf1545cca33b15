"""Serving runtime + persistent weight split-cache.

Covers the PR-5 subsystem (docs/serving.md):

* cached-vs-uncached bitwise identity of the presplit path for all six
  variants x accumulator dtypes x the fused pipeline, eager and jitted,
  including auto-k (frozen static plan == traced static plan);
* the engine-level `PresplitWeight` wrapper (use + safe fallback);
* SplitCache keying (spec miss, update miss, weakref invalidation);
* scheduler invariants (no slot leak, FIFO fairness under eviction,
  bucketed prefill grouping);
* runtime end-to-end vs a per-request reference decode (continuous
  batching with mixed prompt lengths is bitwise-faithful), presplit on
  and off;
* paged-KV equivalence to the monolithic cache per token, including
  under pool pressure (evictions);
* chunked prefill: bitwise identity to monolithic prefill across chunk
  sizes (1, a non-divisor, larger-than-any-prompt), decode interleaving
  during a long chunked prefill, and a chunked+paged soak;
* property-based scheduler/pool invariants (hypothesis when installed;
  skipped gracefully otherwise — tests/conftest.hypothesis_or_stubs);
* `slow`-marked soak replays (random trace, tight pool, chunking).

The `@mesh` composition of the presplit path is asserted in
tests/test_distributed.py (needs forced host devices).
"""
import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ozimmu, split_cache
from repro.core.engine import PresplitWeight, make_engine

DN = (((1,), (0,)), ((), ()))


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal((6, 96)))
    b = jnp.asarray(rng.standard_normal((96, 10)))
    return a, b


# ---------------------------------------------------------------------------
# presplit bitwise identity
# ---------------------------------------------------------------------------

SPECS = [
    f"{variant}-4{dt}{fused}"
    for variant in ("ozimmu", "ozimmu_rn", "ozimmu_ef", "ozimmu_h",
                    "ozimmu_sm_b", "ozimmu_sm_h", "oz2_b", "oz2_h")
    for dt in ("", ":df32", ":f32")
    for fused in ("", ":fused")
] + ["oz2_h-4:fast", "oz2_b-4:df32:fast", "oz2_h-4:fast:fused",
     "oz2_h-4:fast2", "oz2_b-4:df32:fast2", "oz2_h-4:fast2:fused"]


@pytest.mark.parametrize("spec", SPECS)
def test_presplit_bitwise(spec, operands):
    """Frozen-B path == splitter-in-the-loop path, bit for bit, eager and
    under jit (the serving steps are jitted)."""
    a, b = operands
    cfg = ozimmu.parse_spec(spec)
    cache = split_cache.SplitCache()
    sp = cache.get(b, DN, cfg)
    ref = ozimmu.ozimmu_dot_general(a, b, DN, cfg)
    out = ozimmu.ozimmu_dot_general(a, b, DN, cfg, rhs_presplit=sp)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    jit_ref = jax.jit(
        lambda a, b: ozimmu.ozimmu_dot_general(a, b, DN, cfg))(a, b)
    jit_out = jax.jit(
        lambda a, b, sp: ozimmu.ozimmu_dot_general(a, b, DN, cfg,
                                                   rhs_presplit=sp)
    )(a, b, sp)
    np.testing.assert_array_equal(np.asarray(jit_out), np.asarray(jit_ref))


def test_presplit_bitwise_batched_dnums():
    """Expert-style stacked rhs: batch dims ride through the frozen split."""
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((3, 5, 64)))
    b = jnp.asarray(rng.standard_normal((3, 64, 7)))
    dn = (((2,), (1,)), ((0,), (0,)))
    for spec in ("ozimmu_h-5:df32", "oz2_h-5:fast", "oz2_h-5:fast2"):
        cfg = ozimmu.parse_spec(spec)
        sp = split_cache.SplitCache().get(b, dn, cfg)
        ref = ozimmu.ozimmu_dot_general(a, b, dn, cfg)
        out = ozimmu.ozimmu_dot_general(a, b, dn, cfg, rhs_presplit=sp)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_presplit_auto_k_matches_jitted_plan(operands):
    """Auto-k freezes the static mantissa-coverage k — the same k a
    jitted (traced) call resolves — so cached and uncached jitted paths
    agree bitwise."""
    a, b = operands
    for spec in ("ozimmu_h-auto:df32", "oz2_h-auto:fast",
                 "oz2_h-auto:fast2"):
        cfg = ozimmu.parse_spec(spec)
        sp = split_cache.SplitCache().get(b, DN, cfg)
        assert sp.digits.shape[0] == split_cache.resolved_k(
            cfg, b.shape[0], b.dtype)
        ref = jax.jit(
            lambda a, b: ozimmu.ozimmu_dot_general(a, b, DN, cfg))(a, b)
        out = jax.jit(
            lambda a, b, sp: ozimmu.ozimmu_dot_general(
                a, b, DN, cfg, rhs_presplit=sp))(a, b, sp)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("spec", ["ozimmu_h-4:df32", "oz2_h-4:fast2",
                                  "oz2_b-4:df32:fast2"])
def test_presplit_grad_matches(spec, operands):
    """Gradients flow through the presplit forward unchanged (cotangent
    contractions never use the frozen split) — including the fast2
    splits, whose base/gbase ride the VJP residual pytree."""
    a, b = operands
    cfg = ozimmu.parse_spec(spec)
    sp = split_cache.SplitCache().get(b, DN, cfg)
    g_ref = jax.grad(
        lambda a, b: ozimmu.ozimmu_dot_general(a, b, DN, cfg).sum(),
        argnums=(0, 1))(a, b)
    g_out = jax.grad(
        lambda a, b: ozimmu.ozimmu_dot_general(
            a, b, DN, cfg, rhs_presplit=sp).sum(), argnums=(0, 1))(a, b)
    for r, o in zip(g_ref, g_out):
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


def test_presplit_mismatch_rejected(operands):
    a, b = operands
    cfg = ozimmu.parse_spec("ozimmu_h-4:df32")
    sp = split_cache.SplitCache().get(b, DN, cfg)
    with pytest.raises(ValueError, match="k="):
        ozimmu.ozimmu_dot_general(a, b, DN, cfg.with_(k=6),
                                  rhs_presplit=sp)
    with pytest.raises(ValueError, match="constant-scaling"):
        ozimmu.ozimmu_dot_general(a, b, DN,
                                  ozimmu.parse_spec("oz2_h-4"),
                                  rhs_presplit=sp)
    # a split frozen under a SIGNED spec cannot serve a sign-magnitude
    # config (its stored digits decode differently) — and vice versa
    with pytest.raises(ValueError, match="signmag"):
        ozimmu.ozimmu_dot_general(a, b, DN,
                                  ozimmu.parse_spec("ozimmu_sm_h-4:df32"),
                                  rhs_presplit=sp)
    sp_sm = split_cache.SplitCache().get(
        b, DN, ozimmu.parse_spec("ozimmu_sm_h-4:df32"))
    with pytest.raises(ValueError, match="signmag"):
        ozimmu.ozimmu_dot_general(a, b, DN, cfg, rhs_presplit=sp_sm)


# ---------------------------------------------------------------------------
# engine wrapper
# ---------------------------------------------------------------------------

def _wrap(w, engine):
    from repro.serving.presplit import freeze_weight
    return freeze_weight(w, engine, split_cache.SplitCache())


def test_engine_wrapper_bitwise(operands):
    a, b = operands
    eng = make_engine("ozimmu_h-4:df32")
    pw = _wrap(b, eng)
    ref = eng(a, b)
    np.testing.assert_array_equal(np.asarray(eng(a, pw)), np.asarray(ref))
    out = jax.jit(lambda x, w: eng(x, w))(a, pw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_engine_wrapper_fallback(operands):
    """A wrapper consumed under an unexpected contraction silently uses
    the raw array (wrapping is always safe)."""
    a, b = operands
    eng = make_engine("ozimmu_h-4:df32")
    pw = _wrap(b, eng)
    # transposed-contraction dnums: not the frozen pattern
    dn = (((1,), (1,)), ((), ()))
    bt = jnp.asarray(np.asarray(b).T)
    pw_t = PresplitWeight(bt, pw.digits, pw.scale, pw.base, pw.gbase,
                          pw.beta, pw.split, pw.k)
    ref = eng.dot_general(a, bt, dn)
    np.testing.assert_array_equal(np.asarray(eng.dot_general(a, pw_t, dn)),
                                  np.asarray(ref))


def test_presplit_consumption_is_measured(operands):
    """The engine records trace-time presplit use vs fallback — the
    serving hit-rate metric is measured, not assumed (a silent
    usable_split fallback must show up in the gated number)."""
    from repro.core.engine import presplit_trace_counts
    a, b = operands
    eng = make_engine("ozimmu_h-4:df32")
    pw = _wrap(b, eng)
    c0 = presplit_trace_counts()
    eng(a, pw)                                    # applies
    other = make_engine("oz2_h-4:df32")           # wrong split strategy
    other(a, pw)                                  # silently falls back
    c1 = presplit_trace_counts()
    assert c1["used"] - c0["used"] == 1
    assert c1["fallback"] - c0["fallback"] == 1


def test_engine_wrapper_stacked_scan(operands):
    """A layer-stacked wrapper sliced by lax.scan equals per-layer calls."""
    a, _ = operands
    rng = np.random.default_rng(11)
    ws = jnp.asarray(rng.standard_normal((3, 96, 10)))
    eng = make_engine("ozimmu_h-4:df32")
    pw = _wrap(ws, eng)
    assert pw.digits.shape[:2] == (3, 4)

    def body(x, w):
        return x, eng(x, w)

    _, outs = jax.lax.scan(body, a, pw)
    for i in range(3):
        np.testing.assert_array_equal(np.asarray(outs[i]),
                                      np.asarray(eng(a, ws[i])))


# ---------------------------------------------------------------------------
# cache keying / invalidation
# ---------------------------------------------------------------------------

def test_cache_keying(operands):
    _, b = operands
    cache = split_cache.SplitCache()
    h = ozimmu.parse_spec("ozimmu_h-4")
    cache.get(b, DN, h)
    assert (cache.stats.hits, cache.stats.misses) == (0, 1)
    cache.get(b, DN, h)
    assert (cache.stats.hits, cache.stats.misses) == (1, 1)
    # same weights + different spec => miss (k, then strategy)
    cache.get(b, DN, h.with_(k=6))
    assert cache.stats.misses == 2
    cache.get(b, DN, ozimmu.parse_spec("oz2_h-4"))
    assert cache.stats.misses == 3
    # fast2 is a DIFFERENT split strategy (oz2_rn_fast2): its own entry,
    # and hitting it again is a hit
    cache.get(b, DN, ozimmu.parse_spec("oz2_h-4:fast2"))
    assert cache.stats.misses == 4
    cache.get(b, DN, ozimmu.parse_spec("oz2_h-4:fast2"))
    assert (cache.stats.hits, cache.stats.misses) == (2, 4)
    # sign-magnitude is its own split strategy ("sm"): a distinct entry
    # from every signed spec at the same k/dtype...
    cache.get(b, DN, ozimmu.parse_spec("ozimmu_sm_h-4"))
    assert cache.stats.misses == 5
    # ...while sm_b / sm_h (same splitter, different accumulation) share
    # one frozen split — the digits are identical by construction
    cache.get(b, DN, ozimmu.parse_spec("ozimmu_sm_b-4"))
    assert (cache.stats.hits, cache.stats.misses) == (3, 5)
    # "updated" weights (a new array) => miss
    b2 = b + 0.0
    cache.get(b2, DN, h)
    assert cache.stats.misses == 6
    assert len(cache) == 6


def test_cache_weakref_invalidation(operands):
    _, b = operands
    cache = split_cache.SplitCache()
    tmp = b * 2.0
    cache.get(tmp, DN, ozimmu.parse_spec("ozimmu_h-4"))
    assert len(cache) == 1
    del tmp
    gc.collect()
    assert len(cache) == 0
    assert cache.stats.invalidations == 1


def test_cache_rejects_tracers(operands):
    _, b = operands
    cache = split_cache.SplitCache()
    cfg = ozimmu.parse_spec("ozimmu_h-4")
    with pytest.raises(TypeError, match="concrete"):
        jax.jit(lambda b: cache.get(b, DN, cfg))(b)


# ---------------------------------------------------------------------------
# scheduler invariants
# ---------------------------------------------------------------------------

def test_scheduler_fifo_and_no_slot_leak():
    from repro.serving.scheduler import Scheduler
    sched = Scheduler(2)
    reqs = [sched.submit([1, 2, 3], max_new=2) for _ in range(5)]
    adm = sched.admit()
    assert [r.rid for _, r in adm] == [reqs[0].rid, reqs[1].rid]
    # finish slot 0's request -> next queued request takes the slot
    sched.on_prefilled(0, first_token=9)
    sched.on_token(0, 9)                     # max_new=2 -> finished
    assert sched.slots[0].free
    adm2 = sched.admit()
    assert [r.rid for _, r in adm2] == [reqs[2].rid]
    # invariant: active + free == n_slots (checked internally every op)
    assert len(sched.active_slots()) + sum(
        s.free for s in sched.slots) == 2


def test_scheduler_eviction_fifo_fair():
    from repro.serving.scheduler import Scheduler
    sched = Scheduler(3)
    reqs = [sched.submit([1] * 4, max_new=8) for _ in range(3)]
    sched.admit()
    for i in range(3):
        sched.on_prefilled(i, first_token=5)
    # victim is the LATEST-admitted slot, never the earliest request
    victim = sched.pick_victim()
    assert sched.slots[victim].request is reqs[2]
    evicted = sched.evict(victim)
    assert evicted is reqs[2]
    # evicted request resumes from the FRONT of the queue with its
    # generated tokens carried (re-prefill = prompt + generated)
    assert sched.queue[0] is reqs[2]
    assert list(evicted.prefill_tokens()) == [1, 1, 1, 1, 5]
    adm = sched.admit()
    assert adm[0][1] is reqs[2]


def test_scheduler_random_soak_invariants():
    from repro.serving.scheduler import Scheduler
    rng = np.random.default_rng(0)
    sched = Scheduler(3)
    for _ in range(200):
        op = rng.integers(0, 4)
        if op == 0:
            sched.submit([1] * int(rng.integers(1, 6)),
                         max_new=int(rng.integers(1, 4)))
        elif op == 1:
            for slot, _ in sched.admit():
                sched.on_prefilled(slot, int(rng.integers(0, 9)))
        elif op == 2:
            for slot in list(sched.active_slots()):
                sched.on_token(slot, int(rng.integers(0, 9)))
        else:
            v = sched.pick_victim()
            if v is not None:
                sched.evict(v)
    # every op ran the internal _check() leak assertions; drain cleanly
    while not sched.all_done:
        for slot, _ in sched.admit():
            sched.on_prefilled(slot, 1)
        for slot in list(sched.active_slots()):
            sched.on_token(slot, 1)


def test_prefill_bucketing():
    from repro.serving.scheduler import Scheduler
    sched = Scheduler(4, bucket="pow2")
    rs = [sched.submit([1] * n, max_new=1) for n in (3, 8, 9, 5)]
    groups = dict(sched.prefill_groups(sched.admit()))
    assert set(groups) == {8, 16}
    assert sorted(r.rid for _, r in groups[8]) == [rs[0].rid, rs[1].rid,
                                                   rs[3].rid]


# ---------------------------------------------------------------------------
# runtime end-to-end
# ---------------------------------------------------------------------------

GEN = 4
PROMPT_LENS = (5, 9, 3, 11, 7)


@pytest.fixture(scope="module")
def served():
    """One smoke model + reference outputs, shared by the e2e tests."""
    from repro import configs
    from repro.models import api
    cfg = configs.get_config("internlm2_1_8b", smoke=True,
                             engine_spec="ozimmu_h-4:df32")
    model = api.get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n, dtype=np.int32)
               for n in PROMPT_LENS]

    step = jax.jit(lambda c, t, n: model.decode_step(params, cfg, c, t, n))

    def reference(prompt):
        cache = model.init_cache(cfg, 1, 64)
        logits = None
        for t, tok in enumerate(prompt):
            logits, cache = step(cache, jnp.asarray([[tok]], jnp.int32),
                                 jnp.asarray(t + 1, jnp.int32))
        out = list(prompt)
        cur = int(jnp.argmax(logits[0, -1, :cfg.vocab]))
        for g in range(GEN):
            out.append(cur)
            logits, cache = step(cache, jnp.asarray([[cur]], jnp.int32),
                                 jnp.asarray(len(prompt) + g + 1,
                                             jnp.int32))
            cur = int(jnp.argmax(logits[0, -1, :cfg.vocab]))
        return np.asarray(out)

    refs = [reference(p) for p in prompts]
    return cfg, params, prompts, refs


def _run(cfg, params, prompts, **kw):
    from repro.serving import ServingRuntime
    rt = ServingRuntime(cfg, params, slots=3, max_len=64, **kw)
    outs = rt.generate([p.copy() for p in prompts], GEN)
    return rt, outs


def test_runtime_matches_reference_presplit(served):
    """Continuous batching with mixed prompt lengths + the weight
    split-cache reproduces the per-request reference decode bitwise."""
    cfg, params, prompts, refs = served
    rt, outs = _run(cfg, params, prompts)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    s = rt.metrics.summary()
    assert s["requests"]["finished"] == len(prompts)
    assert s["tokens_generated"] == GEN * len(prompts)
    from repro.serving.presplit import wrappable_paths
    sc = s["split_cache"]
    assert sc["weight_split_hit_rate"] == 1.0
    assert sc["avoided_split_bytes"] > 0
    assert sc["misses"] == len(wrappable_paths(params))
    assert rt.nonfinite_logit_calls == 0
    # the finite flag looks at live slots only
    bad = jnp.zeros((2, cfg.vocab)).at[0, 3].set(jnp.nan)
    assert bool(rt._greedy(bad, jnp.asarray([False, True]))[1])
    assert not bool(rt._greedy(bad, jnp.asarray([True, True]))[1])


def test_runtime_matches_reference_no_presplit(served):
    cfg, params, prompts, refs = served
    _, outs = _run(cfg, params, prompts, presplit=False)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_paged_equals_monolithic_per_token(served):
    """Block-paged KV pool: same tokens as the monolithic cache."""
    cfg, params, prompts, refs = served
    rt, outs = _run(cfg, params, prompts, page_block=8)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    assert rt.metrics.summary()["evictions"] == 0


def test_paged_eviction_pressure(served):
    """A pool too small for all slots forces eviction; outputs stay
    correct (recompute-resume) and the earliest request is never the
    victim (FIFO fairness)."""
    cfg, params, prompts, refs = served
    # 3 blocks of 8 positions: the admission wave alone wants 4 (1+2+1),
    # so the latest-admitted slot is preempted at prefill time
    rt, outs = _run(cfg, params, prompts, page_block=8, page_blocks=3)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    s = rt.metrics.summary()
    assert s["evictions"] > 0
    assert s["requests"]["finished"] == len(prompts)


def test_runtime_matches_reference_oz2(served):
    """oz2 engines are the sensitive case for slot hygiene: one garbage
    cache row would shift the GLOBAL digit grid of the whole per-slot
    operand (per-row ozimmu scales only ever confine damage to a masked
    row/column).  The right-aligned prefill warm-up and idle decode slots
    must therefore write NOTHING (cache_update_row's cur==0 no-op)."""
    from repro import configs
    from repro.models import api
    from repro.serving import ServingRuntime
    cfg = configs.get_config("internlm2_1_8b", smoke=True,
                             engine_spec="oz2_h-4:df32:fast")
    model = api.get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    _, _, prompts, _ = served
    prompts = prompts[:3]
    step = jax.jit(lambda c, t, n: model.decode_step(params, cfg, c, t, n))

    def reference(prompt):
        cache = model.init_cache(cfg, 1, 64)
        logits = None
        for t, tok in enumerate(prompt):
            logits, cache = step(cache, jnp.asarray([[tok]], jnp.int32),
                                 jnp.asarray(t + 1, jnp.int32))
        out = list(prompt)
        cur = int(jnp.argmax(logits[0, -1, :cfg.vocab]))
        for g in range(3):
            out.append(cur)
            logits, cache = step(cache, jnp.asarray([[cur]], jnp.int32),
                                 jnp.asarray(len(prompt) + g + 1,
                                             jnp.int32))
            cur = int(jnp.argmax(logits[0, -1, :cfg.vocab]))
        return np.asarray(out)

    refs = [reference(p) for p in prompts]
    rt = ServingRuntime(cfg, params, slots=2, max_len=64)
    outs = rt.generate([p.copy() for p in prompts], 3)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_runtime_ssm_family(served):
    """State-family (exact-length prefill buckets) end-to-end smoke."""
    from repro import configs
    from repro.models import api
    from repro.serving import ServingRuntime
    cfg = configs.get_config("mamba2_780m", smoke=True)
    model = api.get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=n, dtype=np.int32)
               for n in (4, 6, 4)]
    step = jax.jit(lambda c, t, n: model.decode_step(params, cfg, c, t, n))

    def reference(prompt):
        cache = model.init_cache(cfg, 1, 32)
        logits = None
        for t, tok in enumerate(prompt):
            logits, cache = step(cache, jnp.asarray([[tok]], jnp.int32),
                                 jnp.asarray(t + 1, jnp.int32))
        out = list(prompt)
        cur = int(jnp.argmax(logits[0, -1, :cfg.vocab]))
        for g in range(3):
            out.append(cur)
            logits, cache = step(cache, jnp.asarray([[cur]], jnp.int32),
                                 jnp.asarray(len(prompt) + g + 1,
                                             jnp.int32))
            cur = int(jnp.argmax(logits[0, -1, :cfg.vocab]))
        return np.asarray(out)

    refs = [reference(p) for p in prompts]
    rt = ServingRuntime(cfg, params, slots=2, max_len=32)
    outs = rt.generate([p.copy() for p in prompts], 3)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


@pytest.mark.slow
def test_serving_soak_random_trace(served):
    """Soak: a longer random mixed trace under tight pool pressure —
    every request completes with the reference continuation."""
    cfg, params, prompts, refs = served
    from benchmarks.bench_serving import make_trace, replay
    from repro.serving import ServingRuntime
    rng = np.random.default_rng(42)
    trace = make_trace(rng, n_requests=9, vocab=cfg.vocab, max_len=48)
    rt = ServingRuntime(cfg, params, slots=3, max_len=48, page_block=8,
                        page_blocks=10)
    summary = replay(rt, trace)
    assert summary["requests"]["finished"] == len(trace)
    assert summary["tokens_generated"] == sum(r["max_new"] for r in trace)
    assert summary["split_cache"]["weight_split_hit_rate"] == 1.0


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", (1, 5, 16))
def test_chunked_prefill_equals_monolithic(served, chunk):
    """Splitting the prefill scan is bitwise-exact: the scan body is the
    same per-token function, each chunk resumes from the cache the
    previous one wrote.  chunk=1 is the extreme (every prompt token its
    own round), 5 divides none of the prompt lengths, 16 exceeds them
    all (degenerates to monolithic prefill)."""
    cfg, params, prompts, refs = served
    rt, outs = _run(cfg, params, prompts, prefill_chunk=chunk)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    s = rt.metrics.summary()
    if chunk < max(PROMPT_LENS):
        assert s["prefill_chunks"] > 0      # actually chunked
    else:
        assert s["prefill_chunks"] == 0     # one call per prompt


@pytest.mark.parametrize("chunk", (1, 5))
def test_chunked_prefill_paged_equals_monolithic(served, chunk):
    """Chunked prefill over the paged pool (span write-back per chunk)
    is bitwise too."""
    cfg, params, prompts, refs = served
    _, outs = _run(cfg, params, prompts, prefill_chunk=chunk,
                   page_block=8)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_chunked_prefill_ssm_family():
    """State families freeze mid-prefill recurrent states through the
    decode-side slot select (`_decode_select`) — a neighbour's decode
    step must not integrate into a half-prefilled SSM state."""
    from repro import configs
    from repro.models import api
    from repro.serving import ServingRuntime
    cfg = configs.get_config("mamba2_780m", smoke=True)
    model = api.get_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n, dtype=np.int32)
               for n in (4, 7, 5)]
    ref_rt = ServingRuntime(cfg, params, slots=2, max_len=32)
    refs = ref_rt.generate([p.copy() for p in prompts], 3)
    rt = ServingRuntime(cfg, params, slots=2, max_len=32,
                        prefill_chunk=2)
    outs = rt.generate([p.copy() for p in prompts], 3)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)


def test_chunked_prefill_interleaves_decode(served):
    """Ordering invariant: while a long prompt trickles in chunk by
    chunk, already-resident slots keep producing one token per round —
    chunking exists so a long prefill cannot stall TTFT/ITL for
    everyone else."""
    from repro.serving import ServingRuntime
    cfg, params, prompts, _ = served
    rt = ServingRuntime(cfg, params, slots=2, max_len=64,
                        prefill_chunk=2)
    short = rt.submit(prompts[2], max_new=12)       # 3 tokens
    for _ in range(3):
        rt.step()
    n0 = len(short.generated)
    assert n0 > 0                                   # already decoding
    long_req = rt.submit(prompts[3], max_new=2)     # 11 tokens: 6 chunks
    for _ in range(4):
        rt.step()
    # the long prompt is still mid-prefill: no token produced yet ...
    assert len(long_req.generated) == 0
    assert rt.metrics.summary()["prefill_chunks"] >= 3
    # ... while the short request advanced one token EVERY round
    assert len(short.generated) == n0 + 4
    rt.run()
    assert len(short.generated) == 12
    assert len(long_req.generated) == 2


@pytest.mark.slow
def test_serving_soak_chunked_eviction(served):
    """Soak: random trace under tight pool pressure WITH chunked prefill
    and the prefix cache — every scheduler op runs the internal
    slot-leak `_check`, every request completes, blocks conserve."""
    cfg, params, prompts, refs = served
    from benchmarks.bench_serving import make_trace, replay
    from repro.serving import ServingRuntime
    rng = np.random.default_rng(43)
    trace = make_trace(rng, n_requests=9, vocab=cfg.vocab, max_len=48)
    rt = ServingRuntime(cfg, params, slots=3, max_len=48, page_block=8,
                        page_blocks=10, prefill_chunk=3,
                        prefix_cache=True)
    summary = replay(rt, trace)
    assert summary["requests"]["finished"] == len(trace)
    assert summary["tokens_generated"] == sum(r["max_new"] for r in trace)
    paged = rt.paged
    assert paged.live_blocks + paged.free_block_count == paged.n_blocks


# ---------------------------------------------------------------------------
# property-based invariants (hypothesis when installed)
# ---------------------------------------------------------------------------

from tests.conftest import hypothesis_or_stubs

given, settings, st = hypothesis_or_stubs()


@given(st.integers(0, 2**32 - 1))
@settings(max_examples=25, deadline=None)
def test_prop_scheduler_fifo_and_no_dropped_tokens(seed):
    """Random op soup, then drain.  Properties: (1) FIRST admissions
    follow submission order exactly (FIFO; front-requeued evictees are
    RE-admissions and exempt); (2) no generated token is ever dropped on
    requeue — we feed each request the sequence 0,1,2,... and every
    finished request must hold exactly range(max_new)."""
    from repro.serving.scheduler import Scheduler
    rng = np.random.default_rng(seed)
    sched = Scheduler(int(rng.integers(1, 4)))
    submitted, first_admits = [], []

    def admit():
        for _, r in sched.admit():
            if r.prefills == 1:
                first_admits.append(r.rid)

    def prefill_round(chunked):
        for slot, r in sched.pending_prefill():
            rem = len(r.prefill_tokens()) - sched.slots[slot].prefilled
            c = int(rng.integers(1, rem + 1)) if chunked else rem
            if c < rem:
                sched.on_chunk(slot, c)
            else:
                sched.on_prefilled(slot, len(r.generated))

    def decode_round():
        for slot in list(sched.decode_slots()):
            r = sched.slots[slot].request
            sched.on_token(slot, len(r.generated))

    for _ in range(60):
        op = rng.integers(0, 5)
        if op == 0 and len(submitted) < 12:
            submitted.append(sched.submit(
                [1] * int(rng.integers(1, 6)),
                max_new=int(rng.integers(1, 4))))
        elif op == 1:
            admit()
        elif op == 2:
            prefill_round(chunked=True)
        elif op == 3:
            decode_round()
        else:
            v = sched.pick_victim()
            if v is not None:
                sched.evict(v)
    while not sched.all_done:
        admit()
        prefill_round(chunked=False)
        decode_round()
    assert first_admits == [r.rid for r in submitted]
    assert len(sched.finished) == len(submitted)
    for r in sched.finished:
        assert r.generated == list(range(r.max_new))


@given(st.integers(0, 2**32 - 1))
@settings(max_examples=10, deadline=None)
def test_prop_paged_block_conservation(seed):
    """Random alloc/free/share/adopt/CoW ops on a real pool: after every
    op `live + free == n_blocks`, and releasing every reference at drain
    returns every block to the free list (alloc == free)."""
    from repro import configs
    from repro.models import api
    from repro.serving import PagedKV
    cfg = configs.get_config("internlm2_1_8b", smoke=True)
    model = api.get_model(cfg)
    paged = PagedKV(cfg, model, 3, 32, block=8)
    rng = np.random.default_rng(seed)
    entries = []
    for _ in range(40):
        op = rng.integers(0, 6)
        slot = int(rng.integers(0, 3))
        if op == 0:
            paged.ensure(slot, int(rng.integers(1, 33)))
        elif op == 1:
            paged.free_slot(slot)
        elif op == 2 and int(paged.allocated[slot]):
            n = int(rng.integers(1, int(paged.allocated[slot]) + 1))
            entries.append(paged.share_blocks(slot, n))
        elif op == 3 and entries:
            paged.release_blocks(
                entries.pop(int(rng.integers(0, len(entries)))))
        elif op == 4 and entries and int(paged.allocated[slot]) == 0:
            paged.adopt_blocks(
                slot, entries[int(rng.integers(0, len(entries)))])
        elif op == 5 and int(paged.allocated[slot]):
            paged.cow_for_write(slot, [0])
        assert paged.live_blocks + paged.free_block_count == paged.n_blocks
    for s in range(3):
        paged.free_slot(s)
    while entries:
        paged.release_blocks(entries.pop())
    assert paged.free_block_count == paged.n_blocks
    assert paged.live_blocks == 0

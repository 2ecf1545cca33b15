"""The serving runtime's host spans and per-execution counts, read back
through the trace reduction.

A tiny paged dense ``ServingRuntime`` runs a few rounds under
``jax.profiler`` on the CPU; ``bench/program_spans.py`` reads its
``serve/*`` spans with their stats.  The spans nest as
docs/observability.md lists them, and the ``int8_gemms`` each dispatch
carries is the planner's static sum over every layer and the head, not
one scanned layer."""
from __future__ import annotations

import glob

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import program_spans as P
from bench import trace as T

SPEC = "ozimmu_h-3:df32"
SLOTS, MAX_LEN, BLOCK, CHUNK = 3, 32, 8, 4
PROMPTS = (3, 4, 4)
PHASES = ("prepare", "dispatch", "wait", "commit")


def tiny(n_layers: int):
    from repro import configs
    return configs.get_config("internlm2_1_8b", smoke=True,
                              engine_spec=SPEC).with_(n_layers=n_layers)


def planned_decode_gemms(cfg) -> tuple:
    """(one layer, the head) of a decode step over ``SLOTS`` slots: the
    planner's static int8 GEMMs of each contraction, times its batch."""
    from repro.core import plan
    oz = cfg.engine.ozimmu_config
    d, H, KV, hd, f = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                       cfg.d_ff)

    def g(m, n, p, batch=1):
        return batch * plan.plan_contraction(oz, m, n, p,
                                             _record=False).int8_gemms

    layer = (g(SLOTS, d, H * hd) + 2 * g(SLOTS, d, KV * hd)
             + g(H // KV, hd, MAX_LEN, SLOTS * KV)     # scores
             + g(H // KV, MAX_LEN, hd, SLOTS * KV)     # attention output
             + g(SLOTS, H * hd, d)
             + 2 * g(SLOTS, d, f) + g(SLOTS, f, d))
    return layer, g(SLOTS, d, cfg.padded_vocab)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Warm every program, then trace a second batch of requests."""
    from repro.models import api
    from repro.serving import ServingRuntime
    cfg = tiny(2)
    params, _ = api.get_model(cfg).init(jax.random.PRNGKey(0), cfg)
    rt = ServingRuntime(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                        page_block=BLOCK, prefill_chunk=CHUNK)
    rng = np.random.default_rng(0)

    def submit():
        for n in PROMPTS:
            rt.submit(rng.integers(0, cfg.vocab, n).astype(np.int32), 3)

    submit()
    while rt.step():
        pass
    live = []                         # live slots of each decode call
    decode = rt._decode_paged

    def counted(*args):
        live.append(int(np.sum(np.asarray(args[-1]))))
        return decode(*args)

    rt._decode_paged = counted
    submit()
    out = str(tmp_path_factory.mktemp("serve_trace"))
    jax.profiler.start_trace(out)
    try:
        with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
            while rt.step():
                pass
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(sorted(glob.glob(
        f"{out}/plugins/profile/*/*.xplane.pb"))[-1])
    return cfg, rt, P.read_profile(pd, P.window_of(pd)), live


def _within(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


def test_spans_nest_as_documented(served):
    _, _, spans, _ = served
    steps = P.named(spans, "serve/step")
    assert steps
    for e in spans:
        if e.name == "serve/step":
            continue
        parents = [s for s in steps if _within(e, s)]
        assert len(parents) == 1, e
    names = {e.name for e in spans}
    assert names == {"serve/step", "serve/admit"} | {
        f"serve/{kind}/{ph}" for kind in ("prefill", "decode")
        for ph in PHASES}
    # inside one round: admission, then each group's prefill phases in
    # order, then the decode phases in order, never overlapping
    for s in steps:
        kids = sorted((e for e in spans if e is not s and _within(e, s)),
                      key=lambda e: e.start)
        assert kids[0].name == "serve/admit"
        assert all(a.end <= b.start for a, b in zip(kids, kids[1:]))
        decode = [e.name for e in kids if e.name.startswith("serve/dec")]
        assert decode in ([], ["serve/decode/prepare"],
                          [f"serve/decode/{ph}" for ph in PHASES])


def test_decode_dispatch_per_call_with_live_slots(served):
    cfg, rt, spans, live = served
    spans = P.named(spans, "serve/decode/dispatch")
    assert len(spans) == len(live) > 0
    assert [e.arg("slots") for e in spans] == live
    layer, head = planned_decode_gemms(cfg)
    want = cfg.n_layers * layer + head
    assert [e.arg("int8_gemms") for e in spans] == [want] * len(spans)
    assert rt.decode_observed["int8_gemms"] == want
    assert rt.decode_observed["int8_gemms_planned"] == want


def test_prefill_chunk_counts_every_position(served):
    """A chunk of bucket Lb scans the decode body over Lb positions: one
    execution of its program is Lb decode steps' contractions."""
    _, rt, spans, _ = served
    spans = P.named(spans, "serve/prefill/dispatch")
    assert spans
    assert sum(e.arg("tokens") for e in spans) == sum(PROMPTS)
    for e in spans:
        lb = e.arg("bucket")
        per_chunk = rt.observed[("paged", lb)]
        assert per_chunk == {k: lb * v
                             for k, v in rt.decode_observed.items()}
        assert e.arg("int8_gemms") == per_chunk["int8_gemms"]


def test_counts_scale_with_the_layers():
    """Two more layers add exactly two layers' planned int8 GEMMs."""
    from repro.models import api
    from repro.obs import registry

    def traced(n_layers):
        cfg = tiny(n_layers)
        model = api.get_model(cfg)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), cfg)[0])
        cache = jax.eval_shape(
            lambda: model.init_cache(cfg, SLOTS, MAX_LEN))
        reg = registry.MetricsRegistry()
        old = registry.set_registry(reg)
        try:
            jax.make_jaxpr(lambda p, c: model.decode_step(
                p, cfg, c, jnp.zeros((SLOTS, 1), jnp.int32),
                jnp.ones((SLOTS,), jnp.int32)))(params, cache)
        finally:
            registry.set_registry(old)
        return cfg, reg.total("emulation.int8_gemms")

    cfg2, two = traced(2)
    _, four = traced(4)
    layer, head = planned_decode_gemms(cfg2)
    assert two == 2 * layer + head
    assert four - two == 2 * layer


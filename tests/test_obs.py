"""Observability layer (docs/observability.md).

Covers:

* MetricsRegistry semantics: labeled counters/gauges/histograms,
  snapshot / diff / merge / total, the injectable clock, and the
  disabled mode being a true no-op (nothing recorded, `enabled()` gates
  hot sites before any work);
* linear-interpolation percentiles (the `_pct` nearest-rank fix) and
  the new p99/p95 blocks in `ServingMetrics.summary`;
* observed emulation counters == `Plan` cost accounting, exactly, for
  every variant family (full/:fast/:fast2, fixed and auto k) — the
  acceptance invariant: what ran is what the planner priced;
* bitwise identity of instrumented runs: obs on vs off over XLA,
  :fused, rhs_presplit, and (subprocess, 8 forced host devices)
  @mesh/int32 — recording happens host-side at trace time, never in
  the graph;
* the planner audit ledger: one decision row per auto-k resolution
  with the spec, mode, chosen k, predicted eps and cost columns;
* split-cache hit/miss mirroring into the global registry;
* `registry.repeated`: emulation counts recorded inside a scope are
  multiplied, scopes nest, other counters record as before;
* the exporter: the JSON document exposes the `totals` surface the CI
  smoke asserts on;
* host spans: `tracing.span` degrades to a no-op without a profiler.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ozimmu, plan, split_cache
from repro.obs import export, registry
from repro.obs.registry import MetricsRegistry, Snapshot

DN = (((1,), (0,)), ((), ()))


@pytest.fixture()
def fresh_registry():
    """Swap in a clean process-global registry (and restore after)."""
    reg = MetricsRegistry()
    old = registry.set_registry(reg)
    registry.set_enabled(True)
    try:
        yield reg
    finally:
        registry.set_registry(old)
        registry.set_enabled(True)


@pytest.fixture()
def operands():
    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.standard_normal((6, 96)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((96, 10)), jnp.float32)
    return a, b


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_labels_and_totals():
    reg = MetricsRegistry()
    reg.inc("gemms", 3, variant="ozimmu_h", k=4)
    reg.inc("gemms", 2, k=4, variant="ozimmu_h")   # kwarg order irrelevant
    reg.inc("gemms", 7, variant="oz2_h", k=4)
    reg.inc("gemms", 1, variant="oz2_h", k=6)
    assert reg.value("gemms", variant="ozimmu_h", k=4) == 5
    assert reg.total("gemms") == 13
    assert reg.total("gemms", variant="oz2_h") == 8
    assert reg.total("gemms", k=4) == 12
    assert reg.total("absent") == 0


def test_gauge_hist_and_virtual_clock_timer():
    t = [0.0]
    reg = MetricsRegistry(now=lambda: t[0])
    reg.gauge("bytes", 10)
    reg.gauge("bytes", 20)              # gauges overwrite
    assert reg.gauge_value("bytes") == 20
    with reg.timer("phase_s", stage="x"):
        t[0] += 2.5
    reg.observe("phase_s", 0.5, stage="x")
    assert reg.hist_values("phase_s", stage="x") == (2.5, 0.5)
    snap = reg.snapshot()
    assert snap.taken_at == 2.5         # snapshot stamps the clock


def test_snapshot_diff_and_merge():
    reg = MetricsRegistry()
    reg.inc("c", 5, tag="a")
    reg.observe("h", 1.0)
    before = reg.snapshot()
    reg.inc("c", 2, tag="a")
    reg.inc("c", 4, tag="b")
    reg.observe("h", 2.0)
    d = reg.snapshot().diff(before)
    assert d.value("c", tag="a") == 2
    assert d.value("c", tag="b") == 4
    assert d.hist_values("h") == (2.0,)   # histograms diff by suffix
    other = MetricsRegistry()
    other.inc("c", 10, tag="a")
    other.observe("h2", 9.0)
    m = reg.snapshot().merge(other.snapshot())
    assert m.value("c", tag="a") == 17
    assert m.hist_values("h2") == (9.0,)
    assert "h2" in m.names() and "c" in m.names()


def test_disabled_mode_records_nothing(fresh_registry):
    with registry.disabled():
        assert not registry.enabled()
        fresh_registry.inc("c", 5)
        fresh_registry.gauge("g", 1)
        fresh_registry.observe("h", 1.0)
        with fresh_registry.timer("t"):
            pass
        assert fresh_registry.is_empty()
    assert registry.enabled()
    fresh_registry.inc("c", 1)
    assert fresh_registry.value("c") == 1


def test_repeated_scopes_multiply_and_nest(fresh_registry, operands):
    """Emulation counts recorded under ``repeated(n)`` count n times (a
    scan body traced once stands for every step); scopes nest
    multiplicatively.  The planner's mirror of its ledger and plain
    counters record as before: one ``plan.decisions`` per ledger row."""
    a, b = operands
    cfg = ozimmu.parse_spec("ozimmu_h-auto:df32")
    plan.get_ledger().clear()
    ozimmu.ozimmu_dot_general(a, b, DN, cfg)
    first = fresh_registry.snapshot()
    once = first.total("emulation.int8_gemms")
    once_planned = first.total("emulation.int8_gemms_planned")
    assert registry.repetitions() == 1
    with registry.repeated(3):
        with registry.repeated(4):
            assert registry.repetitions() == 12
            ozimmu.ozimmu_dot_general(a, b, DN, cfg)
            fresh_registry.inc("c", 1)
        assert registry.repetitions() == 3
    assert registry.repetitions() == 1
    snap = fresh_registry.snapshot()
    assert snap.total("emulation.int8_gemms") == 13 * once
    assert snap.total("emulation.int8_gemms_planned") == 13 * once_planned
    assert snap.total("emulation.calls") == 13
    assert fresh_registry.value("c") == 1
    assert snap.total("plan.decisions") == len(plan.get_ledger()) == 2


def test_percentile_linear_interpolation():
    assert registry.percentile([1, 2, 3, 4], 0.5) == 2.5
    assert registry.percentile([1, 2, 3, 4], 0.0) == 1.0
    assert registry.percentile([1, 2, 3, 4], 1.0) == 4.0
    assert registry.percentile([7], 0.95) == 7.0
    assert registry.percentile([10, 20], 0.25) == 12.5
    with pytest.raises(ValueError):
        registry.percentile([], 0.5)


def test_serving_metrics_percentile_blocks():
    from repro.serving.metrics import ServingMetrics

    t = [0.0]
    m = ServingMetrics(now=lambda: t[0])
    m.start()
    t[0] = 10.0

    class R:
        arrival = 0.0
        first_token_at = None

    for i, (ttft, lat) in enumerate([(1, 2), (2, 4), (3, 6), (4, 8)]):
        r = R()
        r.arrival, r.first_token_at = 0.0, float(ttft)
        m.record_finish(r, float(lat))
    for d in (1, 2, 3, 4):
        m.sample_queue(d)
    s = m.summary()
    assert s["ttft_s"]["p50"] == 2.5          # linear, not nearest-rank
    assert "p99" in s["ttft_s"] and "p99" in s["latency_s"]
    assert s["queue_depth"]["p95"] == pytest.approx(3.85)
    m.observe_timing("decode_step", 0.25)
    tm = m.summary()["timings_s"]["decode_step"]
    assert tm["count"] == 1 and tm["p99"] == 0.25


# ---------------------------------------------------------------------------
# observed emulation counters == Plan accounting
# ---------------------------------------------------------------------------

# one spec per variant family cell: every split family, both accumulate
# paths, the oz2 full / :fast / :fast2 cost shapes
FIXED_SPECS = [
    "ozimmu-3:f32", "ozimmu_rn-3:f32", "ozimmu_ef-3:df32",
    "ozimmu_h-4:df32", "ozimmu_sm_b-3:f32", "ozimmu_sm_h-4:df32",
    "oz2_b-4:df32", "oz2_h-4:df32:fast", "oz2_b-4:df32:fast2",
]
AUTO_SPECS = ["ozimmu_h-auto:df32", "oz2_h-auto:df32:fast",
              "ozimmu_sm_h-auto:df32:prob"]


@pytest.mark.parametrize("spec", FIXED_SPECS + AUTO_SPECS)
def test_observed_counts_match_plan(spec, fresh_registry, operands):
    a, b = operands
    cfg = ozimmu.parse_spec(spec)
    ozimmu.ozimmu_dot_general(a, b, DN, cfg)
    # expected costs from the SAME accounting the planner prices with
    # (probing the concrete operands exactly like the eager auto-k path)
    pl = plan.plan_contraction(
        cfg if cfg.accum_dtype != "f64" else cfg.with_(accum_dtype="f32"),
        a.shape[0], a.shape[1], b.shape[1], a=a, b=b, _record=False)
    snap = fresh_registry.snapshot()
    assert snap.total("emulation.calls") == 1
    assert snap.total("emulation.int8_gemms") == pl.int8_gemms, spec
    assert snap.total("emulation.highprec_adds") == pl.highprec_adds, spec
    assert snap.total("emulation.int8_gemms", k=pl.k) == pl.int8_gemms
    assert snap.total("emulation.split_bytes") == \
        4 * (a.size + b.size)   # f32 operands, both sides split


@pytest.mark.parametrize("spec", ["ozimmu_h-auto:df32:fused",
                                  "ozimmu_sm_h-4:df32:fused"])
def test_kernel_operand_copy_bytes(spec, fresh_registry, operands):
    """The group-GEMM kernel reads int8 slice pairs in place: no digit
    copies are made for it; sign-magnitude digits cost only their bf16
    decode.  The int8 GEMM count stays the plan's."""
    a, b = operands
    cfg = ozimmu.parse_spec(spec)
    ozimmu.ozimmu_dot_general(a, b, DN, cfg)
    pl = plan.plan_contraction(cfg, a.shape[0], a.shape[1], b.shape[1],
                               a=a, b=b, _record=False)
    snap = fresh_registry.snapshot()
    assert "emulation.kernel_operand_copy_bytes" in snap.names()
    decode = 2 * pl.k * (a.size + b.size) if cfg.split == "sm" else 0
    assert snap.total("emulation.kernel_operand_copy_bytes") == decode
    assert snap.total("emulation.int8_gemms") == pl.int8_gemms


@pytest.mark.parametrize("spec", AUTO_SPECS)
def test_planned_counts_match_jitted(spec, fresh_registry, operands):
    """In a jitted program the static plan of each contraction is what
    runs, with the rhs split in-call or frozen in the split cache."""
    a, b = operands
    cfg = ozimmu.parse_spec(spec)
    jax.jit(lambda x, y: ozimmu.ozimmu_dot_general(x, y, DN, cfg))(a, b)
    sp = split_cache.SplitCache().get(b, DN, cfg)
    jax.jit(lambda x, s: ozimmu.ozimmu_dot_general(
        x, b, DN, cfg, rhs_presplit=s))(a, sp)
    snap = fresh_registry.snapshot()
    for presplit in (0, 1):
        ran = snap.total("emulation.int8_gemms", presplit=presplit)
        assert ran > 0, (spec, presplit)
        assert snap.total("emulation.int8_gemms_planned",
                          presplit=presplit) == ran, (spec, presplit)


def test_observed_counts_batched_and_presplit(fresh_registry):
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.standard_normal((3, 5, 64)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((3, 64, 7)), jnp.float32)
    dn = (((2,), (1,)), ((0,), (0,)))
    cfg = ozimmu.parse_spec("ozimmu_h-4:df32")
    pl = plan.plan_contraction(cfg, 5, 64, 7, _record=False)
    ozimmu.ozimmu_dot_general(a, b, dn, cfg)
    snap = fresh_registry.snapshot()
    assert snap.total("emulation.int8_gemms") == 3 * pl.int8_gemms
    sp = split_cache.SplitCache().get(b, dn, cfg)
    before = fresh_registry.snapshot()
    ozimmu.ozimmu_dot_general(a, b, dn, cfg, rhs_presplit=sp)
    d = fresh_registry.snapshot().diff(before)
    assert d.total("emulation.int8_gemms", presplit=1) == \
        3 * pl.int8_gemms
    # the frozen rhs skips the B-side splitter: only A bytes recorded
    assert d.total("emulation.split_bytes") == 4 * a.size


def test_mesh_label_none_when_sharded_path_falls_back(fresh_registry,
                                                      operands):
    """A mesh-native spec without an installed mesh runs the local path,
    and its counters say so (mesh="none")."""
    a, b = operands
    ozimmu.ozimmu_dot_general(a, b, DN,
                              ozimmu.parse_spec("ozimmu_h-4:df32@model"))
    snap = fresh_registry.snapshot()
    assert snap.total("emulation.int8_gemms", mesh="none") > 0
    assert snap.total("emulation.int8_gemms", mesh="model") == 0


def test_trace_time_recording_once_per_compile(fresh_registry, operands):
    """Counters record at trace time: a jitted call records once at
    compile, and compiled replays add nothing (each replay executes the
    same contractions — the per-execution count IS the traced count)."""
    a, b = operands
    cfg = ozimmu.parse_spec("ozimmu_h-4:df32")
    fn = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(a, b, DN, cfg))
    fn(a, b).block_until_ready()
    once = fresh_registry.total("emulation.int8_gemms")
    assert once == plan.plan_contraction(
        cfg, a.shape[0], a.shape[1], b.shape[1], _record=False).int8_gemms
    fn(a, b).block_until_ready()
    assert fresh_registry.total("emulation.int8_gemms") == once


# ---------------------------------------------------------------------------
# bitwise identity: obs on vs off
# ---------------------------------------------------------------------------

BITWISE_SPECS = ["ozimmu_h-4:df32", "ozimmu_sm_h-4:df32",
                 "oz2_h-4:df32:fast", "oz2_b-4:df32:fast2",
                 "ozimmu_h-4:df32:fused", "oz2_h-auto:df32:fast:fused"]


@pytest.mark.parametrize("spec", BITWISE_SPECS)
def test_bitwise_identity_obs_on_off(spec, fresh_registry, operands):
    a, b = operands
    cfg = ozimmu.parse_spec(spec)
    sp = split_cache.SplitCache().get(b, DN, cfg)
    on = ozimmu.ozimmu_dot_general(a, b, DN, cfg)
    on_jit = jax.jit(
        lambda a, b: ozimmu.ozimmu_dot_general(a, b, DN, cfg))(a, b)
    on_pre = ozimmu.ozimmu_dot_general(a, b, DN, cfg, rhs_presplit=sp)
    assert not fresh_registry.is_empty()
    with registry.disabled():
        off = ozimmu.ozimmu_dot_general(a, b, DN, cfg)
        off_jit = jax.jit(
            lambda a, b: ozimmu.ozimmu_dot_general(a, b, DN, cfg))(a, b)
        off_pre = ozimmu.ozimmu_dot_general(a, b, DN, cfg,
                                            rhs_presplit=sp)
    np.testing.assert_array_equal(np.asarray(on), np.asarray(off))
    np.testing.assert_array_equal(np.asarray(on_jit), np.asarray(off_jit))
    np.testing.assert_array_equal(np.asarray(on_pre), np.asarray(off_pre))


def test_bitwise_identity_mesh_int32_obs_on_off():
    """@mesh/int32 in a subprocess with 8 forced host devices: the
    sharded path's outputs are bitwise-identical with obs on vs off
    (named scopes are metadata; counters are host-side)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = src
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ozimmu
        from repro.obs import registry
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(11)
        a = jnp.asarray(rng.standard_normal((8, 256)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((256, 16)), jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        cfg = ozimmu.parse_spec("ozimmu_h-4:df32@model/int32")
        mesh = make_test_mesh(data=1, model=8)
        with set_mesh(mesh):
            on = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                a, b, dn, cfg))(a, b)
            assert registry.get_registry().total(
                "emulation.int8_gemms", mesh="model") == 10
            with registry.disabled():
                off = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                    a, b, dn, cfg))(a, b)
        assert bool(jnp.all(on == off))
        print("OK")
    """)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    assert "OK" in p.stdout


def test_product_reduce_bytes_sharded_int32_only(fresh_registry, operands):
    """``emulation.product_reduce_bytes`` is what one device sends into the
    exact int32 reduction of the G group products: (D-1)/D x G x m x p x 4
    under the reduce-scatter (rows divide the axis), twice that under the
    psum (they do not); 0 on one device and under ``/df32``, which reduces
    accumulators, not products (subprocess, 4 forced host devices)."""
    a, b = operands
    ozimmu.ozimmu_dot_general(a, b, DN, ozimmu.parse_spec("ozimmu_h-4:df32"))
    assert "emulation.product_reduce_bytes" in fresh_registry.snapshot().names()
    assert fresh_registry.total("emulation.product_reduce_bytes") == 0
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = src
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ozimmu, plan
        from repro.obs import registry
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(11)
        dn = (((1,), (0,)), ((), ()))
        mesh = make_test_mesh(data=1, model=4)
        reg = registry.get_registry()
        for spec, m, per_product in (
                ("ozimmu_h-4:df32@model/int32", 8, 3 / 4),
                ("ozimmu_h-4:df32@model/int32", 6, 2 * 3 / 4),
                ("oz2_h-4:df32:fast@model/int32", 8, 3 / 4),
                ("ozimmu_h-4:df32@model/df32", 8, 0)):
            a = jnp.asarray(rng.standard_normal((m, 256)), jnp.float32)
            b = jnp.asarray(rng.standard_normal((256, 16)), jnp.float32)
            cfg = ozimmu.parse_spec(spec)
            before = reg.total("emulation.product_reduce_bytes")
            with set_mesh(mesh):
                jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                    a, b, dn, cfg))(a, b)
            sent = reg.total("emulation.product_reduce_bytes") - before
            pl = plan.plan_contraction(cfg, m, 256, 16, _record=False)
            # oz2 reduces each anti-diagonal group g = 2..k+1 before its
            # ladder fold: k = 4 groups (r covers every group at n = 256)
            groups = 4 if cfg.accumulate == "oz2" else pl.highprec_adds
            assert sent == per_product * groups * m * 16 * 4, (spec, m, sent)
            print(spec, m, sent)
        print("OK")
    """)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    assert "OK" in p.stdout


# ---------------------------------------------------------------------------
# planner audit ledger
# ---------------------------------------------------------------------------

def test_plan_ledger_records_auto_k(fresh_registry, operands):
    a, b = operands
    led = plan.get_ledger()
    led.clear()
    ozimmu.ozimmu_dot_general(a, b, DN,
                              ozimmu.parse_spec("ozimmu_h-auto:df32"))
    ozimmu.ozimmu_dot_general(
        a, b, DN, ozimmu.parse_spec("oz2_h-auto:df32:fast:prob"))
    entries = led.entries()
    assert len(entries) == 2
    det, prob = entries
    assert det.mode == "deterministic" and det.probed
    assert prob.mode == "probabilistic" and ":prob" in prob.spec
    for e in entries:
        assert e.k >= 1 and e.int8_gemms > 0 and e.predicted_eps > 0
        assert e.m == a.shape[0] and e.n == a.shape[1]
        assert set(e.as_dict()) >= {"spec", "k", "predicted_eps",
                                    "int8_gemms", "highprec_adds"}
    summ = led.summary()
    assert summ["decisions"] == 2 and summ["probabilistic"] == 1
    assert summ["k_hist"] and summ["worst_predicted_eps"] > 0
    assert "auto-k decisions" in led.describe()
    # fixed-k contractions plan statically and leave no ledger rows
    led.clear()
    ozimmu.ozimmu_dot_general(a, b, DN,
                              ozimmu.parse_spec("ozimmu_h-4:df32"))
    assert len(led) == 0


def test_ledger_disabled_with_obs(fresh_registry, operands):
    a, b = operands
    led = plan.get_ledger()
    led.clear()
    with registry.disabled():
        ozimmu.ozimmu_dot_general(
            a, b, DN, ozimmu.parse_spec("ozimmu_h-auto:df32"))
    assert len(led) == 0


# ---------------------------------------------------------------------------
# split-cache mirroring
# ---------------------------------------------------------------------------

def test_split_cache_obs_counters(fresh_registry, operands):
    _, b = operands
    cfg = ozimmu.parse_spec("ozimmu_h-4:df32")
    cache = split_cache.SplitCache()
    cache.get(b, DN, cfg)
    cache.get(b, DN, cfg)
    snap = fresh_registry.snapshot()
    assert snap.total("split_cache.misses") == 1
    assert snap.total("split_cache.hits") == 1
    assert snap.total("split_cache.hit_bytes") == 4 * b.size
    assert snap.gauge("split_cache.cached_bytes") > 0


# ---------------------------------------------------------------------------
# exporter
# ---------------------------------------------------------------------------

def test_json_document_totals_and_ledger(fresh_registry, operands):
    a, b = operands
    plan.get_ledger().clear()
    ozimmu.ozimmu_dot_general(a, b, DN,
                              ozimmu.parse_spec("ozimmu_h-auto:df32"))
    extra_reg = MetricsRegistry()
    extra_reg.inc("serving.tokens_generated", 12)
    snap = export.unified_snapshot(extra_reg)
    doc = json.loads(export.to_json(snap, extra={"serving_summary": {}}))
    assert doc["totals"]["emulation.int8_gemms"] > 0
    assert doc["totals"]["serving.tokens_generated"] == 12
    assert doc["plan_ledger"]["decisions"] >= 1
    assert "serving_summary" in doc


# ---------------------------------------------------------------------------
# run-level profiler capture
# ---------------------------------------------------------------------------

def test_profile_raises_when_trace_cannot_start(monkeypatch, tmp_path):
    """A trace that was asked for and cannot start is an error, not a
    warning; without a directory ``profile`` is a passthrough."""
    from repro.obs import tracing

    def refuse(*a, **k):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with tracing.profile(str(tmp_path)):
            pass
    ran = []
    with tracing.profile(None):
        ran.append(1)
    assert ran == [1]


def test_profile_raises_when_trace_cannot_stop(monkeypatch, tmp_path):
    from repro.obs import tracing
    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: started.append(d))

    def refuse():
        raise RuntimeError("stop failed")

    monkeypatch.setattr(jax.profiler, "stop_trace", refuse)
    with pytest.raises(RuntimeError, match="stop failed"):
        with tracing.profile(str(tmp_path)):
            pass
    assert started == [str(tmp_path)]


def test_host_scope_degrades_without_profiler(monkeypatch):
    from repro.obs import tracing

    def refuse(name, **counts):
        raise RuntimeError("no profiler backend")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    with tracing.span("serve/step", slots=2):
        pass

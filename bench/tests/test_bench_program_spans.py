"""The readers of the program's own spans (``bench/program_spans.py``):
on a hand-built timeline, on a serve trace recorded on a TPU v5e
(``data/tpu_serve_tiny.xplane.pb``: one decode round of a two-layer
d = 256 model served through the serve driver, written by
``data/record_serve_trace.py``), and beside the accepted metrics, which
read on the recorded GEMM trace what they read before these readers
existed."""
from __future__ import annotations

import dataclasses
import shutil
import sys
from pathlib import Path

import pytest

from bench import harness as H
from bench import program_spans as P
from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
NEW = ("sched_host_ms", "decode_dispatch_ms", "device_idle_runtime.serve",
       "int8_gemms_per_decode_step")


def _view(red, record=None):
    return T.View(trace=red, record=record or {},
                  peaks=H.load_peaks("TPU v5 lite"), config={}, traffic={},
                  chips=1)


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    """Place a recorded trace where ``bench/run.py`` leaves a run's trace
    (a ``bench_trace_*`` directory of the temporary directory) and
    return its reduction."""
    def place(name):
        d = tmp_path / "bench_trace_0" / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        shutil.copy(DATA / name, d / "host.xplane.pb")
        monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
        monkeypatch.setattr(P, "_READ", {})
        return T.reduce_file(str(d / "host.xplane.pb"))
    return place


def _round(start, dispatch, wait, commit, end, **args):
    """A ``serve/step`` with admission, no prefill and the four decode
    phases: [start, start+1) admit, then prepare up to ``dispatch``."""
    return [P.Span("serve/step", start, end),
            P.Span("serve/admit", start, start + 1),
            P.Span("serve/decode/prepare", start + 1, dispatch),
            P.Span("serve/decode/dispatch", dispatch, wait,
                   tuple(args.items())),
            P.Span("serve/decode/wait", wait, commit),
            P.Span("serve/decode/commit", commit, end)]


def _op(name, s, e):
    return T.Event(name, s, e, 0, f"%{name} = s32[8] fusion(...)")


def test_readers_on_a_hand_built_timeline(monkeypatch):
    red = T.Reduced(window=(0.0, 100.0), devices=[0],
                    ops=[_op("fusion.1", 0, 30), _op("fusion.2", 50, 70),
                         _op("fusion.3", 90, 100)],
                    modules=[], spans=[T.Event("bench/traced", 0, 100)])
    spans = _round(20, 23, 35, 55, 60, slots=3) \
        + _round(72, 75, 80, 92, 95, slots=2, int8_gemms=100)
    monkeypatch.setattr(P, "spans_of", lambda view: spans)

    def read(name):
        return H.load_metric_reader(name)(_view(red))

    # admit + prepare + commit: (1 + 2 + 5) + (1 + 2 + 3) ns, 2 rounds
    assert read("sched_host_ms") == pytest.approx(7e-6)
    assert read("decode_dispatch_ms") == pytest.approx(8.5e-6)  # 12, 5 ns
    # idle 30-50 and 70-90; the rounds are open 20-60 and 72-95
    assert read("device_idle.serve") == pytest.approx(40.0)
    assert read("device_idle_runtime.serve") == pytest.approx(38.0)
    # only a dispatch that carries a count is averaged
    assert read("int8_gemms_per_decode_step") == 100


def test_readers_on_a_recorded_serve_trace(traced):
    red = traced("tpu_serve_tiny.xplane.pb")
    view = _view(red)
    spans = P.spans_of(view)
    lo, hi = red.window
    assert spans and all(lo <= sp.start <= sp.end <= hi for sp in spans)
    steps = P.named(spans, "serve/step")
    dispatch = P.named(spans, "serve/decode/dispatch")
    assert len(steps) == len(dispatch) == \
        len(red.modules_named("jit__decode"))
    assert {sp.arg("slots") for sp in dispatch} == {4}
    values = {n: H.load_metric_reader(n)(view) for n in NEW}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert values["int8_gemms_per_decode_step"] == dispatch[0].arg(
        "int8_gemms")
    # the round's share of the idle time is part of the whole
    assert values["device_idle_runtime.serve"] <= \
        H.load_metric_reader("device_idle.serve")(view)
    # one wait and one dispatch span a round, inside it
    for name in ("serve/decode/dispatch", "serve/decode/wait"):
        assert len(P.named(spans, name)) == len(steps)


# What the accepted reduction and readers read on the recorded GEMM
# trace, before the program's span readers existed, with this record
# (made up around the trace: 126 calls of 512^3; the numbers only have
# to stay put).
GEMM512_RECORD = {"shape": [512, 512, 512], "calls": 126,
                  "flops": 2 * 512 ** 3 * 126, "int8_gemms_per_call": 21}
ACCEPTED_ON_GEMM512 = {
    "gemm_mfu": 0.17030659099109585,
    "int8_gemms_per_call": 21,
    "group_gemm_roofline": 11.971124059537,
    "scale_accum_roofline": 182.10114867930943,
    "device_idle.gemm": 44.939849689079615,
    "serve_mfu": None,
    "decode_step_ms": None,
    "device_idle.serve": 44.939849689079615,
    "decode_occupancy": None,
}


def test_accepted_metrics_read_as_before(traced):
    red = traced("tpu_gemm512.xplane.pb")
    view = _view(red, GEMM512_RECORD)
    names = [m["name"] for m in H.load_benchmark()["per_layer"]]
    # the accepted metrics come first; later entries may follow the new
    assert names[:len(ACCEPTED_ON_GEMM512)] == list(ACCEPTED_ON_GEMM512)
    assert set(NEW) <= set(names)
    # the new readers first: they read the same file and find no span
    for name in NEW:
        assert H.load_metric_reader(name)(view) is None, name
    for name, want in ACCEPTED_ON_GEMM512.items():
        assert H.load_metric_reader(name)(view) == want, name


def test_no_trace_file_reads_nothing(tmp_path, monkeypatch):
    """Where the run's trace cannot be found the readers read nothing."""
    monkeypatch.setattr("tempfile.tempdir", str(tmp_path))
    monkeypatch.setattr(P, "_READ", {})
    red = T.reduce_file(str(DATA / "tpu_serve_tiny.xplane.pb"))
    for name in NEW:
        assert H.load_metric_reader(name)(_view(red)) is None, name


def test_idle_during_counts_overlapping_spans_once():
    """Idle time under spans that overlap, or repeat, counts once."""
    red = T.Reduced(window=(0.0, 100.0), devices=[0],
                    ops=[_op("fusion.1", 10, 20), _op("fusion.2", 60, 70)],
                    modules=[], spans=[])
    spans = [P.Span("serve/step", 0, 50), P.Span("serve/step", 0, 50),
             P.Span("serve/step", 30, 80)]
    # idle 0-10, 20-60 and 70-100; the spans are open 0-80
    assert P.idle_during(red, spans) == pytest.approx((10 + 40 + 10) * 1e-9)
    assert P.idle_during(red, []) == 0.0
    assert P.idle_during(dataclasses.replace(red, devices=[]), spans) == 0.0


def test_recorder_drops_only_the_metadata_plane():
    sys.path.insert(0, str(DATA))
    try:
        import record_serve_trace as R
    finally:
        sys.path.remove(str(DATA))
    from jax.profiler import ProfileData
    raw = (DATA / "tpu_gemm512.xplane.pb").read_bytes()
    cut = R.drop_plane(raw, R.DROPPED_PLANE)
    assert len(cut) < len(raw)
    planes = [p.name for p in ProfileData.from_serialized_xspace(raw).planes]
    kept = [p.name for p in ProfileData.from_serialized_xspace(cut).planes]
    assert kept == [n for n in planes if n != "/host:metadata"]
    assert R.drop_plane(cut, R.DROPPED_PLANE) == cut
    a = T.reduce_profile(ProfileData.from_serialized_xspace(raw))
    b = T.reduce_profile(ProfileData.from_serialized_xspace(cut))
    assert (a.window, a.ops, a.modules, a.spans) == \
        (b.window, b.ops, b.modules, b.spans)

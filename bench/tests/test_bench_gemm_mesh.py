"""The four-chip GEMM cell without a chip: the reduction's bytes and
bandwidth share by hand, its two trace readers on hand-built timelines,
discovery of the cell's pieces by name, and whole runs of
``bench/run.py`` on four virtual CPU devices at a small size, sound and
with the cross-device sum broken."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import collective_cost as C
from bench import harness as H
from bench import trace as T

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "dgemm_model4.square32768"
ICI = C.ICI_BYTES_PER_S["TPU v5 lite"]


def test_reduce_scatter_bytes_by_hand():
    # 3 groups of a (4 x 5) int32 product over 2 chips: each chip sends
    # the other's half of every product
    assert C.reduce_scatter_bytes_sent(4, 5, 3, 2) == 3 * 10 * 4
    # 8 groups at 32768^3 over 4 chips: 3/4 of 8 x 4.29 GB
    assert C.reduce_scatter_bytes_sent(32768, 32768, 8, 4) == \
        25_769_803_776
    assert C.reduce_scatter_bytes_sent(4, 5, 3, 1) == 0


def test_link_share_is_100_at_bytes_over_bandwidth():
    assert ICI == 200e9             # 1,600 Gbit/s
    assert C.link_share(2e9, 2e9 / ICI, ICI) == pytest.approx(100.0)
    assert C.link_share(1e9, 2e9 / ICI, ICI) == pytest.approx(50.0)
    assert C.link_share(1e9, 0.0, ICI) is None


def _op(name, s, e, dev, opcode, dtype="s32", operand="%x"):
    text = f"%{name} = {dtype}[64,8]{{1,0}} {opcode}({operand}), x=1"
    return T.Event(name, s, e, dev, text)


def _gemm(name, s, e, dev):
    text = (f'%{name} = s32[64,8]{{1,0}} custom-call(%a, %b), '
            f'custom_call_target="tpu_custom_call"')
    return T.Event(name, s, e, dev, text)


def _view(ops, devices=(0, 1), calls=2, window=(0.0, 100.0)):
    red = T.Reduced(window=window, devices=list(devices), ops=ops,
                    modules=[], spans=[])
    record = {"calls": calls, "groups_per_call": 3,
              "device_kind": "TPU v5 lite"}
    return T.View(trace=red, record=record, peaks={}, config={},
                  traffic={"m": 64, "p": 8}, chips=len(devices))


def _reader(name):
    return H.load_metric_reader(name)


def test_exposed_share_with_and_without_overlap():
    ops = [
        # device 0: the collective 10-30 overlaps a GEMM 20-40 for 10 ns
        _op("all-reduce.1", 10, 30, 0, "all-reduce"),
        _gemm("group_gemm.2", 20, 40, 0),
        # a float32 collective (the digit grids' pmax) is not the reduction
        _op("all-reduce.3", 50, 60, 0, "all-reduce", dtype="f32"),
        # device 1: the collective 10-30 runs alone; an async pair runs
        # from its start (60) to the end of its done (80), a GEMM 65-70
        # inside it
        _op("all-reduce.1", 10, 30, 1, "all-reduce"),
        _op("all-reduce-start.4", 60, 61, 1, "all-reduce-start"),
        _gemm("group_gemm.5", 65, 70, 1),
        _op("all-reduce-done.4", 75, 80, 1, "all-reduce-done",
            operand="%all-reduce-start.4"),
    ]
    per_device = C.reduction_intervals(_view(ops).trace)
    assert per_device == {0: [(10, 30)], 1: [(10, 30), (60, 80)]}
    # device 0: 10 of 20 ns exposed; device 1: 20 + 15 ns
    assert _reader("product_reduce_exposed")(_view(ops)) == \
        pytest.approx(100.0 * (10 + 35) / 2 / 100)
    assert _reader("product_reduce_exposed")(
        _view([_gemm("group_gemm.2", 0, 50, 0)])) is None


def test_roofline_is_100_when_time_is_bytes_over_bandwidth():
    view = _view([])
    sent = C.reduce_scatter_bytes_sent(64, 8, 3, 2)
    seconds = 2 * sent * view.record["calls"] / ICI   # sent and received
    ns = seconds * 1e9
    ops = [_op("all-reduce.1", 0, ns / 2, d, "all-reduce") for d in (0, 1)] \
        + [_op("all-reduce.2", ns / 2, ns, d, "all-reduce") for d in (0, 1)]
    view = _view(ops, window=(0.0, 2 * ns))
    assert _reader("product_reduce_roofline")(view) == pytest.approx(100.0)
    slow = _view([_op("all-reduce.1", 0, 2 * ns, d, "all-reduce")
                  for d in (0, 1)], window=(0.0, 2 * ns))
    assert _reader("product_reduce_roofline")(slow) == pytest.approx(50.0)
    assert _reader("product_reduce_roofline")(_view([])) is None


def test_bytes_per_call_reads_the_record():
    view = _view([])
    assert _reader("product_reduce_bytes_per_call")(view) is None
    view.record["product_reduce_bytes_per_call"] = 1536.0
    assert _reader("product_reduce_bytes_per_call")(view) == 1536.0


def test_cell_pieces_load_by_name():
    bench = H.load_benchmark()
    cell = H.find_cell(bench, CELL)
    assert cell["chips"] == 4
    config = H.load_config(cell["config"])
    traffic = H.load_traffic(cell["traffic"])
    driver = H.load_driver(config["driver"])
    assert config["driver"] == "gemm_mesh" and config["reduced"] == []
    assert config["spec"].endswith("@model/int32")
    assert traffic["kind"] in driver.TRAFFIC_KINDS
    assert (traffic["m"], traffic["n"], traffic["p"]) == (32768,) * 3
    assert set(driver.CONTROLS) == {"f32_highest", "f32_high"}
    e2e = {m["name"] for m in H.cell_metrics(bench, CELL, "end_to_end")}
    assert e2e == {"gemm_tflops", "setup_s"}
    per_layer = {m["name"] for m in H.cell_metrics(bench, CELL, "per_layer")}
    assert per_layer == {"gemm_mfu", "int8_gemms_per_call",
                         "group_gemm_roofline", "device_idle.gemm",
                         "product_reduce_roofline", "product_reduce_exposed",
                         "product_reduce_bytes_per_call"}


RUN = textwrap.dedent("""
    import json, sys
    sys.path[:0] = [{root!r}, {src!r}]
    import jax
    from bench import harness as H
    from bench import run as bench_run
    load_config, load_traffic = H.load_config, H.load_traffic

    def config(name, *a):
        cfg = load_config(name, *a)
        cfg["check"]["entries"] = 256
        cfg["check"]["sharded_mismatch"].update(rows=64, cols=128)
        return cfg

    H.load_config = config
    H.load_traffic = lambda name, *a: dict(load_traffic(name, *a), m=256,
                                           n=512, p=512)
    H.require_chips = lambda jax_, n: jax.devices()[:n]
    peaks = H.load_peaks
    H.load_peaks = lambda kind: peaks("TPU v5 lite")
    import repro.launch.entry as entry
    entry.prepare_entry_point = lambda: "unused"
    if {broken!r}:
        from repro.distributed import collectives
        exact = collectives.psum_exact_int32
        collectives.psum_exact_int32 = \\
            lambda q, axis, scatter_dimension=None: exact(
                q, axis, scatter_dimension) + 1
    assert bench_run.main(["--workload", {cell!r}, "--seed",
                           str(2 ** 33 + 7), "--seconds", "0.5",
                           "--trace", "0"]) == 0
""")


@pytest.mark.parametrize("broken", [False, True], ids=["sound", "broken"])
def test_run_on_four_cpu_devices(broken):
    """The cell's whole run on four virtual CPU devices at 256x512x512
    (four 128-column blocks): correct as it stands; with 1 added to every
    reduced int32 product, both checks fail."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = RUN.format(root=ROOT, src=os.path.join(ROOT, "src"),
                      broken=broken, cell=CELL)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=600, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    checks = {c["name"]: c for c in out["checks"]}
    assert out["device"]["count"] == 4
    assert out["correct"] is (not broken)
    assert checks["sharded_mismatch"]["ok"] is (not broken)
    assert checks["gemm_err_excess"]["ok"] is (not broken)
    if not broken:
        assert checks["sharded_mismatch"]["value"] == 0

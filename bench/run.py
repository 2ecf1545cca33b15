"""Run one benchmark cell once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, its driver and its metric
readers are all found by name from ``BENCHMARK.json`` (see
``bench/harness.py``).  The run sets up (weights or operands made on the
device from ``--seed``, every program the cell uses compiled or loaded
from the persistent cache and warmed), measures for ``--seconds``, reads
the peak device memory, and then checks the window's own output against
the plain reference in ``bench/reference/``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of standard output is the result: one JSON object
whose last key, ``checks``, holds each compared number beside its limit;
the same numbers are the last lines of standard error.  No TPU, fewer
chips than the cell asks for, or a compile inside the window: the run
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
# the bench package and the system under test; the script's own directory
# leaves the path so that bench/trace.py never shadows the stdlib module
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH_DIR]
sys.path[:0] = [str(BENCH_DIR.parent), str(BENCH_DIR.parent / "src")]

from bench import harness as H  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def per_layer_values(run: "H.Run", rec: dict, entries) -> dict:
    from bench import trace as T
    files = glob.glob(f"{run.trace_dir}/plugins/profile/*/*.xplane.pb")
    if not files:
        raise H.BenchError(f"no trace was written under {run.trace_dir}")
    red = T.reduce_file(sorted(files)[-1])
    view = T.View(trace=red, record=rec, peaks=run.peaks,
                  config=run.config, traffic=run.traffic,
                  chips=len(run.devices))
    out = {}
    for m in entries:
        value = H.load_metric_reader(m["name"])(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out, red


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = H.load_benchmark()
    cell = H.find_cell(bench, args.workload)
    config = H.load_config(cell["config"])
    traffic = H.load_traffic(cell["traffic"])
    driver = H.load_driver(config["driver"])
    if traffic["kind"] not in driver.TRAFFIC_KINDS:
        raise H.BenchError(f"driver {config['driver']!r} cannot generate "
                           f"traffic of kind {traffic['kind']!r}")
    e2e = H.cell_metrics(bench, cell["name"], "end_to_end")
    per_layer = H.cell_metrics(bench, cell["name"], "per_layer")

    # the system under test's own process set-up, before JAX starts a
    # backend: exact bf16 rounding and the checkout's compile cache
    from repro.launch.entry import prepare_entry_point
    cache_dir = prepare_entry_point()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = H.require_chips(jax, cell["chips"])
    peaks = H.load_peaks(devices[0].device_kind)
    run = H.Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
                seconds=args.seconds, trace=bool(args.trace),
                devices=devices, peaks=peaks)
    jax.monitoring.register_event_duration_secs_listener(
        H.on_compile_event(run))
    H.log(f"{cell['name']}: device {devices[0].device_kind} x"
          f"{len(devices)}, compile cache {cache_dir}")
    tmp = None
    if run.trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        run.trace_dir = tmp
    try:
        state = driver.setup(run)
        # set-up's objects live for the whole run: collect once and move
        # them out of the collector's reach, so that no collection pass
        # over them lands inside the window
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - T_START
        compiles0 = run.compile_count()
        rec = driver.window(state, run)
        in_window = run.compile_count() - compiles0
        if in_window:
            raise H.BenchError(f"{in_window} programs compiled inside the "
                               f"measured window")
        peak = H.memory_peak_bytes(devices)
        print(f"[bench] setup_s {setup_s:.3f}, compiles before the window "
              f"{compiles0} ({sum(run.compiles):.1f} s), peak_bytes_in_use "
              f"{peak}", flush=True)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        breakdown = None
        if run.trace:
            metrics, red = per_layer_values(run, rec, per_layer)
            device["busy_s"] = red.busy_s()
            device["window_s"] = red.window_s()
            breakdown = red.breakdown()
        else:
            metrics = {}
            for m in e2e:
                value = setup_s if m["name"] == "setup_s" \
                    else rec["e2e"].get(m["name"])
                if value is None:
                    raise H.BenchError(f"the window gave no {m['name']}")
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        gc.collect()
        t_check = time.perf_counter()
        correct, checks = driver.check(state, run, rec)
        H.log(f"check {time.perf_counter() - t_check:.1f} s, whole run "
              f"{time.perf_counter() - T_START:.1f} s")
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    for c in checks:
        print(f"[check] {c['name']} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAILED'}", file=sys.stderr,
              flush=True)
    print(H.result_line(correct=correct, attempted=rec["attempted"],
                        failed=rec["failed"], metrics=metrics,
                        device=device, checks=checks,
                        breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except H.BenchError as e:
        print(f"[bench] error: {e}", file=sys.stderr, flush=True)
        sys.exit(2)

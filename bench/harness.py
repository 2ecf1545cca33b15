"""The benchmark harness: finds a cell's pieces by name and runs it once.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the configuration; its ``driver`` key
  names the window driver;
* ``bench/drivers/<driver>.py``: one module per kind of window, exposing
  ``setup(run)``, ``window(state, run)`` and ``check(state, run)``;
* ``bench/traffic/<traffic>.json``: the parameters that the driver's
  general generator reads;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, a
  function ``read(view)`` that returns a number or None.

So a later change adds a configuration, a traffic mix or a metric by
adding files and entries, without editing a file that exists.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(Exception):
    """A run that cannot produce a result (no chip, a missing file, a
    compile inside the window): it exits non-zero and prints no result."""


# ---------------------------------------------------------------------------
# discovery by name
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Any:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchError(f"missing file {path}") from None


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    names = [c["name"] for c in bench["workloads"]]
    raise BenchError(f"unknown workload {workload!r}; cells: {names}")


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return load_json(bench_dir / "configs" / f"{name}.json")


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return load_json(bench_dir / "traffic" / f"{name}.json")


def _load_module(path: Path, label: str):
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    if label in sys.modules:
        return sys.modules[label]
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[label] = mod
    spec.loader.exec_module(mod)
    return mod


def load_driver(name: str, bench_dir: Path = BENCH_DIR):
    if bench_dir == BENCH_DIR:
        if not (bench_dir / "drivers" / f"{name}.py").is_file():
            raise BenchError(f"missing file {bench_dir}/drivers/{name}.py")
        return importlib.import_module(f"bench.drivers.{name}")
    return _load_module(bench_dir / "drivers" / f"{name}.py",
                        f"bench_driver_{name}")


def load_metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    label = "bench_metric_" + name.replace(".", "_").replace("-", "_")
    return _load_module(bench_dir / "metrics" / f"{name}.py", label).read


def cell_metrics(bench: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports: those
    that list it under ``workloads``, or that list no workloads."""
    out = []
    e2e_here = {m["name"] for m in cell_metrics(bench, cell, "end_to_end")} \
        if kind == "per_layer" else None
    for m in bench[kind]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e_here:
            out.append(m)
    return out


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def load_peaks(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = load_json(bench_dir / "peaks.json")
    if kind not in table["devices"]:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json "
                         f"({sorted(table['devices'])}); no default peaks")
    return table["devices"][kind]


def require_chips(jax, chips: int):
    """The devices a cell runs on: TPU only, at least ``chips`` of them."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's devices are {devs[0].platform!r}; "
                         f"the benchmark never runs on another backend")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a driver gets: the cell's pieces and the run's arguments."""
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    peaks: dict
    trace_dir: Optional[str] = None
    compiles: List[float] = dataclasses.field(default_factory=list)

    def span(self, name: str):
        """A host span around a call into one layer, written into the
        profiler's trace (where one runs) as ``bench/<name>``."""
        import jax
        return jax.profiler.TraceAnnotation(f"bench/{name}")

    def compile_count(self) -> int:
        return len(self.compiles)

    @contextlib.contextmanager
    def traced_span(self):
        """The traced stretch of a ``--trace 1`` run (a no-op otherwise);
        a driver wraps part of its window in it."""
        if not self.trace:
            yield
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # no per-call Python events
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with self.span("traced"):
                yield
        finally:
            jax.profiler.stop_trace()


def on_compile_event(run: Run):
    def listener(event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            run.compiles.append(duration)
    return listener


def seed_key(jax, seed: int):
    """A PRNG key from any whole-number seed, 64-bit ones included."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, dict], device: dict,
                checks: List[dict], breakdown: Optional[dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return json.dumps(out)


def check_entry(name: str, value: float, limit: float) -> dict:
    """One compared number beside its limit (at most the limit is ok; a
    NaN is not)."""
    return {"name": name, "value": value, "limit": limit,
            "ok": bool(value <= limit)}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)

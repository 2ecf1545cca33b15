"""Device memory of a served cell: what its resident arrays and its
compiled programs take, and so how many decode slots fit.

    python bench/memory.py --workload <cell> --seed <n> [--slots S ...]

It runs the cell's set-up at the configuration's slot count and a short
window, printing the bytes in use and the peak after each phase of
set-up, then ``memory_analysis()`` of the decode program and of each
prefill program.  With ``--slots`` it then frees that runtime and, for
each S, builds the runtime at S slots and compiles its decode program and
its largest prefill program without running them: their temporaries at S
slots.  One JSON line per reading on standard output.  The benchmark's
own runs never call this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH_DIR]
sys.path[:0] = [str(BENCH_DIR.parent), str(BENCH_DIR.parent / "src")]

from bench import harness as H  # noqa: E402

PHASES = ("weights", "runtime", "warmup", "ramp")
FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
          "alias_size_in_bytes", "temp_size_in_bytes",
          "generated_code_size_in_bytes")


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def in_use(devices) -> dict:
    stats = devices[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                      "bytes_limit")}


class MemRun(H.Run):
    """A run whose set-up phases report the device memory after them."""

    def span(self, name: str):
        import contextlib
        inner = super().span(name)
        if name not in PHASES:
            return inner

        @contextlib.contextmanager
        def phase():
            with inner:
                yield
            emit(phase=name, **in_use(self.devices))
        return phase()


def analysis(fn, args) -> dict:
    m = fn.lower(*args).compile().memory_analysis()
    return {k: getattr(m, k) for k in FIELDS}


def program_args(rt, rows: int = 0):
    """The decode program's arguments (``rows`` = 0), or those of the
    prefill program of a bucket of ``rows`` tokens."""
    import jax.numpy as jnp
    S, pg = rt.n_slots, rt.paged
    head = (rt.params, pg.pool, pg.state, pg.device_tables())
    if not rows:
        return head + (jnp.zeros((S, 1), jnp.int32),
                       jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool))
    return head + (jnp.zeros((S, rows), jnp.int32),
                   jnp.full((S,), rows, jnp.int32),
                   jnp.zeros((S,), jnp.int32), jnp.zeros((S,), bool))


def programs(rt, buckets):
    yield "decode", analysis(rt._decode_paged, program_args(rt))
    for rows in buckets:
        yield f"prefill{rows}", analysis(rt._prefill_paged_fn(rows),
                                         program_args(rt, rows))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--slots", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    bench = H.load_benchmark()
    cell = H.find_cell(bench, args.workload)
    config = H.load_config(cell["config"])
    traffic = H.load_traffic(cell["traffic"])
    if config["driver"] != "serve":
        raise H.BenchError("bench/memory.py reads served cells only")
    driver = H.load_driver("serve")
    from repro.launch.entry import prepare_entry_point
    prepare_entry_point()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.serving.runtime import ServingRuntime
    from repro.serving.scheduler import bucket_pow2
    devices = H.require_chips(jax, cell["chips"])
    run = MemRun(cell=cell, config=config, traffic=traffic, seed=args.seed,
                 seconds=args.seconds, trace=False, devices=devices,
                 peaks=H.load_peaks(devices[0].device_kind))
    sv = config["serving"]
    buckets = sorted({bucket_pow2(n) for n in driver.warm_prompt_lengths(
        traffic, int(sv["page_block"]), int(sv["prefill_chunk"]))})
    state = driver.setup(run)
    driver.window(state, run)
    emit(phase="window", slots=int(sv["slots"]), **in_use(devices))
    for name, mem in programs(state.rt, buckets):
        emit(program=name, slots=int(sv["slots"]), **mem)
    params, cfg = state.params, driver.model_config(config)
    del state
    gc.collect()
    for slots in args.slots:
        rt = ServingRuntime(cfg, params, slots=slots,
                            max_len=int(sv["max_len"]),
                            page_block=int(sv["page_block"]),
                            prefill_chunk=int(sv["prefill_chunk"]),
                            presplit=bool(sv["presplit"]))
        emit(phase="runtime", slots=slots, **in_use(devices))
        for name, mem in programs(rt, buckets[-1:]):
            emit(program=name, slots=slots, **mem)
        del rt
        gc.collect()
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except H.BenchError as e:
        print(f"[bench] error: {e}", file=sys.stderr, flush=True)
        sys.exit(2)

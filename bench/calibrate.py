"""Readings that set the limits of ``correct``: the program's number and
its control's, over many seeds, in one process per cell.

    python bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--seconds S]

For each seed it runs the cell's set-up, a window of ``--seconds``, the
driver's controls (the plain reference put in the program's place at the
nearest lower precision, ``control`` in the driver) and the check, each
through the same compared numbers and limits.  One JSON line per seed
goes to standard output.  It exits non-zero where the program comes out
not correct or a control in the driver's ``CONTROLS`` comes out correct.
The benchmark's own runs never call this.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    bench = H.load_benchmark()
    cell = H.find_cell(bench, args.workload)
    config = H.load_config(cell["config"])
    traffic = H.load_traffic(cell["traffic"])
    driver = H.load_driver(config["driver"])
    from repro.launch.entry import prepare_entry_point
    prepare_entry_point()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = H.require_chips(jax, cell["chips"])
    peaks = H.load_peaks(devices[0].device_kind)
    faults = 0
    for seed in args.seeds:
        run = H.Run(cell=cell, config=config, traffic=traffic, seed=seed,
                    seconds=args.seconds, trace=False, devices=devices,
                    peaks=peaks)
        state = driver.setup(run)
        rec = driver.window(state, run)
        controls = driver.control(state, run, rec)
        correct, checks = driver.check(state, run, rec)
        passed = [label for label in driver.CONTROLS
                  if controls.get(label, (True,))[0]]
        faults += (not correct) + len(passed)
        print(json.dumps({
            "seed": seed, "correct": correct,
            "program": {c["name"]: c["value"] for c in checks},
            "controls": {label: dict({c["name"]: c["value"] for c in cs},
                                     correct=ok)
                         for label, (ok, cs) in controls.items()},
            "controls_correct": passed, "e2e": rec["e2e"]}), flush=True)
        del state
        gc.collect()
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())

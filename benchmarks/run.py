"""Benchmark harness entry point: one bench per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only accuracy,...]
        [--out experiments/bench] [--summary BENCH_ozimmu.json]

Benches:
  accuracy    Figs. 1/5   — measured error vs k, phi (dd reference)
  breakdown   Figs. 2-3, 6-11 — phase-time shares (v5e model + CPU sanity)
  throughput  Figs. 12-13 — emulated TFLOPS vs n (v5e model)
  pareto      Fig. 14     — measured error vs modeled TFLOPS
  ozimmu_roofline          — roofline terms of the emulated GEMM (HLO)

Besides the per-bench JSON in ``--out``, the harness writes a top-level
``BENCH_ozimmu.json`` headline summary (schema documented in
docs/benchmarks.md) so the perf trajectory of the repo can be tracked
across PRs from one small committed artifact.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# v2: added the `serving` bench (trace-replay tokens/s + TTFT +
# split-cache savings; docs/benchmarks.md#serving)
# v3: planner-economy headlines — `accuracy.prob_auto` (probed det/prob
# auto-k twins) and `breakdown.auto_cost` (static jit-path twins), both
# gated by check_against
# v4: serving `prefix` headline — shared-prompt-trace prefix-cache hit
# rate (gated) and TTFT ratio cached/uncached (recorded)
SUMMARY_SCHEMA_VERSION = 4


def _headline_accuracy(rows):
    """Max-phi errors at the paper's default k=8 per variant (+ fp64),
    plus the ``prob_auto`` planner-economy section: each ``<label>_prob``
    auto row paired with its deterministic twin's k / GEMM count."""
    fixed = [r for r in rows if not r.get("auto")]
    phis = sorted({r["phi"] for r in fixed if r["variant"] != "fp64"})
    ks = sorted({r["k"] for r in fixed if r["variant"] != "fp64"})
    if not phis or not ks:
        return {}
    phi = phis[-1]
    k = 8 if 8 in ks else ks[-1]
    err = {r["variant"]: r["err"] for r in fixed
           if r["phi"] == phi and r["k"] == k}
    fp64 = [r["err"] for r in fixed
            if r["phi"] == phi and r["variant"] == "fp64"]
    out = {"phi": phi, "k": k, "err": err,
           "err_fp64": fp64[0] if fp64 else None}
    auto = {r["variant"]: r for r in rows
            if r.get("auto") and r["phi"] == phi}
    prob = {}
    for label, r in sorted(auto.items()):
        if not label.endswith("_prob"):
            continue
        entry = {"k": r["k"], "err": r["err"],
                 "int8_gemms": r["int8_gemms"]}
        det = auto.get(label[: -len("_prob")])
        if det is not None:
            entry.update(k_det=det["k"], err_det=det["err"],
                         gemms_det=det["int8_gemms"])
        prob[label] = entry
    if prob:
        out["prob_auto"] = {"phi": phi, "rows": prob}
    return out


def _headline_breakdown(rows):
    """Accumulation-time shares, EF/H/oz2 modeled speedups, and the Plan
    cost accounting (int8 GEMMs / high-precision adds — where the oz2
    exponent ladder's reduction shows up) at one k.  Auto-planned rows
    (``"plan": "auto"``) stay out of the fixed-k section and feed the
    ``auto_cost`` section instead: the static det/prob k the jit path
    resolves, with the GEMM-count delta the :prob shave buys."""
    fixed = [r for r in rows if r.get("plan") != "auto"]
    ks = sorted({r["k"] for r in fixed})
    k = 8 if 8 in ks else ks[-1]
    at_k = [r for r in fixed if r["k"] == k]
    out = {
        "n": at_k[0]["n"], "k": k,
        "accum_share": {r["variant"]: r["share_accum"] for r in at_k},
        "speedup_vs_ozimmu": {
            r["variant"]: r["speedup_vs_ozimmu"] for r in at_k
            if "speedup_vs_ozimmu" in r},
        "cost": {r["variant"]: {"int8_gemms": r["int8_gemms"],
                                "hp_adds": r["hp_adds"]}
                 for r in at_k if "int8_gemms" in r},
    }
    auto = {r["variant"]: r for r in rows if r.get("plan") == "auto"}
    cost = {}
    for label, r in sorted(auto.items()):
        if not label.endswith("_prob"):
            continue
        entry = {"k": r["k"], "int8_gemms": r["int8_gemms"],
                 "hp_adds": r["hp_adds"]}
        det = auto.get(label[: -len("_prob")])
        if det is not None:
            entry.update(
                k_det=det["k"], gemms_det=det["int8_gemms"],
                gemms_saved=det["int8_gemms"] - r["int8_gemms"])
        cost[label] = entry
    if cost:
        out["auto_cost"] = {"n": auto[next(iter(auto))]["n"],
                            "rows": cost}
    return out


def _headline_throughput(rows):
    """Modeled TFLOPS per variant at the largest n, k=8."""
    ns = sorted({r["n"] for r in rows})
    ks = sorted({r["k"] for r in rows})
    n, k = ns[-1], (8 if 8 in ks else ks[-1])
    tf = {r["variant"]: r["tflops"] for r in rows
          if r["n"] == n and r["k"] == k}
    base = tf.get("ozimmu")
    return {"n": n, "k": k, "tflops": tf,
            "ef_over_base": (tf.get("ozimmu_ef", 0) / base) if base else None,
            "h_over_base": (tf.get("ozimmu_h", 0) / base) if base else None}


def _headline_pareto(rows):
    """Fraction of k cells where H Pareto-dominates base (Fig. 14 claim)."""
    idx = {(r["variant"], r["k"]): r for r in rows}
    ks = sorted({r["k"] for r in rows})
    claims = []
    for k in ks:
        h, b = idx.get(("ozimmu_h", k)), idx.get(("ozimmu", k))
        if h and b:
            claims.append(h["tflops"] >= 1.2 * b["tflops"]
                          and h["err"] <= 2.0 * b["err"])
    return {"ks": ks,
            "h_dominates_base_frac":
                (sum(claims) / len(claims)) if claims else None}


def _headline_roofline(rows):
    """Roofline-bound emulated TFLOPS per analyzed spec."""
    return {"n": rows[0]["n"] if rows else None,
            "emulated_tflops_bound": {
                r["spec"]: r["emulated_tflops_bound"] for r in rows},
            "bound": {r["spec"]: r["bound"] for r in rows}}


def _headline_serving(rows):
    """Runtime-vs-legacy tokens/s, split-cache effect, and the modeled
    decode-step splitter share under the weight split-cache, for the
    first ozimmu engine row (wall-clock ratios are recorded for the
    trajectory; the gate only checks the deterministic fields)."""
    oz = [r for r in rows if r.get("cached_over_uncached") is not None]
    if not oz:
        return {}
    r = oz[0]
    out = {
        "engine": r["engine"], "slots": r["slots"],
        "requests": r["requests"],
        "tokens_per_s": {m: round(v["tokens_per_s"], 3)
                         for m, v in r["modes"].items()},
        "runtime_over_legacy": r["runtime_over_legacy"],
        "cached_over_uncached": r["cached_over_uncached"],
        "weight_split_hit_rate": r["weight_split_hit_rate"],
        "modeled_decode": r.get("modeled_decode"),
    }
    pfx = r.get("prefix")
    if pfx is not None:
        out["prefix"] = {
            "hit_rate": pfx["hit_rate"],
            "hit_tokens": pfx["hit_tokens"],
            "prefix_ttft_ratio": round(pfx["prefix_ttft_ratio"], 4),
        }
    return out


_HEADLINES = {
    "accuracy": _headline_accuracy,
    "breakdown": _headline_breakdown,
    "throughput": _headline_throughput,
    "pareto": _headline_pareto,
    "ozimmu_roofline": _headline_roofline,
    "serving": _headline_serving,
}


def check_against(summary: dict, committed_path: str, tol: float = 2.0,
                  allow_new_rows: bool = False):
    """Regression gate: the run's accuracy headline must not be worse than
    the committed trajectory artifact (``BENCH_ozimmu.json``) by more than
    ``tol``x per variant.  One-sided — better-than-committed always passes
    (quick grids at smaller n measure smaller errors).  Returns a list of
    human-readable failures (empty = gate passes); raises on a summary
    that cannot be compared at all (missing/failed accuracy bench).

    Row sets must MATCH the committed artifact both ways: a committed row
    missing from this run fails (a variant silently dropped out), and a
    row in this run that the artifact has never seen fails too — an
    ungated row is a row whose regressions CI can't see.  Adding a
    variant legitimately means regenerating ``BENCH_ozimmu.json`` with a
    full ``python -m benchmarks.run`` in the same change;
    ``allow_new_rows`` (CLI ``--allow-new-rows``) is the escape hatch for
    runs that intentionally carry rows the artifact predates.

    The ``prob_auto`` planner-economy headline is gated the same way,
    plus its own invariants: measured err within ``tol``x, the resolved
    probabilistic k never above the committed one (quick grids run at
    n <= the full grid's, which needs no more slices), and within-run
    economy — k and GEMM count never above the deterministic twin's.
    """
    with open(committed_path) as f:
        committed = json.load(f)
    failures = []
    bench = summary.get("benches", {}).get("accuracy")
    if bench is None or bench.get("status") != "ok":
        raise SystemExit(f"[check] accuracy bench missing or failed in "
                         f"this run: {bench}")
    got = bench.get("headline", {}).get("err", {})
    want = committed["benches"]["accuracy"]["headline"]["err"]
    for variant, ref_err in sorted(want.items()):
        new_err = got.get(variant)
        if new_err is None:
            failures.append(f"accuracy: variant {variant!r} missing from "
                            f"this run's headline")
        elif new_err > tol * ref_err:
            failures.append(
                f"accuracy: {variant} err {new_err:.3e} exceeds "
                f"{tol}x committed {ref_err:.3e}")
    extra = sorted(set(got) - set(want))
    if extra and not allow_new_rows:
        failures.append(
            f"accuracy: headline row(s) {extra} absent from the committed "
            f"artifact — regenerate it with a full `python -m "
            f"benchmarks.run`, or pass --allow-new-rows")
    got_pa = (bench.get("headline", {}).get("prob_auto") or {}
              ).get("rows", {})
    want_pa = (committed["benches"]["accuracy"]["headline"]
               .get("prob_auto") or {}).get("rows", {})
    for label, ref in sorted(want_pa.items()):
        r = got_pa.get(label)
        if r is None:
            failures.append(f"prob_auto: row {label!r} missing from this "
                            f"run's headline")
            continue
        if r["err"] > tol * ref["err"]:
            failures.append(
                f"prob_auto: {label} err {r['err']:.3e} exceeds "
                f"{tol}x committed {ref['err']:.3e}")
        if r["k"] > ref["k"]:
            failures.append(
                f"prob_auto: {label} resolved k={r['k']} above committed "
                f"k={ref['k']} (planner regression)")
        if "k_det" in r and r["k"] > r["k_det"]:
            failures.append(
                f"prob_auto: {label} k={r['k']} exceeds its deterministic "
                f"twin's k={r['k_det']} — planner economy violated")
        if "gemms_det" in r and r["int8_gemms"] > r["gemms_det"]:
            failures.append(
                f"prob_auto: {label} int8_gemms={r['int8_gemms']} exceeds "
                f"its deterministic twin's {r['gemms_det']}")
    extra_pa = sorted(set(got_pa) - set(want_pa))
    if extra_pa and not allow_new_rows:
        failures.append(
            f"prob_auto: row(s) {extra_pa} absent from the committed "
            f"artifact — regenerate it with a full `python -m "
            f"benchmarks.run`, or pass --allow-new-rows")
    # serving gate (when both sides ran it): the weight split-cache must
    # stay fully effective — a deterministic property, unlike the
    # wall-clock ratios, which are recorded but not gated (CI noise).
    srv = summary.get("benches", {}).get("serving")
    srv_ref = committed.get("benches", {}).get("serving")
    if srv is not None and srv.get("status") == "ok" and srv_ref:
        got_rate = (srv.get("headline") or {}).get("weight_split_hit_rate")
        want_rate = (srv_ref.get("headline") or {}
                     ).get("weight_split_hit_rate")
        if want_rate is not None and (got_rate or 0.0) < want_rate:
            failures.append(
                f"serving: weight split-cache hit rate {got_rate} fell "
                f"below committed {want_rate}")
        # prefix-cache hit rate on the shared-prompt trace is likewise
        # deterministic (same trace, same keying); the TTFT ratio rides
        # along uncommitted-gated (wall clock).
        got_pfx = ((srv.get("headline") or {}).get("prefix")
                   or {}).get("hit_rate")
        want_pfx = ((srv_ref.get("headline") or {}).get("prefix")
                    or {}).get("hit_rate")
        if want_pfx is not None and (got_pfx or 0.0) < want_pfx:
            failures.append(
                f"serving: prefix-cache hit rate {got_pfx} fell below "
                f"committed {want_pfx}")
    for name, entry in summary["benches"].items():
        if entry.get("status") != "ok":
            failures.append(f"{name}: status {entry.get('status')!r} "
                            f"({entry.get('error')})")
    return failures


def _build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced problem sizes / grids (CI smoke)")
    ap.add_argument("--out", default="experiments/bench",
                    help="directory for the full per-bench JSON rows")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of bench names")
    ap.add_argument("--summary", default=None,
                    help="headline summary path (schema: docs/benchmarks.md)."
                         " Default: BENCH_ozimmu.json (the committed "
                         "trajectory artifact) for FULL runs; partial runs "
                         "(--quick/--only) default to bench_summary.json so "
                         "they never clobber the committed record. "
                         "'' disables")
    ap.add_argument("--check-against", default=None, metavar="BENCH_JSON",
                    help="regression gate: fail (exit 1) if this run's "
                         "accuracy headline errors exceed 2x the committed "
                         "summary's (e.g. BENCH_ozimmu.json), any headline "
                         "row is unknown to it, or any bench failed.  The "
                         "same gate CI runs — runnable locally.")
    ap.add_argument("--allow-new-rows", action="store_true",
                    help="with --check-against: tolerate headline rows the "
                         "committed artifact predates (default: unknown "
                         "rows are a hard failure — an ungated row is a "
                         "row whose regressions CI can't see)")
    return ap


def main(argv=None):
    ap = _build_parser()
    args = ap.parse_args(argv)
    if args.summary is None:
        args.summary = ("BENCH_ozimmu.json"
                        if not args.quick and not args.only
                        else "bench_summary.json")
    os.makedirs(args.out, exist_ok=True)

    from benchmarks import (bench_accuracy, bench_breakdown,
                            bench_ozimmu_roofline, bench_pareto,
                            bench_serving, bench_throughput)
    benches = {
        "accuracy": bench_accuracy.main,
        "breakdown": bench_breakdown.main,
        "throughput": bench_throughput.main,
        "pareto": bench_pareto.main,
        # roofline terms of the emulated GEMM itself, from compiled HLO
        # (n=2048 keeps the harness fast; §Perf Cell C uses 4096/8192)
        "ozimmu_roofline": lambda out_json=None, quick=False:
            bench_ozimmu_roofline.main(out_json=out_json, quick=True),
        # serving trace replay (continuous batching + weight split-cache)
        "serving": bench_serving.main,
    }
    unknown = (set(args.only.split(",")) - set(benches)) if args.only else ()
    if unknown:
        ap.error(f"unknown bench names {sorted(unknown)}; "
                 f"options: {sorted(benches)}")
    only = set(args.only.split(",")) if args.only else set(benches)
    failures = []
    summary = {
        "schema_version": SUMMARY_SCHEMA_VERSION,
        "generated_unix": int(time.time()),
        "quick": bool(args.quick),
        "only": sorted(only),
        "benches": {},
    }
    for name, fn in benches.items():
        if name not in only:
            continue
        print(f"\n===== bench: {name} =====")
        t0 = time.time()
        try:
            rows = fn(out_json=os.path.join(args.out, f"{name}.json"),
                      quick=args.quick)
            seconds = time.time() - t0
            try:
                headline = _HEADLINES[name](rows or [])
            except Exception as e:  # a bench reshape must not kill the run
                headline = {"error": f"headline extraction failed: {e!r}"}
            summary["benches"][name] = {
                "status": "ok", "seconds": round(seconds, 2),
                "headline": headline,
            }
            print(f"===== {name} done in {seconds:.1f}s =====")
        except Exception as e:
            import traceback
            traceback.print_exc()
            failures.append((name, repr(e)))
            summary["benches"][name] = {
                "status": "failed", "seconds": round(time.time() - t0, 2),
                "error": repr(e),
            }
    if args.summary:
        with open(args.summary, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"\nheadline summary -> {args.summary}")
    if failures:
        print("\nFAILED benches:", failures)
        sys.exit(1)
    if args.check_against:
        gate = check_against(summary, args.check_against,
                             allow_new_rows=args.allow_new_rows)
        if gate:
            print("\n[check] REGRESSION GATE FAILED vs", args.check_against)
            for line in gate:
                print("[check]  -", line)
            sys.exit(1)
        print(f"[check] regression gate vs {args.check_against}: OK")
    print("\nall benches complete; JSON in", args.out)


if __name__ == "__main__":
    from repro.launch.entry import prepare_entry_point
    prepare_entry_point()
    main()

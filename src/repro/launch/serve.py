"""Serving driver over the continuous-batching runtime.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b \
        --requests 8 --slots 4 --prompt-len 32 --gen 32 \
        --engine ozimmu_h-8:df32 --page-block 16

Reduced widths (``--smoke``, the default) keep CPU runs short; ``--full``
serves the published widths, and ``--n-layers`` cuts depth to fit one
chip (weights are random, from a fixed seed).

The heavy lifting lives in :mod:`repro.serving` (docs/serving.md): a
slot-based continuous batcher with bucketed batched prefill (mixed-length
prompts share one compiled call), an optional block-paged KV pool
(``--page-block``), and — for ozimmu engines — the persistent weight
split-cache: every projection weight is frozen into its int8 digit
slices once at startup, so decode steps skip the B-side splitter
entirely (bit-identical; the dominant per-step splitting cost at
decode).  ``--no-presplit`` disables the cache for A/B comparison.
"""
from __future__ import annotations

import argparse
import contextlib
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.distributed.sharding import use_rules
from repro.launch.entry import prepare_entry_point
from repro.launch.mesh import mesh_rules, parse_mesh_spec
from repro.models import api
from repro.obs import export as obs_export
from repro.obs import tracing as obs_tracing
from repro.serving import ServingRuntime


def make_runtime(cfg, params, *, slots: int, max_len: int,
                 page_block: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 presplit: Optional[bool] = None, ctx=None) -> ServingRuntime:
    return ServingRuntime(cfg, params, slots=slots, max_len=max_len,
                          page_block=page_block, prefill_chunk=prefill_chunk,
                          prefix_cache=prefix_cache, presplit=presplit,
                          ctx=ctx)


def slot_context(cfg, params, prompt_len: int):
    """Static single-slot context for the vlm/encdec families (shared
    across slots, matching the pre-runtime driver)."""
    if cfg.family == "vlm":
        return jnp.zeros((1, cfg.vision_seq, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        from repro.models import encdec
        frames = jnp.zeros((1, prompt_len, cfg.d_model), jnp.float32)
        return encdec.encode(params, cfg, frames)
    return None


def dump_metrics(path: str, runtime: ServingRuntime,
                 final: bool = False) -> None:
    """Write the unified metrics document (global registry merged with the
    runtime's private serving registry, plus the plan ledger) to ``path``.
    Final dumps embed the serving summary and decode-observed counters."""
    snap = obs_export.unified_snapshot(runtime.metrics.registry)
    extra = None
    if final:
        extra = {"serving_summary": runtime.metrics.summary()}
        if runtime.decode_observed is not None:
            extra["decode_observed"] = runtime.decode_observed
    text = obs_export.to_json(snap, extra=extra)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(text + "\n")
    import os
    os.replace(tmp, path)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced same-family widths (default)")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="the published widths of --arch")
    ap.add_argument("--n-layers", type=int, default=None,
                    help="override the layer count (depth is the only cut)")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4,
                    help="decode slots (compiled batch dimension)")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests to serve (default: slots, i.e. one "
                         "full wave)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--page-block", type=int, default=None,
                    help="positions per KV block: enables the paged "
                         "KV-cache pool (every family; state leaves stay "
                         "resident per the family descriptor)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="max prompt tokens fed per slot per scheduler "
                         "round (chunked prefill; default whole-prompt)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cache shared prompt prefixes as frozen paged "
                         "blocks (requires --page-block)")
    ap.add_argument("--no-presplit", action="store_true",
                    help="disable the weight split-cache (A/B baseline; "
                         "ozimmu engines only)")
    ap.add_argument("--engine", "--matmul_engine", dest="engine",
                    default="bf16",
                    help="matmul engine spec, e.g. bf16, ozimmu_h-8:df32@model "
                         "or ozimmu_h-auto:df32:fused (auto-k planner + fused "
                         "Pallas pipeline; docs/engine.md)")
    ap.add_argument("--mesh", default=None,
                    help="mesh spec: 'data=2,model=4', 'single_pod', "
                         "'multi_pod'; default no mesh (single device)")
    ap.add_argument("--metrics-json", default=None, metavar="PATH",
                    help="write the unified metrics document (registry "
                         "snapshot + plan ledger + serving summary) to "
                         "PATH; with --metrics-every also periodically "
                         "during the run")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="dump --metrics-json every N scheduler rounds "
                         "(0 = final dump only)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax profiler trace of the serving "
                         "loop into DIR (view with TensorBoard/Perfetto)")
    args = ap.parse_args(argv)
    n_requests = args.requests if args.requests is not None else args.slots

    mesh = parse_mesh_spec(args.mesh)
    rules = mesh_rules(mesh, args.arch) if mesh is not None else None
    mesh_ctx = (jax.set_mesh(mesh) if mesh is not None
                else contextlib.nullcontext())
    overrides = {"engine_spec": args.engine}
    if args.n_layers is not None:
        overrides["n_layers"] = args.n_layers
    cfg = configs.get_config(args.arch, smoke=args.smoke, **overrides)
    oz_cfg = cfg.engine.ozimmu_config
    if oz_cfg is not None:
        from repro.core import plan
        print(f"[serve] engine {args.engine}: "
              f"{plan.describe_config(oz_cfg, cfg.d_model, cfg.d_model, cfg.d_model)}")
    with mesh_ctx, use_rules(rules):
        model = api.get_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(0), cfg)
        ctx = slot_context(cfg, params, args.prompt_len)
        runtime = make_runtime(
            cfg, params, slots=args.slots, max_len=args.max_len,
            page_block=args.page_block, prefill_chunk=args.prefill_chunk,
            prefix_cache=args.prefix_cache,
            presplit=False if args.no_presplit else None, ctx=ctx)
        if runtime.split_cache is not None:
            st = runtime.split_cache.stats
            print(f"[serve] split-cache: froze {st.misses} weight splits "
                  f"({st.cached_bytes / 1e6:.2f} MB resident)")
        from repro.core import plan as _plan
        if len(_plan.get_ledger()):
            print(f"[serve] planner: {_plan.get_ledger().describe()}")
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, cfg.vocab, size=args.prompt_len,
                                dtype=np.int32) for _ in range(n_requests)]
        t0 = time.time()
        reqs = [runtime.submit(p, args.gen) for p in prompts]
        with obs_tracing.profile(args.profile_dir):
            rounds = 0
            while runtime.step():
                rounds += 1
                if (args.metrics_json and args.metrics_every
                        and rounds % args.metrics_every == 0):
                    dump_metrics(args.metrics_json, runtime)
        runtime.run()  # no rounds left; finalizes the metrics window
        outs = [np.concatenate([r.prompt,
                                np.asarray(r.generated, np.int32)])
                for r in reqs]
        dt = time.time() - t0
    s = runtime.metrics.summary()
    if args.metrics_json:
        dump_metrics(args.metrics_json, runtime, final=True)
        print(f"[serve] metrics written to {args.metrics_json}")
    print(f"[serve] {args.arch}: {s['tokens_generated']} tokens from "
          f"{s['requests']['finished']} requests in {dt:.2f}s "
          f"({s['tokens_per_s']:.1f} tok/s, slots={args.slots}, "
          f"prefill_calls={s['prefill_calls']}, "
          f"evictions={s['evictions']})")
    if s["ttft_s"]["mean"] is not None:
        print(f"[serve] TTFT mean {s['ttft_s']['mean']:.3f}s "
              f"p95 {s['ttft_s']['p95']:.3f}s; queue depth max "
              f"{s['queue_depth']['max']}")
    if s["split_cache"] is not None:
        sc = s["split_cache"]
        print(f"[serve] split-cache: weight-split hit rate "
              f"{sc['weight_split_hit_rate']:.2f}, "
              f"{sc['avoided_split_bytes'] / 1e6:.2f} MB of decode-time "
              f"re-splitting avoided")
    if s.get("prefix_cache") is not None:
        pc = s["prefix_cache"]
        print(f"[serve] prefix-cache: hit rate {pc['hit_rate']:.2f} "
              f"({pc['hit_tokens']} prefill tokens aliased, "
              f"{pc['entries']} entries)")
    if runtime.decode_observed is not None:
        obs = runtime.decode_observed
        print(f"[serve] observed per decode step: "
              f"{obs['contractions']:.0f} contractions, "
              f"{obs['int8_gemms']:.0f} int8 GEMMs "
              f"({obs['int8_gemms_presplit']:.0f} on presplit weights; "
              f"planned {obs['int8_gemms_planned']:.0f}), "
              f"{obs['highprec_adds']:.0f} high-precision adds")
    if runtime.nonfinite_logit_calls:
        print(f"[serve] WARNING: {runtime.nonfinite_logit_calls} prefill/"
              f"decode calls produced non-finite logits")
    print("[serve] sample continuation:",
          outs[0][-args.gen:][:16].tolist())
    return {"summary": s,
            "generated": [list(map(int, r.generated)) for r in reqs],
            "decode_observed": runtime.decode_observed,
            "nonfinite_logit_calls": runtime.nonfinite_logit_calls}


if __name__ == "__main__":
    prepare_entry_point()
    main()

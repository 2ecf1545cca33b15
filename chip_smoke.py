"""Smoke test of the main path on a TPU: the emulated GEMM and the served
internlm2_1_8b at its published widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the @model mesh path, four chips

One chip runs three phases in one process:

1. device: a TPU must be present (no CPU fallback);
2. GEMM: (2048x2048) @ (2048x8192), the MLP up-projection of 2048 prefill
   tokens, through ``repro.core.ozimmu_dot_general`` for four specs.  Each
   ``:fused`` result must equal its XLA twin bitwise, every result must
   stay within the documented error bound of its variant
   (``repro.core.analysis`` at the df32 unit roundoff, as
   tests/test_oracle.py holds it) plus the rounding of the f32 result,
   against a host f64 reference, and the compiled ``:fused`` programs
   must contain ``tpu_custom_call`` (the Pallas kernels went through
   Mosaic);
3. serve: internlm2_1_8b (8 layers, random weights from seed 0) through
   ``repro.launch.serve.main`` on ``ozimmu_h-auto:df32:fused``: every
   request finishes, the weight-split hit rate is 1, the int8 GEMM count
   of one decode program (every layer and the head) equals the planner's
   static plan of the same contractions, and the greedy tokens equal
   those of a second run on the XLA spec ``ozimmu_h-auto:df32``; every
   logit the runtime computed for a live slot is finite.

Like every entry point, the script first calls
``repro.launch.entry.prepare_entry_point``: it turns XLA's excess
precision off (so a ``:fused`` model and its XLA twin round their bf16
activations alike) and turns the persistent compile cache on.

``--four-chips`` runs only the mesh phase: the same GEMM as
``ozimmu_h-auto:df32@model/int32`` on a ``model=4`` mesh, bitwise against
the one-device emulation, and ``serve.main --mesh model=4`` on
``ozimmu_h-auto:df32@model``, token for token against one device.

The exit code is the verdict.  The last line of stdout is one JSON object
naming the device; a failed check exits non-zero before it.  Host wall
times printed along the way come from a single run: information, not
measurements.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

GEMM_SHAPE = (2048, 2048, 8192)          # (m, n, p)
# each :fused spec runs after its XLA twin (the spec without ":fused")
GEMM_SPECS = ("ozimmu_h-auto:df32:fused", "oz2_h-auto:fast2:df32:fused",
              "ozimmu_sm_h-auto:df32:fused")
SERVE_OPTS = {"arch": "internlm2_1_8b", "n-layers": 8, "page-block": 16,
              "prefill-chunk": 64, "slots": 4, "requests": 6,
              "prompt-len": 128, "gen": 16, "max-len": 256}
SERVE_FLAGS = ("--full", "--prefix-cache")
MESH_SERVE_OPTS = dict(SERVE_OPTS, **{"n-layers": 4, "requests": 4,
                                      "gen": 8})
MESH_SERVE_FLAGS = ("--full",)
DN = (((1,), (0,)), ((), ()))
U_DF32 = 2.0 ** -48                       # tests/test_oracle.py's U["df32"]
U_F32 = 2.0 ** -24                        # rounding of the f32 result


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

_COMPILE_S = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += duration


def compile_seconds() -> float:
    """Backend compile seconds so far (0 for programs the persistent
    cache served)."""
    return _COMPILE_S[0]


# ---------------------------------------------------------------------------
# phase 1: device
# ---------------------------------------------------------------------------

def device_phase(n_chips: int):
    import jax
    from repro.launch.entry import prepare_entry_point
    cache_dir = prepare_entry_point()       # before the backend starts
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: jax.devices()[0].platform is "
                           f"{devs[0].platform!r}")
    check(len(devs) >= n_chips,
          f"{n_chips} chips needed, {len(devs)} visible")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    log(f"device {devs[0].device_kind}, count {len(devs)}, "
        f"compile cache {cache_dir}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phase 2: the emulated GEMM
# ---------------------------------------------------------------------------

def phi_operands(m: int, n: int, p: int, seed: int = 0, phi: float = 0.5):
    """The paper's test matrices a_ij = (U - 0.5) exp(phi N), rounded to
    f32 (what a ``:df32`` engine computes on)."""
    rng = np.random.default_rng(seed)

    def mat(r, c):
        u = rng.uniform(0.0, 1.0, (r, c))
        return ((u - 0.5) * np.exp(phi * rng.standard_normal((r, c)))
                ).astype(np.float32)
    return mat(m, n), mat(n, p)


def error_bound(spec: str, a: np.ndarray, b: np.ndarray, k: int):
    """The documented elementwise bound tests/test_oracle.py holds the
    spec's variant to, at the df32 unit roundoff."""
    from repro.core import analysis, parse_spec
    cfg = parse_spec(spec)
    if cfg.accumulate == "oz2":
        return analysis.error_bound_oz2(a, b, k, cfg.fast, U_DF32)
    if cfg.split == "sm":
        return analysis.error_bound_sm(a, b, k, U_DF32)
    return analysis.error_bound_rn(a, b, k, U_DF32)


def resolved_k(spec: str, m: int, n: int, p: int) -> int:
    """The k a jitted contraction of f32 operands resolves to (the
    planner's static plan for a 24-bit mantissa)."""
    import jax
    from repro.core import parse_spec, plan
    f32 = jax.ShapeDtypeStruct((m, n), np.float32)
    return plan.plan_contraction(parse_spec(spec), m, n, p, a=f32,
                                 _record=False).k


def run_gemm(spec: str, a, b):
    """jit + compile one emulated GEMM; returns (result, hlo text,
    compile seconds, run seconds)."""
    import jax
    from repro.core import ozimmu_dot_general, parse_spec
    cfg = parse_spec(spec)
    fn = jax.jit(lambda x, y: ozimmu_dot_general(x, y, DN, cfg))
    t0 = time.perf_counter()
    compiled = fn.lower(a, b).compile()
    t1 = time.perf_counter()
    out = compiled(a, b)
    out.block_until_ready()
    t2 = time.perf_counter()
    return np.asarray(out), compiled.as_text(), t1 - t0, t2 - t1


def gemm_phase(shape=GEMM_SHAPE, specs=GEMM_SPECS, seed: int = 0):
    import jax.numpy as jnp
    from repro.core import parse_spec
    m, n, p = shape
    a_np, b_np = phi_operands(m, n, p, seed)
    a, b = jnp.asarray(a_np), jnp.asarray(b_np)
    a64, b64 = a_np.astype(np.float64), b_np.astype(np.float64)
    ref = a64 @ b64
    # the f32 operands give an f32 result: its final rounding, plus the
    # f64 reference's own worst-case error gamma_n |A||B|
    slack = U_F32 * np.abs(ref) + n * 2.0 ** -53 * (np.abs(a64)
                                                    @ np.abs(b64))
    results = {}
    order = [s for fused in specs for s in (fused.replace(":fused", ""),
                                            fused)]
    for spec in order:
        twin = spec.replace(":fused", "") if ":fused" in spec else None
        out, hlo, c_s, r_s = run_gemm(spec, a, b)
        check(out.shape == (m, p) and np.all(np.isfinite(out)),
              f"{spec}: non-finite or misshapen result {out.shape}")
        k = resolved_k(spec, m, n, p)
        err = np.abs(out.astype(np.float64) - ref)
        bound = error_bound(spec, a64, b64, k) + slack
        worst = float(np.max(err / bound))
        check(worst <= 1.0, f"{spec}: error exceeds its documented bound "
                            f"(max err/bound {worst:.3e})")
        line = (f"gemm {spec} {m}x{n}@{n}x{p} k={k}: max|err| "
                f"{float(err.max()):.3e}, max err/bound {worst:.3e}")
        if parse_spec(spec).use_pallas == "fused":
            kernels = hlo.count("tpu_custom_call")
            check(kernels > 0,
                  f"{spec}: no tpu_custom_call in the compiled HLO")
            line += f", tpu_custom_call x{kernels}"
        if twin is not None:
            same = np.array_equal(out.view(np.uint32),
                                  results[twin].view(np.uint32))
            check(same, f"{spec}: not bitwise equal to {twin}")
            line += f", bitwise == {twin}"
        log(f"{line}; host wall: compile {c_s:.2f}s, run {r_s:.3f}s")
        results[spec] = out
    return results


# ---------------------------------------------------------------------------
# phase 3: serve
# ---------------------------------------------------------------------------

def serve_argv(opts: dict, flags) -> list:
    return [f for k, v in opts.items() for f in (f"--{k}", str(v))] \
        + list(flags)


def serve_once(args):
    """One ``serve.main`` run; returns its result and host seconds."""
    from repro.launch import serve
    t0 = time.perf_counter()
    c0 = compile_seconds()
    res = serve.main(args)
    res["host_s"] = time.perf_counter() - t0
    res["compile_s"] = compile_seconds() - c0
    gc.collect()
    return res


def check_served(res, label: str, n_requests: int, gen: int) -> None:
    s = res["summary"]
    check(s["requests"]["finished"] == n_requests,
          f"{label}: {s['requests']['finished']}/{n_requests} requests "
          f"finished")
    lens = [len(g) for g in res["generated"]]
    check(all(n == gen for n in lens),
          f"{label}: generated lengths {lens}, want {gen} each")
    check(res["nonfinite_logit_calls"] == 0,
          f"{label}: {res['nonfinite_logit_calls']} prefill/decode calls "
          f"produced non-finite logits")


def serve_phase(opts=SERVE_OPTS, flags=SERVE_FLAGS,
                fused="ozimmu_h-auto:df32:fused", xla="ozimmu_h-auto:df32"):
    n_req, gen = opts["requests"], opts["gen"]
    args = serve_argv(opts, flags)

    fres = serve_once(args + ["--engine", fused])
    check_served(fres, fused, n_req, gen)
    sc = fres["summary"]["split_cache"]
    check(sc is not None and sc["weight_split_hit_rate"] == 1.0,
          f"{fused}: weight-split hit rate {sc and sc['weight_split_hit_rate']}")
    obs = fres["decode_observed"]
    check(obs is not None and obs["int8_gemms"] > 0
          and obs["int8_gemms"] == obs["int8_gemms_planned"],
          f"{fused}: int8 GEMMs per decode program observed "
          f"{obs and obs['int8_gemms']} != planned "
          f"{obs and obs['int8_gemms_planned']}")
    log(f"serve {fused}: {fres['summary']['requests']['finished']}/{n_req} "
        f"requests, {fres['summary']['tokens_generated']} tokens, "
        f"weight_split_hit_rate {sc['weight_split_hit_rate']:.2f}, int8 "
        f"GEMMs per decode program observed {obs['int8_gemms']:.0f} == "
        f"planned {obs['int8_gemms_planned']:.0f}, logits finite; host "
        f"wall: compile {fres['compile_s']:.1f}s"
        f", serve {fres['host_s']:.1f}s; peak HBM bytes so far "
        f"{peak_hbm_bytes()}")

    xres = serve_once(args + ["--engine", xla])
    check_served(xres, xla, n_req, gen)
    check(fres["generated"] == xres["generated"],
          f"greedy tokens of {fused} differ from {xla}")
    log(f"serve {xla}: tokens bitwise == {fused} "
        f"({sum(map(len, xres['generated']))} tokens), logits finite; "
        f"host wall: compile {xres['compile_s']:.1f}s, serve "
        f"{xres['host_s']:.1f}s; peak HBM bytes so far {peak_hbm_bytes()}")
    return fres, xres


def peak_hbm_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# four chips: the mesh-native @model path
# ---------------------------------------------------------------------------

def mesh_phase(shape=GEMM_SHAPE, opts=MESH_SERVE_OPTS,
               flags=MESH_SERVE_FLAGS, n_model: int = 4, seed: int = 0):
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import parse_mesh_spec
    from repro.obs import registry
    m, n, p = shape
    a_np, b_np = phi_operands(m, n, p, seed)
    one, _, _, _ = run_gemm("ozimmu_h-auto:df32",
                            jax.device_put(a_np, jax.devices()[0]),
                            jax.device_put(b_np, jax.devices()[0]))
    mesh = parse_mesh_spec(f"model={n_model}")
    reg = registry.get_registry()
    before = reg.total("emulation.int8_gemms", mesh="model")
    with jax.set_mesh(mesh):
        sharded, hlo, c_s, r_s = run_gemm("ozimmu_h-auto:df32@model/int32",
                                          jnp.asarray(a_np),
                                          jnp.asarray(b_np))
    ran_sharded = reg.total("emulation.int8_gemms", mesh="model") - before
    check(ran_sharded > 0, "@model GEMM: the sharded path did not run "
                           "(no int8 GEMMs labelled mesh=model)")
    check(np.array_equal(sharded.view(np.uint32), one.view(np.uint32)),
          "@model/int32 GEMM is not bitwise equal to one device")
    log(f"gemm ozimmu_h-auto:df32@model/int32 on model={n_model}: bitwise "
        f"== one device, {ran_sharded:.0f} int8 GEMMs labelled mesh=model, "
        f"all-reduce in HLO: {'all-reduce' in hlo}; host wall: compile "
        f"{c_s:.2f}s, run {r_s:.3f}s")

    n_req, gen = opts["requests"], opts["gen"]
    base = serve_argv(opts, flags)
    ref = serve_once(base + ["--engine", "ozimmu_h-auto:df32"])
    check_served(ref, "one device", n_req, gen)
    before = reg.total("emulation.int8_gemms", mesh="model")
    got = serve_once(base + ["--engine", "ozimmu_h-auto:df32@model",
                             "--mesh", f"model={n_model}"])
    ran_sharded = reg.total("emulation.int8_gemms", mesh="model") - before
    check_served(got, "mesh", n_req, gen)
    check(ran_sharded > 0, "@model serve: the sharded path did not run")
    check(got["generated"] == ref["generated"],
          "@model serve tokens differ from one device")
    log(f"serve ozimmu_h-auto:df32@model on model={n_model}: tokens == "
        f"one device ({sum(map(len, got['generated']))} tokens), "
        f"{ran_sharded:.0f} int8 GEMMs labelled mesh=model; host wall: "
        f"compile {got['compile_s']:.1f}s, serve {got['host_s']:.1f}s")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the @model mesh phase on four chips")
    args = ap.parse_args(argv)
    n_chips = 4 if args.four_chips else 1
    try:
        device = device_phase(n_chips)
        if args.four_chips:
            mesh_phase()
        else:
            gemm_phase()
            gc.collect()
            log(f"peak HBM bytes after the GEMM phase: {peak_hbm_bytes()}")
            serve_phase()
        log(f"total backend compile seconds: {compile_seconds():.1f}")
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

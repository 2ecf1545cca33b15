"""Window driver for emulated-GEMM cells: one jitted
``repro.core.ozimmu_dot_general`` at the traffic's shape, called back to
back for the window.

Set-up makes the operands on the device from the seed (the paper's test
matrices, a_ij = (U - 0.5) exp(phi N), in float32), compiles the call (or
loads it from the persistent cache) and runs it twice.  The window keeps
at most two calls in flight and ends on the first call that returns after
``--seconds``; ``gemm_tflops`` is 2*m*n*p of every call over the window.

The check compares entries of the last timed call's product, drawn from
the seed, with their double-double value (``bench/reference/dd.py``): the
error beyond that of the correctly rounded float32 result, in units of
float32's unit roundoff times the magnitudes summed:

    gemm_err_excess = max (|C_ij - (AB)_ij| - ulp_f32((AB)_ij) / 2)+
                          / (2^-24 (|A||B|)_ij)

A correctly rounded result reads 0; a float32 GEMM's own rounding errors
read about 1.  Units of the magnitudes, not of the entry, keep the number
steady where a sum cancels, (AB)_ij << (|A||B|)_ij.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Optional

import numpy as np

from bench import harness as H
from bench.reference import dd

TRAFFIC_KINDS = ("gemm",)
DN = (((1,), (0,)), ((), ()))
U_F32 = 2.0 ** -24


@dataclasses.dataclass
class State:
    fn: Any
    a: Any
    b: Any
    out: Optional[Any]
    m: int
    n: int
    p: int
    int8_gemms_per_call: float


def operands(key, m: int, n: int, p: int, phi: float):
    """The paper's test matrices, made on the device in float32."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(key, 4)

    def mat(k1, k2, shape):
        u = jax.random.uniform(k1, shape, jnp.float32)
        g = jax.random.normal(k2, shape, jnp.float32)
        return (u - 0.5) * jnp.exp(phi * g)

    return mat(ks[0], ks[1], (m, n)), mat(ks[2], ks[3], (n, p))


def gemm_fn(spec: str):
    import jax
    from repro.core import ozimmu_dot_general, parse_spec
    cfg = parse_spec(spec)
    return jax.jit(lambda x, y: ozimmu_dot_general(x, y, DN, cfg))


def setup(run: H.Run) -> State:
    import jax
    from repro.obs import registry
    t = run.traffic
    m, n, p = int(t["m"]), int(t["n"]), int(t["p"])
    make = jax.jit(operands, static_argnums=(1, 2, 3, 4))
    with run.span("operands"):
        a, b = make(H.seed_key(jax, run.seed), m, n, p,
                    float(run.config["operands"]["phi"]))
        jax.block_until_ready((a, b))
    fn = gemm_fn(run.config["spec"])
    reg = registry.get_registry()
    before = reg.total("emulation.int8_gemms")
    with run.span("warmup"):
        fn(a, b).block_until_ready()      # traces, so the counter moves
    gemms = reg.total("emulation.int8_gemms") - before
    with run.span("warmup"):
        fn(a, b).block_until_ready()
    return State(fn, a, b, None, m, n, p, gemms)


def window(state: State, run: H.Run) -> dict:
    calls, prev = 0, None
    with run.traced_span():
        with run.span("window"):
            t0 = time.perf_counter()
            while True:
                with run.span("dispatch"):
                    out = state.fn(state.a, state.b)
                calls += 1
                if prev is not None:
                    with run.span("wait"):
                        prev.block_until_ready()
                prev = out
                if time.perf_counter() - t0 >= run.seconds:
                    break
            with run.span("wait"):
                out.block_until_ready()
            t1 = time.perf_counter()
    state.out = out
    flops = 2.0 * state.m * state.n * state.p * calls
    return {"e2e": {"gemm_tflops": flops / (t1 - t0) / 1e12},
            "attempted": calls, "failed": 0, "calls": calls,
            "window_s": t1 - t0, "flops": flops,
            "shape": [state.m, state.n, state.p],
            "int8_gemms_per_call": state.int8_gemms_per_call}


def sample_entries(seed: int, m: int, p: int, count: int):
    rng = np.random.default_rng(int(seed))
    return rng.integers(0, m, count), rng.integers(0, p, count)


def gather(c, a, b, i, j):
    """Entries C[i, j] with the rows of A and columns of B they need."""
    import jax
    take = jax.jit(lambda c, a, b, i, j: (c[i, j], a[i], b[:, j].T))
    return jax.device_get(take(c, a, b, i, j))


def ulp_f32(x: np.ndarray) -> np.ndarray:
    """Unit in the last place of float32 at |x| (normal range; a zero
    takes the smallest subnormal's)."""
    _, e = np.frexp(np.abs(x))
    return np.where(x == 0, 2.0 ** -149, np.ldexp(1.0, e - 24))


def errors(c, rows, cols):
    """Per entry: |c - row.col| in float32 ulps of row.col, and the error
    beyond half an ulp in units of 2^-24 |row|.|col|; row.col in
    double-double."""
    hi, lo = dd.dd_dots(rows, cols)
    err = np.abs((np.asarray(c, np.float64) - hi) - lo)
    ulp = ulp_f32(hi + lo)
    mag = np.sum(np.abs(rows.astype(np.float64))
                 * np.abs(cols.astype(np.float64)), axis=1)
    return err / ulp, np.maximum(err - ulp / 2, 0.0) / (U_F32 * mag)


def check_product(out, state: State, run: H.Run):
    """The check's entries of one product ``out`` of the run's operands."""
    chk = run.config["check"]
    i, j = sample_entries(run.seed, state.m, state.p, int(chk["entries"]))
    c, rows, cols = gather(out, state.a, state.b, i, j)
    ulps, excess = errors(c, rows, cols)
    H.log(f"gemm check: {len(ulps)} entries, widest error "
          f"{float(np.max(ulps))!r} ulps of the exact entry")
    entry = H.check_entry("gemm_err_excess", float(np.max(excess)),
                          float(chk["limit"]))
    return entry["ok"], [entry]


def check(state: State, run: H.Run, rec: dict):
    out = check_product(state.out, state, run)
    state.out = state.fn = state.a = state.b = None
    return out


# The controls, each of which has to come out not correct: a plain float32
# GEMM in the program's place, the nearest precision below the FP64-class
# product the configuration states, at precision HIGHEST and at HIGH
# (three bfloat16 passes).
CONTROLS = {"f32_highest": "HIGHEST", "f32_high": "HIGH"}


def control_fn(label: str):
    import jax
    import jax.numpy as jnp
    prec = getattr(jax.lax.Precision, CONTROLS[label])
    return jax.jit(lambda x, y: jnp.matmul(x, y, precision=prec))


def control(state: State, run: H.Run, rec: dict) -> dict:
    """Each control's check on the same operands and entries."""
    return {label: check_product(control_fn(label)(state.a, state.b),
                                 state, run) for label in CONTROLS}

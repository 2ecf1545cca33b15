"""The plain references against the program at smoke size, on the CPU.

* The double-double product is exact to ~2^-106: checked against exact
  rational arithmetic.
* The emulated GEMM (the GEMM driver's own call) stays within the
  check's limit on its error beyond correct rounding; a float32 GEMM, the
  check's control, does not.
* Served logits, chunked prefill and then cached decode, agree with the
  plain float32 forward to within what bfloat16 activations allow.
"""
from __future__ import annotations

from fractions import Fraction

import numpy as np
import pytest

from bench import harness as H
from bench.drivers import gemm, serve
from bench.reference import dd, dense_gqa


def test_dd_matmul_is_exact_to_double_double():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((3, 40)) * np.exp(rng.standard_normal((3, 40)))
    b = rng.standard_normal((40, 2))
    hi, lo = dd.dd_matmul(a, b)
    for i in range(3):
        for j in range(2):
            exact = sum(Fraction(a[i, k]) * Fraction(b[k, j])
                        for k in range(40))
            got = Fraction(hi[i, j]) + Fraction(lo[i, j])
            assert abs(got - exact) <= abs(exact) * Fraction(1, 2 ** 100)
    dh, dl = dd.dd_dots(a[[0, 2, 1]], b.T[[1, 0, 1]])
    assert np.array_equal(dh, hi[[0, 2, 1], [1, 0, 1]])
    assert np.array_equal(dl, lo[[0, 2, 1], [1, 0, 1]])


def _operands(m, n, p, seed=3):
    import jax
    return jax.jit(gemm.operands, static_argnums=(1, 2, 3, 4))(
        H.seed_key(jax, seed), m, n, p, 0.5)


@pytest.mark.parametrize("shape", [(128, 512, 256), (256, 128, 128)])
def test_emulated_gemm_against_dd(shape):
    import jax
    import jax.numpy as jnp
    m, n, p = shape
    a, b = _operands(m, n, p)
    i, j = gemm.sample_entries(5, m, p, 512)
    c, rows, cols = gemm.gather(gemm.gemm_fn("ozimmu_h-auto:df32")(a, b),
                                a, b, i, j)
    limit = H.load_config("dgemm")["check"]["limit"]
    # beyond the float32 rounding of the result, the emulation's error is
    # about 2^-31 (|A||B|)_ij at k = 5 and far less at k = 6; a float32
    # GEMM's is about 2^-24 (|A||B|)_ij
    assert np.max(gemm.errors(c, rows, cols)[1]) < limit / 4
    f32 = jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    c2, _, _ = gemm.gather(f32, a, b, i, j)
    assert np.max(gemm.errors(c2, rows, cols)[1]) > 4 * limit


def test_ulp_f32_by_hand():
    x = np.array([1.0, 1.5, 2.0 ** -20, -3.0, 0.0])
    got = gemm.ulp_f32(x)
    want = [2.0 ** -23, 2.0 ** -23, 2.0 ** -43, 2.0 ** -22, 2.0 ** -149]
    assert np.array_equal(got, want)


SMOKE = {"hidden_size": 64, "intermediate_size": 96,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "num_hidden_layers": 2, "vocab_size": 256}


def smoke_config(**serving):
    cfg = H.load_config("internlm2_1_8b")
    cfg.update(SMOKE)
    cfg["serving"] = dict(cfg["serving"], slots=1, max_len=40, page_block=8,
                          prefill_chunk=8, **serving)
    return cfg


def test_served_logits_match_the_plain_forward():
    """Chunked prefill (12 prompt tokens in chunks of 8) and then cached
    decode, one slot, logits read where the runtime computes them."""
    import jax
    import jax.numpy as jnp
    from repro.serving.runtime import ServingRuntime
    cfg = smoke_config()
    dims = serve.dims_of(cfg)
    params = jax.jit(serve.make_weights, static_argnums=1)(
        H.seed_key(jax, 11), serve._frozen(dims))
    seen = []

    class Capture(ServingRuntime):
        def _greedy(self, logits, live):
            jax.debug.callback(lambda x: seen.append(np.asarray(x)),
                               logits[0, :dims["vocab"]])
            return super()._greedy(logits, live)

    sv = cfg["serving"]
    rt = Capture(serve.model_config(cfg), params, slots=1,
                 max_len=sv["max_len"], page_block=sv["page_block"],
                 prefill_chunk=sv["prefill_chunk"], presplit=True)
    prompt = np.random.default_rng(1).integers(0, dims["vocab"], 12)
    rt.generate([prompt.astype(np.int32)], max_new=10)
    req = rt.sched.finished[0]
    toks = np.concatenate([prompt, req.generated[:-1]]).astype(np.int32)
    ref = np.asarray(dense_gqa.forward(params, dims, jnp.asarray(toks)))
    # chunk 1 ends at position 7, chunk 2 at 11 (first token), then one
    # decode step per position
    positions = [7, 11] + list(range(12, 12 + 9))
    assert len(seen) == len(positions)
    got = np.stack(seen)
    scale = np.std(ref[positions])
    # bfloat16 activations, KV cache and logits: each rounding is 2^-9
    # relative, a few dozen of them compound over two layers; the served
    # logits stay within 5% of the logits' spread of the exact ones
    assert np.max(np.abs(got - ref[positions])) < 0.05 * scale
    gaps, _ = dense_gqa.served_gaps(params, dims, jnp.asarray(toks),
                                    jnp.asarray(np.r_[toks[1:],
                                                      req.generated[-1]]))
    assert float(np.max(np.asarray(gaps)[11:])) < 0.05 * scale

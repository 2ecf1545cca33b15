"""Tests for `ozimmu_dot_general`: batched / multi-batch / transposed
contractions vs `jnp.einsum` references, gradient correctness through the
general-dimension-numbers custom VJP, batch-vs-loop bit-equality, the
batched Pallas path, and the engine routing (no reshape-to-2D)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (VARIANTS, make_engine, ozimmu_dot_general,
                        ozimmu_matmul)
from tests.conftest import make_phi_matrix


def phi_tensor(rng, shape, phi=0.5, dtype=np.float64):
    flat = make_phi_matrix(rng, int(np.prod(shape[:-1])), shape[-1], phi,
                           dtype)
    return jnp.asarray(flat.reshape(shape))


TOL = dict(rtol=1e-12, atol=1e-12)


def test_batched_bmn_bnp(rng):
    """bmn,bnp->bmp to emulation accuracy, every variant."""
    a = phi_tensor(rng, (3, 24, 40))
    b = phi_tensor(rng, (3, 40, 12))
    dn = (((2,), (1,)), ((0,), (0,)))
    ref = jnp.einsum("bmn,bnp->bmp", a, b)
    for variant in VARIANTS:
        c = ozimmu_dot_general(a, b, dn, VARIANTS[variant].with_(k=10))
        assert c.shape == ref.shape
        np.testing.assert_allclose(np.asarray(c), np.asarray(ref), **TOL)


def test_multi_batch_and_multi_free(rng):
    """Two batch dims + a free dim on each side (attention-score shape)."""
    q = phi_tensor(rng, (2, 3, 10, 32))
    k = phi_tensor(rng, (2, 3, 14, 32))
    dn = (((3,), (3,)), ((0, 1), (0, 1)))
    ref = jnp.einsum("xyld,xysd->xyls", q, k)
    c = ozimmu_dot_general(q, k, dn, VARIANTS["ozimmu_h"].with_(k=10))
    np.testing.assert_allclose(np.asarray(c), np.asarray(ref), **TOL)


def test_transposed_contraction(rng):
    """Contract over lhs axis 0 / rhs axis 1: nm,pn->mp (both transposed)."""
    a = phi_tensor(rng, (40, 24))      # (n, m)
    b = phi_tensor(rng, (12, 40))      # (p, n)
    dn = (((0,), (1,)), ((), ()))
    ref = jnp.einsum("nm,pn->mp", a, b)
    c = ozimmu_dot_general(a, b, dn, VARIANTS["ozimmu_h"].with_(k=10))
    np.testing.assert_allclose(np.asarray(c), np.asarray(ref), **TOL)


def test_multiple_contraction_axes(rng):
    """Two contraction axes flatten into one inner dim (beta from total n)."""
    x = phi_tensor(rng, (2, 6, 5, 8))
    y = phi_tensor(rng, (2, 6, 8, 7))
    dn = (((1, 3), (1, 2)), ((0,), (0,)))
    ref = jax.lax.dot_general(x, y, dn)
    c = ozimmu_dot_general(x, y, dn, VARIANTS["ozimmu_h"].with_(k=10))
    np.testing.assert_allclose(np.asarray(c), np.asarray(ref), **TOL)


def test_batched_equals_per_batch_loop(rng):
    """Batch dims must be carried natively: the batched emulation is
    BIT-IDENTICAL to looping ozimmu_matmul over the batch (per-batch
    row/col scales, same int8 digits, same accumulation order)."""
    a = phi_tensor(rng, (4, 16, 48))
    b = phi_tensor(rng, (4, 48, 8))
    dn = (((2,), (1,)), ((0,), (0,)))
    for variant in ("ozimmu", "ozimmu_rn", "ozimmu_h",
                    "ozimmu_sm_b", "ozimmu_sm_h"):
        cfg = VARIANTS[variant].with_(k=8)
        got = np.asarray(ozimmu_dot_general(a, b, dn, cfg))
        want = np.stack([np.asarray(ozimmu_matmul(a[i], b[i], cfg))
                         for i in range(a.shape[0])])
        np.testing.assert_array_equal(got, want, err_msg=variant)


def test_oz2_fast_modes_batched_equals_loop_and_grads(rng):
    """oz2 :fast and :fast2 under general dnums: batched == per-batch loop
    bitwise (per-batch gbase and, for fast2, per-batch diag unscale), and
    cotangents match the f64 reference through the custom VJP."""
    a = phi_tensor(rng, (3, 16, 48), phi=1.5)
    b = phi_tensor(rng, (3, 48, 8), phi=1.5)
    dn = (((2,), (1,)), ((0,), (0,)))
    for variant in ("oz2_b", "oz2_h"):
        for fast in (True, "fast2"):
            cfg = VARIANTS[variant].with_(k=10, fast=fast)
            got = np.asarray(ozimmu_dot_general(a, b, dn, cfg))
            want = np.stack([np.asarray(ozimmu_matmul(a[i], b[i], cfg))
                             for i in range(a.shape[0])])
            np.testing.assert_array_equal(got, want)
    cfg = VARIANTS["oz2_h"].with_(k=10, fast="fast2")
    ga, gb = jax.grad(lambda a, b: jnp.sum(
        jnp.sin(ozimmu_dot_general(a, b, dn, cfg))), (0, 1))(a, b)
    ra, rb = jax.grad(lambda a, b: jnp.sum(
        jnp.sin(jax.lax.dot_general(a, b, dn))), (0, 1))(a, b)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ra),
                               rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                               rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("variant", ["ozimmu_h", "ozimmu_sm_h"])
def test_grads_of_batched_contraction(rng, variant):
    """Cotangents flow through the emulation under general dnums — the
    sign-magnitude family included (its cotangent contractions re-split
    both operands under the sm convention)."""
    a = phi_tensor(rng, (3, 9, 20))
    b = phi_tensor(rng, (3, 20, 7))
    dn = (((2,), (1,)), ((0,), (0,)))
    cfg = VARIANTS[variant].with_(k=10)

    def loss_oz(a, b):
        return jnp.sum(jnp.sin(ozimmu_dot_general(a, b, dn, cfg)))

    def loss_ref(a, b):
        return jnp.sum(jnp.sin(jax.lax.dot_general(a, b, dn)))

    ga, gb = jax.grad(loss_oz, argnums=(0, 1))(a, b)
    ra, rb = jax.grad(loss_ref, argnums=(0, 1))(a, b)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ra),
                               rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                               rtol=1e-9, atol=1e-12)


def test_grads_transposed_and_multi_batch(rng):
    """VJP transpose bookkeeping for non-trivial axis layouts."""
    x = phi_tensor(rng, (2, 4, 5, 3))
    y = phi_tensor(rng, (2, 4, 3, 6))
    dn = (((1, 3), (1, 2)), ((0,), (0,)))
    cfg = VARIANTS["ozimmu_h"].with_(k=10)
    g1 = jax.grad(lambda x, y: jnp.sum(
        jnp.sin(ozimmu_dot_general(x, y, dn, cfg))), (0, 1))(x, y)
    g2 = jax.grad(lambda x, y: jnp.sum(
        jnp.sin(jax.lax.dot_general(x, y, dn))), (0, 1))(x, y)
    for got, want in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-9, atol=1e-12)


def test_jit_vmap_compose(rng):
    """vmap over an already-batched emulated contraction, under jit."""
    a = phi_tensor(rng, (2, 3, 8, 16))
    b = phi_tensor(rng, (3, 16, 5))
    cfg = VARIANTS["ozimmu_h"].with_(k=6)
    dn = (((2,), (1,)), ((0,), (0,)))
    f = jax.jit(jax.vmap(lambda x: ozimmu_dot_general(x, b, dn, cfg)))
    out = f(a)
    ref = jnp.einsum("vbmn,bnp->vbmp", a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-8)


def test_pallas_batched_path_matches_jnp(rng):
    """The Pallas group-GEMM kernel's batch grid axis is bit-identical to
    the pure-jnp batched path."""
    a = phi_tensor(rng, (2, 40, 64), dtype=np.float32)
    b = phi_tensor(rng, (2, 64, 24), dtype=np.float32)
    dn = (((2,), (1,)), ((0,), (0,)))
    for variant in ("ozimmu_ef", "ozimmu_h"):
        cfg = VARIANTS[variant].with_(k=5, accum_dtype="f32")
        c_jnp = np.asarray(ozimmu_dot_general(a, b, dn, cfg))
        c_pl = np.asarray(ozimmu_dot_general(
            a, b, dn, cfg.with_(use_pallas=True)))
        np.testing.assert_array_equal(c_pl, c_jnp)


def test_engine_batched_no_reshape(rng):
    """MatmulEngine handles leading dims as dot_general free dims and true
    batched contractions via .dot_general — no flatten-to-2D on either."""
    x = jnp.asarray(make_phi_matrix(rng, 4 * 6, 32, dtype=np.float32)
                    .reshape(4, 6, 32))
    w = jnp.asarray(make_phi_matrix(rng, 32, 16, dtype=np.float32))
    ref = np.asarray(jnp.einsum("abi,ij->abj", x.astype(jnp.float64),
                                w.astype(jnp.float64)))
    for spec in ("f32", "ozimmu_h-6:f32", "ozimmu_h-6:df32"):
        out = np.asarray(make_engine(spec)(x, w), np.float64)
        rel = np.abs(out - ref) / (np.abs(ref) + 1e-6)
        assert rel.max() < 5e-5, (spec, rel.max())

    # true batched rhs — impossible for the old reshape-to-2D engine
    wb = jnp.asarray(make_phi_matrix(rng, 4 * 32, 16, dtype=np.float32)
                     .reshape(4, 32, 16))
    dn = (((2,), (1,)), ((0,), (0,)))
    refb = np.asarray(jnp.einsum("bli,bij->blj", x.astype(jnp.float64),
                                 wb.astype(jnp.float64)))
    for spec in ("f32", "ozimmu_h-6:df32", "ozimmu_ef-6:f32"):
        out = np.asarray(make_engine(spec).dot_general(x, wb, dn), np.float64)
        rel = np.abs(out - refb) / (np.abs(refb) + 1e-6)
        assert rel.max() < 5e-5, (spec, rel.max())


def test_dnum_validation():
    a = jnp.zeros((3, 4, 5))
    b = jnp.zeros((3, 6, 7))
    with pytest.raises(ValueError):
        ozimmu_dot_general(a, b, (((2,), (1,)), ((0,), (0,))))
    with pytest.raises(ValueError):
        ozimmu_dot_general(a, b, (((2,), (1,), (0,)), ((0,), (0,))))
    with pytest.raises(ValueError):
        ozimmu_matmul(a, b)


def _dot_precisions(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [e.params["precision"] for e in jaxpr.eqns
            if e.primitive.name == "dot_general"]


@pytest.mark.parametrize("spec", ["f32", "f64"])
def test_native_reference_engines_ask_highest_precision(spec):
    """The f32/f64 reference engines carry Precision.HIGHEST in their dots
    (a TPU would otherwise run a one-pass bf16 product); bf16 and the
    engine-less attention dots keep the backend default."""
    from repro.models import layers
    hi = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    x = jnp.ones((2, 8), jnp.float32)
    w = jnp.ones((8, 4), jnp.float32)
    dn = (((1,), (0,)), ((), ()))
    eng = make_engine(spec)
    assert _dot_precisions(eng, x, w) == [hi]
    assert _dot_precisions(
        lambda a, b: layers._edot(eng, a, b, dn), x, w) == [hi]
    assert _dot_precisions(make_engine("bf16"), x, w) == [None]
    assert _dot_precisions(
        lambda a, b: layers._edot(None, a, b, dn), x, w) == [None]

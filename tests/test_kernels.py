"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles, with
shape/dtype sweeps, plus end-to-end equivalence against the core library."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import hypothesis_or_stubs

given, settings, st = hypothesis_or_stubs()

from repro.core import (OzimmuConfig, VARIANTS, ozimmu_matmul, compute_beta,
                        split_bitmask, split_rn_const)
from repro.core.ozimmu import split_operands
from repro.kernels import ops, ref
from repro.kernels.split_fused import split_fused as raw_split
from repro.kernels.group_gemm import group_gemm as raw_group_gemm
from repro.kernels.scale_accum import scale_accum as raw_scale_accum
from tests.conftest import make_phi_matrix


# ---------------------------------------------------------------------------
# split_fused
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["bitmask", "rn_const"])
@pytest.mark.parametrize("m,n", [(8, 128), (16, 256), (256, 512), (264, 640)])
def test_split_fused_matches_ref(rng, mode, m, n):
    k, beta = 5, 7
    a = jnp.asarray(make_phi_matrix(rng, m, n, phi=1.0, dtype=np.float32))
    rowmax = jnp.max(jnp.abs(a), axis=1, keepdims=True)
    from repro.core.splitting import _pow2_ceil, _pow2_floor
    if mode == "bitmask":
        inv = (2.0 ** beta) / (2.0 * _pow2_floor(rowmax))
    else:
        inv = 1.0 / (_pow2_ceil(rowmax) * 2.0 ** (1 - beta))
    bm = 8 if m <= 8 else 16
    bn = 128
    a_p = ops._pad_to(a, (bm, bn))
    inv_p = ops._pad_to(inv, (bm, 1))
    got = raw_split(a_p, inv_p, k=k, beta=beta, mode=mode, bm=bm, bn=bn,
                    interpret=True)
    want = ref.split_fused_ref(a_p, inv_p, k=k, beta=beta, mode=mode)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mode,lib", [("bitmask", split_bitmask),
                                      ("rn_const", split_rn_const)])
def test_split_fused_matches_library(rng, mode, lib):
    """The kernel path must produce the SAME digits as the core splitters
    (both axes), since they implement the same algorithm."""
    a = jnp.asarray(make_phi_matrix(rng, 48, 160, dtype=np.float32))
    k = 4
    beta = compute_beta(160)
    for axis in (0, 1):
        sp_k = ops.split_fused(a, k, beta, mode=mode, axis=axis)
        sp_l = lib(a, k, beta=beta, axis=axis)
        np.testing.assert_array_equal(np.asarray(sp_k.digits),
                                      np.asarray(sp_l.digits))
        np.testing.assert_allclose(np.asarray(sp_k.scale),
                                   np.asarray(sp_l.scale), rtol=0)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 40), n=st.integers(1, 300), k=st.integers(1, 6),
       seed=st.integers(0, 2**31), mode=st.sampled_from(["bitmask", "rn_const"]))
def test_split_fused_property_padding(m, n, k, seed, mode):
    """Arbitrary (unaligned) shapes: ops.split_fused == library splitter."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(make_phi_matrix(rng, m, n, dtype=np.float32))
    beta = 7
    lib = split_bitmask if mode == "bitmask" else split_rn_const
    sp_k = ops.split_fused(a, k, beta, mode=mode)
    sp_l = lib(a, k, beta=beta)
    np.testing.assert_array_equal(np.asarray(sp_k.digits),
                                  np.asarray(sp_l.digits))


# ---------------------------------------------------------------------------
# group_gemm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G,m,n,p", [(1, 128, 128, 128), (3, 128, 256, 128),
                                     (7, 256, 128, 384)])
def test_group_gemm_matches_ref(rng, G, m, n, p):
    a8 = jnp.asarray(rng.integers(-127, 128, (G, m, n)), jnp.int8)
    b8 = jnp.asarray(rng.integers(-127, 128, (G, n, p)), jnp.int8)
    idx = jnp.arange(G, dtype=jnp.int32)
    got = raw_group_gemm(idx, idx, a8, b8, bm=128, bp=128, bn=128,
                         interpret=True)
    want = ref.group_gemm_ref(a8, b8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# slice-pair lists of k = 6 splits as the accumulators hand them over
_PAIR_LISTS = {
    # one anti-diagonal group (g = 7) chunked at r = 4
    "antidiagonal_cut_at_r": [(1, 6), (2, 5), (3, 4), (4, 3)],
    # the oz2 fast band s + t <= k + 1 as one list
    "oz2_fast_band": [(s, t) for s in range(1, 7) for t in range(1, 8 - s)],
    "single_pair": [(3, 3)],
    "descending_t": [(1, 6), (2, 4), (4, 2), (6, 1)],
}
# (batch, m, n, p, sign-magnitude digits)
_PAIR_SHAPES = {
    "unbatched": ((), 40, 256, 128, False),
    "batched": ((12, 8), 2, 16, 8, False),
    "padded": ((), 12, 200, 1088, False),
    "signmag_bf16": ((), 20, 300, 50, True),
}


@pytest.mark.parametrize("shape", list(_PAIR_SHAPES))
@pytest.mark.parametrize("pairs", list(_PAIR_LISTS))
def test_group_gemm_pair_lists(pairs, shape):
    """ops.group_gemm reads each slice pair from the split's own stacks
    and matches the int64 sum of A_s @ B_t, batched, padded and on
    sign-magnitude digits alike."""
    from repro.core.splitting import Split, sm_decode
    batch, m, n, p, signmag = _PAIR_SHAPES[shape]
    pairs = _PAIR_LISTS[pairs]
    rng = np.random.default_rng(sum(map(ord, shape)))
    a8 = jnp.asarray(rng.integers(-128, 128, (6,) + batch + (m, n)), jnp.int8)
    b8 = jnp.asarray(rng.integers(-128, 128, (6,) + batch + (n, p)), jnp.int8)
    sa = Split(a8, None, None, 7, 0, signmag=signmag)
    sb = Split(b8, None, None, 7, 1, signmag=signmag)
    got = np.asarray(ops.group_gemm(sa, sb, pairs), np.int64)
    da = np.asarray(sm_decode(a8) if signmag else a8, np.int64)
    db = np.asarray(sm_decode(b8) if signmag else b8, np.int64)
    want = sum(da[s - 1] @ db[t - 1] for s, t in pairs)
    assert got.shape == batch + (m, p)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=8, deadline=None)
@given(G=st.integers(1, 5), m=st.integers(1, 150), n=st.integers(1, 200),
       p=st.integers(1, 150), seed=st.integers(0, 2**31))
def test_group_gemm_property_unaligned(G, m, n, p, seed):
    """ops.group_gemm pads arbitrary shapes and matches the int64 oracle."""
    rng = np.random.default_rng(seed)
    from repro.core.splitting import Split
    a8 = jnp.asarray(rng.integers(-64, 65, (3, m, n)), jnp.int8)
    b8 = jnp.asarray(rng.integers(-64, 65, (3, n, p)), jnp.int8)
    sa = Split(a8, None, None, 7, 0)
    sb = Split(b8, None, None, 7, 1)
    pairs = [(s + 1, 3 - s) for s in range(min(G, 2) + 1)][:G] or [(1, 1)]
    pairs = [(s, t) for s, t in pairs if s <= 3 and t <= 3]
    got = np.asarray(ops.group_gemm(sa, sb, pairs), np.int64)
    want = np.zeros((m, p), np.int64)
    for s, t in pairs:
        want += np.asarray(a8[s - 1], np.int64) @ np.asarray(b8[t - 1], np.int64)
    np.testing.assert_array_equal(got, want)


def test_group_gemm_no_int32_overflow_at_r_limit(rng):
    """Adversarial: G = r pairs of max-magnitude digits must NOT overflow."""
    n = 128
    beta = compute_beta(n)  # 7
    from repro.core import compute_r
    r = compute_r(n, beta)
    G = min(r, 8)
    a8 = jnp.full((G, 8, n), 127, jnp.int8)
    b8 = jnp.full((G, n, 8), 127, jnp.int8)
    idx = jnp.arange(G, dtype=jnp.int32)
    got = np.asarray(raw_group_gemm(
        idx, idx, ops._pad_to(a8, (1, 128, 128)),
        ops._pad_to(b8, (1, 128, 128)),
        bm=128, bp=128, bn=128, interpret=True), np.int64)[:8, :8]
    want = G * n * 127 * 127
    assert want < 2**31
    np.testing.assert_array_equal(got, np.full((8, 8), want))


# ---------------------------------------------------------------------------
# scale_accum
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,p", [(8, 128), (256, 512), (100, 300)])
def test_scale_accum_matches_ref(rng, m, p):
    p32 = jnp.asarray(rng.integers(-2**30, 2**30, (m, p)), jnp.int32)
    srow = jnp.asarray(2.0 ** rng.integers(-20, 20, (m,)), jnp.float32)
    scol = jnp.asarray(2.0 ** rng.integers(-20, 20, (p,)), jnp.float32)
    c_hi = jnp.asarray(rng.standard_normal((m, p)), jnp.float32)
    c_lo = jnp.asarray(rng.standard_normal((m, p)) * 1e-7, jnp.float32)
    hi, lo = ops.scale_accum(p32, srow, scol, c_hi, c_lo)
    whi, wlo = ref.scale_accum_ref(p32, srow[:, None], scol[None, :], c_hi, c_lo)
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(whi))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(wlo))


@pytest.mark.parametrize("batch", [(3,), (2, 2)])
def test_scale_accum_batched_matches_ref(rng, batch):
    """Leading batch dims map onto the kernel's batch grid axis with
    per-batch scale vectors."""
    m, p = 24, 140
    p32 = jnp.asarray(rng.integers(-2**30, 2**30, batch + (m, p)), jnp.int32)
    srow = jnp.asarray(2.0 ** rng.integers(-10, 10, batch + (m,)), jnp.float32)
    scol = jnp.asarray(2.0 ** rng.integers(-10, 10, batch + (p,)), jnp.float32)
    c_hi = jnp.asarray(rng.standard_normal(batch + (m, p)), jnp.float32)
    c_lo = jnp.asarray(rng.standard_normal(batch + (m, p)) * 1e-7, jnp.float32)
    hi, lo = ops.scale_accum(p32, srow, scol, c_hi, c_lo)
    whi, wlo = ref.scale_accum_ref(p32, srow[..., :, None],
                                   scol[..., None, :], c_hi, c_lo)
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(whi))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(wlo))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
@pytest.mark.parametrize("m,p", [(8, 128), (100, 300)])
def test_scale_accum_plain_matches_ref(rng, dtype, m, p):
    """The plain-accumulator kernel mode (f64 interpret / f32) equals the
    inline epilogue in the accumulator's own dtype."""
    p32 = jnp.asarray(rng.integers(-2**30, 2**30, (m, p)), jnp.int32)
    srow = jnp.asarray(2.0 ** rng.integers(-20, 20, (m,)), dtype)
    scol = jnp.asarray(2.0 ** rng.integers(-20, 20, (p,)), dtype)
    c = jnp.asarray(rng.standard_normal((m, p)), dtype)
    got = ops.scale_accum_plain(p32, srow, scol, c)
    want = ref.scale_accum_plain_ref(p32, srow[:, None], scol[None, :], c)
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("batch", [(), (3,)])
def test_scale_accum_const_matches_jnp_epilogue(rng, batch):
    """The constant-grid (oz2 ladder) df32 kernel is bit-identical to the
    inline `accumulate._oz2_accum_df32` epilogue, rank-2 and batched."""
    from repro.core.accumulate import DF32, _oz2_accum_df32
    m, p = 24, 140
    word = jnp.asarray(rng.integers(-2**30, 2**30, batch + (m, p)), jnp.int32)
    s = jnp.asarray(2.0 ** rng.integers(-10, 10, batch), jnp.float32)
    c_hi = jnp.asarray(rng.standard_normal(batch + (m, p)), jnp.float32)
    c_lo = jnp.asarray(rng.standard_normal(batch + (m, p)) * 1e-7, jnp.float32)
    hi, lo = ops.oz2_scale_accum(word, s, c_hi, c_lo)
    want = _oz2_accum_df32(word, s, DF32(c_hi, c_lo))
    np.testing.assert_array_equal(np.asarray(hi), np.asarray(want.hi))
    np.testing.assert_array_equal(np.asarray(lo), np.asarray(want.lo))


@pytest.mark.parametrize("word_dtype", [jnp.int32, jnp.int64])
@pytest.mark.parametrize("acc_dtype", [jnp.float32, jnp.float64])
def test_scale_accum_const_plain_matches_jnp(rng, word_dtype, acc_dtype):
    """The plain const kernel accepts int32 AND int64 ladder words (the
    f64/x64 exponent ladder) and equals the inline epilogue bitwise."""
    from repro.core.accumulate import _oz2_accum_plain
    m, p = 16, 130
    word = jnp.asarray(rng.integers(-2**50, 2**50, (m, p)), word_dtype)
    s = jnp.asarray(2.0 ** rng.integers(-10, 10, ()), acc_dtype)
    c = jnp.asarray(rng.standard_normal((m, p)), acc_dtype)
    got = ops.oz2_scale_accum_plain(word, s, c)
    want = _oz2_accum_plain(word, s, c)
    assert got.dtype == acc_dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_split_fused_const_grid_matches_library(rng):
    """The const-grid kernel mode (one (1,1) scalar operand) produces the
    same digits/scales as the library oz2 splitters, both axes, f32/f64,
    batched (where the scalar broadcasts onto the row grid)."""
    from repro.core.splitting import split_oz2, split_oz2_bitmask
    k, n = 4, 160
    beta = compute_beta(n)
    for lib, mode in ((split_oz2, "oz2_rn"),
                      (split_oz2_bitmask, "oz2_bitmask")):
        for shape, dtype in (((48, n), np.float32), ((48, n), np.float64),
                             ((2, 24, n), np.float32)):
            a = jnp.asarray(make_phi_matrix(
                rng, int(np.prod(shape[:-1])), n,
                dtype=dtype).reshape(shape))
            for axis in (0, 1):
                x = a if axis == 0 else jnp.swapaxes(a, -1, -2)
                sp_k = ops.split_fused(x, k, beta, mode=mode, axis=axis)
                sp_l = lib(x, k, beta=beta, axis=axis)
                np.testing.assert_array_equal(np.asarray(sp_k.digits),
                                              np.asarray(sp_l.digits))
                np.testing.assert_array_equal(np.asarray(sp_k.gbase),
                                              np.asarray(sp_l.gbase))
                np.testing.assert_array_equal(np.asarray(sp_k.scale),
                                              np.asarray(sp_l.scale))


@pytest.mark.parametrize("mode,lib", [("bitmask", split_bitmask),
                                      ("rn_const", split_rn_const)])
def test_split_fused_f64_and_batched_matches_library(rng, mode, lib):
    """The fused splitter preserves f64 through the interpret path (the
    paper-faithful DGEMM emulation needs digits beyond f32's 24 bits) and
    flattens batch dims without changing any digit."""
    a64 = jnp.asarray(make_phi_matrix(rng, 40, 96, dtype=np.float64))
    k, beta = 9, 7  # k*beta = 63 bits > f32 mantissa: catches an f32 cast
    for axis in (0, 1):
        sp_k = ops.split_fused(a64, k, beta, mode=mode, axis=axis)
        sp_l = lib(a64, k, beta=beta, axis=axis)
        assert sp_k.digits.dtype == jnp.int8 and sp_k.scale.dtype == a64.dtype
        np.testing.assert_array_equal(np.asarray(sp_k.digits),
                                      np.asarray(sp_l.digits))
        np.testing.assert_array_equal(np.asarray(sp_k.scale),
                                      np.asarray(sp_l.scale))
    ab = jnp.asarray(make_phi_matrix(rng, 6 * 20, 64,
                                     dtype=np.float32).reshape(2, 3, 20, 64))
    for axis in (0, 1):
        sp_k = ops.split_fused(ab, 5, beta, mode=mode, axis=axis)
        sp_l = lib(ab, 5, beta=beta, axis=axis)
        np.testing.assert_array_equal(np.asarray(sp_k.digits),
                                      np.asarray(sp_l.digits))
        np.testing.assert_array_equal(np.asarray(sp_k.scale),
                                      np.asarray(sp_l.scale))


def test_scale_accum_compensation_beats_naive(rng):
    """df32 accumulation keeps bits a plain f32 accumulator loses."""
    m = p = 8
    c_hi = jnp.full((m, p), 1e8, jnp.float32)
    c_lo = jnp.zeros((m, p), jnp.float32)
    p32 = jnp.full((m, p), 3, jnp.int32)
    one_r = jnp.ones((m,), jnp.float32)
    one_c = jnp.ones((p,), jnp.float32)
    hi, lo = ops.scale_accum(p32, one_r, one_c, c_hi, c_lo)
    total = np.asarray(hi, np.float64) + np.asarray(lo, np.float64)
    np.testing.assert_array_equal(total, np.full((m, p), 1e8 + 3.0))
    naive = np.asarray(c_hi) + np.float32(3.0)
    assert not np.array_equal(naive, np.full((m, p), 1e8 + 3.0))  # f32 lost it


# ---------------------------------------------------------------------------
# end-to-end: full ozimmu through the Pallas path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["ozimmu_ef", "ozimmu_h"])
def test_pallas_path_matches_jnp_path(rng, variant):
    a = jnp.asarray(make_phi_matrix(rng, 96, 160, dtype=np.float32))
    b = jnp.asarray(make_phi_matrix(rng, 160, 64, dtype=np.float32))
    cfg = VARIANTS[variant].with_(k=5, accum_dtype="f32")
    c_jnp = np.asarray(ozimmu_matmul(a, b, cfg))
    c_pl = np.asarray(ozimmu_matmul(a, b, cfg.with_(use_pallas=True)))
    np.testing.assert_array_equal(c_pl, c_jnp)

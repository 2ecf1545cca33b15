"""Compile-only checks of the Pallas kernels for a described TPU v5e.

Nothing runs on a chip: each test lowers a kernel wrapper of
``repro.kernels.ops`` (or a whole emulated contraction) for a v5e chip
that ``jax.experimental.topologies`` describes, compiles it with the TPU
compiler, and asserts that the Mosaic kernel (``tpu_custom_call``) is in
the compiled program.  This is what interpret mode cannot check: tiles
aligned to the hardware tiling, the VMEM budget, and the dtypes Mosaic
lowers.  Shapes are the real widths of internlm2_1_8b's MLP
(2048 x 2048 @ 2048 x 8192) and a 4-row decode step.

The topology is described inside a module-scoped fixture, which skips
where it cannot be described; nothing touches the TPU library while the
module is imported.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import make_engine, parse_spec, splitting
from repro.core.ozimmu import ozimmu_dot_general
from repro.kernels import ops

M, N, P = 2048, 2048, 8192
K = 6                       # the planner's static k for f32 at n = 2048
DN = (((1,), (0,)), ((), ()))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [4, M])
@pytest.mark.parametrize("mode", ["rn_const", "bitmask", "sm"])
def test_split_fused_compiles(one_chip, mode, rows):
    beta = splitting.beta_for(mode, N)

    def fn(a):
        return ops.split_fused(a, K, beta, mode=mode).digits

    _assert_kernel(_compiled_text(fn, one_chip, ((rows, N), jnp.float32)))


def _split(digits, beta, axis, signmag=False):
    k, r = digits.shape[0], digits.shape[-2 + axis]
    scale = jnp.ones((k, r), jnp.float32)
    return splitting.Split(digits, scale, scale[0], beta, axis,
                           signmag=signmag)


@pytest.mark.parametrize("signmag", [False, True])
def test_group_gemm_compiles(one_chip, signmag):
    beta = splitting.compute_beta_sm(N) if signmag \
        else splitting.compute_beta(N)
    pairs = [(s, K + 1 - s) for s in range(1, K + 1)]

    def fn(da, db):
        return ops.group_gemm(_split(da, beta, 0, signmag),
                              _split(db, beta, 1, signmag), pairs)

    _assert_kernel(_compiled_text(fn, one_chip, ((K, M, N), jnp.int8),
                                  ((K, N, P), jnp.int8)))


def test_group_gemm_reads_pairs_in_place(one_chip):
    """A contraction's group GEMMs read their slice pairs straight from
    the split's digit stacks: ahead of the kernels the lowered program
    has no gather and no transpose, and at most one pad per operand for
    all six anti-diagonal groups (m = 12 and p = 1088 need padding).
    The kernel keeps its instruction name, which the trace reader
    finds it by."""
    beta = splitting.compute_beta(N)
    chunks = [[(s, g - s) for s in range(1, g)] for g in range(2, K + 2)]

    def fn(da, db):
        gemm = ops.group_gemm_fn(_split(da, beta, 0), _split(db, beta, 1))
        return [gemm(pairs) for pairs in chunks]

    args = [jax.ShapeDtypeStruct(s, jnp.int8, sharding=one_chip)
            for s in ((K, 12, N), (K, N, 1088))]
    lowered = jax.jit(fn).lower(*args)
    text = lowered.as_text()
    assert text.count("tpu_custom_call") == len(chunks)
    assert "stablehlo.gather" not in text
    assert "stablehlo.transpose" not in text
    assert text.count("stablehlo.pad") <= 2
    compiled = lowered.compile().as_text()
    assert len(re.findall(r"%group_gemm\.\d+ = \S+ custom-call",
                          compiled)) == len(chunks)


def test_scale_accum_compiles(one_chip):
    _assert_kernel(_compiled_text(
        ops.scale_accum, one_chip, ((M, P), jnp.int32), ((M,), jnp.float32),
        ((P,), jnp.float32), ((M, P), jnp.float32), ((M, P), jnp.float32)))


def test_scale_accum_const_compiles(one_chip):
    def fn(word, s, hi, lo):
        return ops.oz2_scale_accum(word, s, hi, lo)

    _assert_kernel(_compiled_text(
        fn, one_chip, ((M, P), jnp.int32), ((), jnp.float32),
        ((M, P), jnp.float32), ((M, P), jnp.float32)))


def test_unscale_compiles(one_chip):
    _assert_kernel(_compiled_text(
        ops.oz2_unscale, one_chip, ((M, P), jnp.float32),
        ((M,), jnp.float32), ((P,), jnp.float32)))


def test_fused_dot_general_compiles(one_chip):
    cfg = parse_spec("ozimmu_h-auto:df32:fused")

    def fn(a, b):
        return ozimmu_dot_general(a, b, DN, cfg)

    _assert_kernel(_compiled_text(fn, one_chip, ((M, N), jnp.float32),
                                  ((N, P), jnp.float32)))


def test_sharded_int32_product_reduce_holds_no_stack(topo):
    """``ozimmu_h-auto:df32:fused@model/int32`` over the described host's
    four chips at 32768^3 (k = 6, 8 groups): each group's int32 product is
    reduced by rows as soon as it exists and C comes back row-sharded, so
    the program's temporaries stay far below the 8 (m, p) int32 products
    that a stacked reduction holds (34 GB a chip, more than the chip).
    The compiler lowers each int32 reduce-scatter as an all-reduce of one
    column block of the product and a slice of its rows."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.core import plan
    mesh = Mesh(np.array(topo.devices), ("model",),
                axis_types=(AxisType.Auto,))
    m = n = p = 32768
    cfg = parse_spec("ozimmu_h-auto:df32:fused@model/int32")
    args = [jax.ShapeDtypeStruct((m, n), jnp.float32, sharding=NamedSharding(
                mesh, PartitionSpec(None, "model"))),
            jax.ShapeDtypeStruct((n, p), jnp.float32, sharding=NamedSharding(
                mesh, PartitionSpec("model", None)))]
    with jax.set_mesh(mesh):
        compiled = jax.jit(lambda a, b: ozimmu_dot_general(
            a, b, DN, cfg)).lower(*args).compile()
    groups = plan.plan_contraction(cfg, m, n, p, a=args[0],
                                   _record=False).highprec_adds
    assert groups == 8
    assert compiled.memory_analysis().temp_size_in_bytes < groups * m * p * 4
    assert compiled.output_shardings.is_equivalent_to(
        NamedSharding(mesh, PartitionSpec("model", None)), 2)
    text = compiled.as_text()
    _assert_kernel(text)
    assert not re.search(r"s32\[8,32768,32768\]", text)


@pytest.mark.parametrize("spec", ["ozimmu_h-auto", "oz2_h-auto:fast2"])
def test_f64_xla_path_not_compiled_for_v5e(one_chip, spec):
    """The f64 XLA path (f64 operands, x64 on) does not compile for a v5e:
    the TPU compiler cannot rewrite the splitter's s64 bitcast-convert.
    If this starts failing, the f64 path runs on a v5e."""
    assert jax.config.jax_enable_x64
    cfg = parse_spec(spec)
    with pytest.raises(Exception, match="X64 element types"):
        _compiled_text(lambda a, b: ozimmu_dot_general(a, b, DN, cfg),
                       one_chip, ((256, 256), jnp.float64),
                       ((256, 256), jnp.float64))


@pytest.mark.parametrize("spec", ["ozimmu_h-auto:fused",
                                  "oz2_h-auto:fast:f64:fused",
                                  "ozimmu_sm_h-8:fused@model"])
def test_fused_f64_spec_refused_on_tpu(monkeypatch, spec):
    """On a TPU backend an f64-accumulator ``:fused`` spec fails at parse
    time, naming the spec; ``:df32`` and the XLA path stay accepted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="cannot run on a TPU") as err:
        parse_spec(spec)
    assert repr(spec) in str(err.value)
    with pytest.raises(ValueError, match="cannot run on a TPU"):
        make_engine(spec)
    parse_spec(spec.replace(":fused", ":df32:fused").replace(":f64", ""))
    parse_spec(spec.replace(":fused", ""))

"""Distributed tests — run in a SUBPROCESS with 8 forced host devices (the
main test process keeps the single real CPU device; jax locks device count
at first init).

Covers: sharded train step on the (data, model) and (pod, data, model)
meshes, sharded-vs-single-device numerical parity, ZeRO-1 state sharding,
int8+error-feedback compressed all-reduce inside shard_map, and a
mini multi-pod dry-run (lower+compile) for one cell per family.
"""
import os
import subprocess
import sys
import textwrap

import pytest

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, timeout=900, x64=False, devices=8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC
    env.pop("JAX_ENABLE_X64", None)
    if x64:  # must be set before jax initializes in the subprocess
        env["JAX_ENABLE_X64"] = "true"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


def test_sharded_train_step_matches_single_device():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import configs, optim
        from repro.launch import steps as S
        from repro.launch.mesh import make_test_mesh, mesh_rules
        from repro.distributed.sharding import use_rules
        from jax import set_mesh

        arch = "internlm2_1_8b"
        cfg = configs.get_config(arch, smoke=True)
        opt_cfg = optim.OptConfig(lr=1e-2, warmup_steps=0, total_steps=10,
                                  min_lr_frac=1.0)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                    cfg.vocab, dtype=jnp.int32)
        batch = {"tokens": tokens}

        def run(mesh):
            rules = mesh_rules(mesh, arch) if mesh else None
            import contextlib
            ctx = set_mesh(mesh) if mesh else contextlib.nullcontext()
            with ctx, use_rules(rules):
                state, axes, opt_axes = S.init_state(
                    jax.random.PRNGKey(0), cfg, opt_cfg)
                step = jax.jit(S.make_train_step(
                    cfg, opt_cfg, S.TrainConfig(microbatches=2),
                    opt_axes=opt_axes))
                losses = []
                for i in range(3):
                    state, m = step(state, batch)
                    losses.append(float(m["loss"]))
            return losses

        l_single = run(None)
        l_mesh = run(make_test_mesh(data=2, model=2))
        l_pod = run(make_test_mesh(data=2, model=2, pod=2))
        print("losses", l_single, l_mesh, l_pod)
        np.testing.assert_allclose(l_single, l_mesh, rtol=2e-2)
        np.testing.assert_allclose(l_single, l_pod, rtol=2e-2)
        assert l_single[2] < l_single[0]  # it learns
        print("OK")
    """)


def test_sharded_decode_matches_forward():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import configs
        from repro.models import api
        from repro.launch.mesh import make_test_mesh, mesh_rules
        from repro.distributed.sharding import use_rules
        from jax import set_mesh

        arch = "recurrentgemma_9b"   # hybrid: ring buffers + LRU state
        cfg = configs.get_config(arch, smoke=True)
        model = api.get_model(cfg)
        mesh = make_test_mesh(data=2, model=2)
        with set_mesh(mesh), use_rules(mesh_rules(mesh, arch)):
            params, _ = model.init(jax.random.PRNGKey(0), cfg)
            B, L = 4, 8
            tokens = jax.random.randint(jax.random.PRNGKey(1), (B, L), 0,
                                        cfg.vocab, dtype=jnp.int32)
            ref = model.forward(params, cfg, {"tokens": tokens})
            cache = model.init_cache(cfg, B, L)
            step = jax.jit(lambda c, t, n: model.decode_step(
                params, cfg, c, t, n))
            outs = []
            for t in range(L):
                logits, cache = step(cache, tokens[:, t:t+1],
                                     jnp.asarray(t+1, jnp.int32))
                outs.append(logits[:, 0])
            got = jnp.stack(outs, axis=1)
            err = float(jnp.max(jnp.abs(got - ref)) /
                        (jnp.max(jnp.abs(ref)) + 1e-9))
            print("decode err", err)
            assert err < 5e-2
        print("OK")
    """)


def test_compressed_psum_shard_map():
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.optim import compress
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(data=8, model=1)
        g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 16, 32))

        def body(g, err):
            g = g[0]; err = err[0]
            mean, new_err = compress.compressed_psum(
                {"w": g}, {"w": err}, "data")
            return mean["w"][None], new_err["w"][None]

        fn = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data"))))
        err0 = jnp.zeros_like(g_global)
        mean, err1 = fn(g_global, err0)
        true_mean = jnp.mean(g_global, axis=0)
        # every shard holds the same mean; int8 quantization error is
        # bounded by scale/2 <= rowmax * 2^-β (β=7 ⇒ <1% of rowmax)
        got = mean[0]
        tol = float(jnp.max(jnp.abs(g_global))) * 2.0 ** -6
        assert float(jnp.max(jnp.abs(got - true_mean))) < tol
        # error feedback: residual + transmitted == local contribution
        print("OK")
    """)


@pytest.mark.slow
def test_mini_dryrun_lower_compile_families():
    """Lower+compile a reduced train cell AND a decode cell on the 8-device
    multi-pod test mesh for one arch per distinct family."""
    run_sub("""
        import jax, jax.numpy as jnp
        from repro import configs, optim
        from repro.launch import steps as S
        from repro.launch.mesh import make_test_mesh, mesh_rules
        from repro.distributed.sharding import use_rules, spec_tree
        from jax import set_mesh
        from repro.models import api
        from jax.sharding import NamedSharding, PartitionSpec as P

        for arch in ("phi4_mini_3_8b", "deepseek_moe_16b", "mamba2_780m",
                     "seamless_m4t_medium", "llama32_vision_11b"):
            cfg = configs.get_config(arch, smoke=True)
            model = api.get_model(cfg)
            mesh = make_test_mesh(data=2, model=2, pod=2)
            rules = mesh_rules(mesh, arch)
            with set_mesh(mesh), use_rules(rules):
                opt_cfg = optim.OptConfig()
                pshapes, axes = S.params_shapes(cfg)
                opt_axes = optim.zero_axes(axes, pshapes, 2)
                step = S.make_train_step(cfg, opt_cfg,
                                         S.TrainConfig(microbatches=2),
                                         opt_axes=opt_axes)
                state, _, _ = S.init_state(jax.random.PRNGKey(0), cfg,
                                           opt_cfg, zero_divisor=2)
                B, L = 8, 32
                batch = {"tokens": jnp.zeros((B, L), jnp.int32)}
                if cfg.family == "vlm":
                    batch["image_embeds"] = jnp.zeros(
                        (B, cfg.vision_seq, cfg.d_model), jnp.float32)
                if cfg.family == "encdec":
                    batch["frames"] = jnp.zeros((B, L, cfg.d_model),
                                                jnp.float32)
                lowered = jax.jit(step).lower(state, batch)
                compiled = lowered.compile()
                assert compiled.memory_analysis() is not None
                print(arch, "train lower+compile OK")
        print("OK")
    """, timeout=1200)


def test_moe_a2a_dispatch_matches_scatter():
    """The shard_map all-to-all MoE dispatch is bit-identical to the GSPMD
    scatter path (values and grads) when capacity is not binding."""
    run_sub("""
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.models import moe
        from repro.launch.mesh import make_test_mesh, mesh_rules
        from repro.distributed.sharding import use_rules
        from jax import set_mesh

        cfg = configs.get_config("deepseek_moe_16b", smoke=True,
                                 capacity_factor=4.0)
        mesh = make_test_mesh(data=4, model=2)
        with set_mesh(mesh), use_rules(mesh_rules(mesh, "deepseek_moe_16b")):
            p, _ = moe.init_moe_ffn(jax.random.PRNGKey(0), cfg)
            x = jax.random.normal(jax.random.PRNGKey(1),
                                  (8, 16, cfg.d_model), jnp.float32)
            y1 = jax.jit(lambda p, x: moe.moe_ffn(p, cfg, x))(p, x)
            y2 = jax.jit(lambda p, x: moe.moe_ffn_a2a(p, cfg, x))(p, x)
            assert float(jnp.max(jnp.abs(y1 - y2))) < 1e-4
            g1 = jax.jit(jax.grad(
                lambda p: jnp.sum(moe.moe_ffn(p, cfg, x)**2)))(p)
            g2 = jax.jit(jax.grad(
                lambda p: jnp.sum(moe.moe_ffn_a2a(p, cfg, x)**2)))(p)
            for k in g1:
                if k == "shared":
                    continue
                e = float(jnp.max(jnp.abs(g1[k] - g2[k])))
                m = float(jnp.max(jnp.abs(g1[k]))) + 1e-9
                assert e < 5e-3 * m, (k, e)  # bf16 engine noise
        print("OK")
    """)


def test_elastic_restore_across_meshes():
    """A checkpoint saved from a (4,2) mesh restores onto a (2,2) mesh
    (elastic reshard-on-restore) with identical values."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import configs, optim
        from repro.checkpoint import Checkpointer
        from repro.launch import steps as S
        from repro.launch.mesh import make_test_mesh, mesh_rules
        from repro.distributed.sharding import use_rules, spec_tree
        from jax import set_mesh
        import tempfile

        arch = "internlm2_1_8b"
        cfg = configs.get_config(arch, smoke=True)
        opt_cfg = optim.OptConfig()
        d = tempfile.mkdtemp()

        mesh_a = make_test_mesh(data=4, model=2)
        with set_mesh(mesh_a), use_rules(mesh_rules(mesh_a, arch)):
            state, axes, _ = S.init_state(jax.random.PRNGKey(0), cfg,
                                          opt_cfg, zero_divisor=4)
            Checkpointer(d).save(7, state, blocking=True)
            ref = np.asarray(state.params["embed"])

        mesh_b = make_test_mesh(data=2, model=2)
        with set_mesh(mesh_b), use_rules(mesh_rules(mesh_b, arch)):
            state_b, axes_b, _ = S.init_state(jax.random.PRNGKey(1), cfg,
                                              opt_cfg, zero_divisor=2)
            shardings = jax.tree.map(
                lambda s: jax.NamedSharding(mesh_b, s),
                spec_tree(axes_b), is_leaf=lambda x: hasattr(x, "index"))
            restored, step = Checkpointer(d).restore(state_b)
            assert step == 7
            got = np.asarray(restored.params["embed"])
            np.testing.assert_array_equal(got, ref)
            # restored params adopt mesh-B shardings when re-pinned
            p = jax.device_put(
                restored.params["embed"],
                jax.NamedSharding(mesh_b, jax.sharding.PartitionSpec(
                    "model", None)))
            assert p.sharding.mesh.shape["data"] == 2
        print("OK")
    """)


# ---------------------------------------------------------------------------
# mesh-native ozimmu: error-free cross-device accumulation
# ---------------------------------------------------------------------------

def test_ozimmu_sharded_bitwise_all_variants():
    """Contraction-axis sharding over 'model' (8 shards) is bit-identical
    to the single-device emulation for all four paper variants under the
    exact-int32 cross-device reduction — f32 and df32 accumulators here
    (no x64 in this subprocess); genuine f64 is the _x64 test below."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ozimmu
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(0)
        def phi_mat(m, n, phi=1.0):
            u = rng.uniform(0, 1, (m, n)); z = rng.standard_normal((m, n))
            return (u - 0.5) * np.exp(phi * z)

        a = jnp.asarray(phi_mat(48, 256), jnp.float32)
        b = jnp.asarray(phi_mat(256, 64), jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        mesh = make_test_mesh(data=1, model=8)
        accums = ("f32", "df32")
        for name in ("ozimmu", "ozimmu_rn", "ozimmu_ef", "ozimmu_h",
                     "ozimmu_sm_b", "ozimmu_sm_h"):
            for accum in accums:
                cfg = ozimmu.VARIANTS[name].with_(k=6, accum_dtype=accum)
                ref = ozimmu.ozimmu_dot_general(a, b, dn, cfg)
                sharded = cfg.with_(mesh_axis="model")
                with set_mesh(mesh):
                    got = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                        a, b, dn, sharded))(a, b)
                assert bool(jnp.all(ref == got)), (name, accum)
                print(name, accum, "bitwise OK")
        print("OK")
    """)


def test_ozimmu_sharded_bitwise_x64():
    """Same bitwise invariant with genuine f64 accumulation (x64 mode)."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        assert jax.config.jax_enable_x64
        from repro.core import ozimmu
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(3)
        a = jnp.asarray(rng.standard_normal((32, 512)), jnp.float64)
        b = jnp.asarray(rng.standard_normal((512, 40)), jnp.float64)
        dn = (((1,), (0,)), ((), ()))
        mesh = make_test_mesh(data=1, model=8)
        for name in ("ozimmu", "ozimmu_rn", "ozimmu_ef", "ozimmu_h",
                     "ozimmu_sm_b", "ozimmu_sm_h"):
            cfg = ozimmu.VARIANTS[name].with_(k=8, accum_dtype="f64")
            ref = ozimmu.ozimmu_dot_general(a, b, dn, cfg)
            with set_mesh(mesh):
                got = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                    a, b, dn, cfg.with_(mesh_axis="model")))(a, b)
            assert bool(jnp.all(ref == got)), name
        print("OK")
    """, x64=True)


def test_ozimmu_batch_sharded_matches_single_device():
    """Batch-dim sharding over 'data' (GSPMD, no cross-device contraction)
    is bit-identical to single-device emulation — batch entries are
    independent, so no reduction crosses devices."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ozimmu
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(1)
        a = jnp.asarray(rng.standard_normal((8, 16, 64)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((8, 64, 24)), jnp.float32)
        dn = (((2,), (1,)), ((0,), (0,)))
        cfg = ozimmu.VARIANTS["ozimmu_h"].with_(k=6, accum_dtype="df32")
        ref = ozimmu.ozimmu_dot_general(a, b, dn, cfg)
        mesh = make_test_mesh(data=8, model=1)
        with set_mesh(mesh):
            spec_a = NamedSharding(mesh, P("data", None, None))
            spec_b = NamedSharding(mesh, P("data", None, None))
            aa = jax.device_put(a, spec_a)
            bb = jax.device_put(b, spec_b)
            got = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                a, b, dn, cfg))(aa, bb)
        assert bool(jnp.all(ref == got))
        print("OK")
    """)


def test_ozimmu_sharded_vjp_bitwise():
    """Gradients through the mesh-native emulated contraction equal the
    single-device gradients bit for bit (the custom VJP's cotangent
    contractions run through the same sharded scheme)."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ozimmu
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(2)
        a = jnp.asarray(rng.standard_normal((32, 256)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((256, 64)), jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        cfg = ozimmu.VARIANTS["ozimmu_h"].with_(k=6, accum_dtype="df32")
        loss0 = lambda a, b: jnp.sum(
            jnp.sin(ozimmu.ozimmu_dot_general(a, b, dn, cfg)))
        g_ref = jax.grad(loss0, argnums=(0, 1))(a, b)
        sharded = cfg.with_(mesh_axis="model")
        loss1 = lambda a, b: jnp.sum(
            jnp.sin(ozimmu.ozimmu_dot_general(a, b, dn, sharded)))
        mesh = make_test_mesh(data=1, model=8)
        with set_mesh(mesh):
            g_got = jax.jit(jax.grad(loss1, argnums=(0, 1)))(a, b)
        for r, g, nm in (*zip(g_ref, g_got, ("da", "db")),):
            assert bool(jnp.all(r == g)), nm
        print("OK")
    """)


@pytest.mark.parametrize("devices,m,p", [
    (8, 48, 64),        # 6 rows a device, one column block
    (4, 48, 512),       # 12 rows a device, four 128-column blocks
    (4, 30, 64),        # rows do not divide the axis: psum, C replicated
], ids=["8dev", "4dev_column_blocks", "4dev_rows_indivisible"])
def test_ozimmu_sharded_fused_pipeline_bitwise(devices, m, p):
    """The fused Pallas pipeline (``:fused``) composes with the mesh-native
    path: under the exact-int32 reduction the sharded fused emulation is
    bit-identical to the single-device fused AND unfused paths, for all
    four variants (the acceptance invariant of the fused pipeline).  Where
    C's rows divide the axis each group's product is reduce-scattered and
    C comes back row-sharded, ``P("model", None)``; otherwise each product
    is psum'd and C comes back replicated."""
    run_sub(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ozimmu
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(0)
        def phi_mat(m, n, phi=1.0):
            u = rng.uniform(0, 1, (m, n)); z = rng.standard_normal((m, n))
            return (u - 0.5) * np.exp(phi * z)

        m, p, devices = {m}, {p}, {devices}
        a = jnp.asarray(phi_mat(m, 256), jnp.float32)
        b = jnp.asarray(phi_mat(256, p), jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        mesh = make_test_mesh(data=1, model=devices)
        rows = NamedSharding(mesh, P("model", None))
        for name in ("ozimmu", "ozimmu_rn", "ozimmu_ef", "ozimmu_h",
                     "ozimmu_sm_b", "ozimmu_sm_h"):
            for accum in ("f32", "df32"):
                cfg = ozimmu.VARIANTS[name].with_(
                    k=6, accum_dtype=accum, use_pallas="fused")
                unfused = ozimmu.ozimmu_dot_general(
                    a, b, dn, cfg.with_(use_pallas=False))
                fused = ozimmu.ozimmu_dot_general(a, b, dn, cfg)
                assert bool(jnp.all(unfused == fused)), (name, accum)
                sharded = cfg.with_(mesh_axis="model")
                with set_mesh(mesh):
                    got = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                        a, b, dn, sharded))(a, b)
                assert bool(jnp.all(fused == got)), (name, accum)
                if m % devices == 0:
                    assert got.sharding.is_equivalent_to(rows, 2), \
                        got.sharding
                else:
                    assert got.sharding.is_fully_replicated, got.sharding
                print(name, accum, "fused sharded bitwise OK")
        print("OK")
    """, devices=devices)


@pytest.mark.parametrize("devices,p", [
    (8, 64),            # one column block
    (4, 512),           # four 128-column blocks where B's grid allows
], ids=["8dev", "4dev_column_blocks"])
def test_oz2_sharded_bitwise_both_modes(devices, p):
    """Ozaki-II (constant scaling + exponent ladder): under the exact-int32
    reduction the sharded emulation — plain and fused — is bit-identical
    to the single-device path for both oz2 variants, full, fast AND fast2
    modes (the digit grid is agreed via one pmax — per-row for fast2 —
    and the int32 chunk products are reduced BEFORE the ladder fold; the
    fast2 diag unscale is a pure-pow2 rescale of the reduced result, so
    it cannot break the bitwise invariant).  With four devices and 512
    columns, B's first 128 columns are scaled up: the full and fast
    modes' single grid for all of B then differs from a grid of any
    other column block alone."""
    run_sub(f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ozimmu
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(5)
        def phi_mat(m, n, phi=1.0):
            u = rng.uniform(0, 1, (m, n)); z = rng.standard_normal((m, n))
            return (u - 0.5) * np.exp(phi * z)

        p, devices = {p}, {devices}
        a = jnp.asarray(phi_mat(48, 256), jnp.float32)
        b = phi_mat(256, p)
        if p > 128:
            b[:, :128] *= 1024.0
        b = jnp.asarray(b, jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        mesh = make_test_mesh(data=1, model=devices)
        for name in ("oz2_b", "oz2_h"):
            for fast in (False, True, "fast2"):
                for pallas in (False, "fused"):
                    cfg = ozimmu.VARIANTS[name].with_(
                        k=6, accum_dtype="df32", fast=fast,
                        use_pallas=pallas)
                    ref = ozimmu.ozimmu_dot_general(a, b, dn,
                                                    cfg.with_(use_pallas=False))
                    local = ozimmu.ozimmu_dot_general(a, b, dn, cfg)
                    assert bool(jnp.all(ref == local)), (name, fast, pallas)
                    with set_mesh(mesh):
                        got = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                            a, b, dn, cfg.with_(mesh_axis="model")))(a, b)
                    assert bool(jnp.all(ref == got)), (name, fast, pallas)
                print(name, {{False: "full", True: "fast"}}.get(fast, "fast2"),
                      "sharded bitwise OK")
        print("OK")
    """, devices=devices)


def test_oz2_fast2_sharded_int32_bitwise():
    """:fast2 composed with @mesh/int32 specifically (the acceptance
    matrix cell): spec-driven configs, exact-int32 reduction, plain and
    fused — bit-identical to the single-device XLA path."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ozimmu
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(11)
        def phi_mat(m, n, phi=2.0):
            u = rng.uniform(0, 1, (m, n)); z = rng.standard_normal((m, n))
            return (u - 0.5) * np.exp(phi * z)

        a = jnp.asarray(phi_mat(48, 256), jnp.float32)
        b = jnp.asarray(phi_mat(256, 64), jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        mesh = make_test_mesh(data=1, model=8)
        for spec, sharded_spec in (
                ("oz2_h-6:df32:fast2", "oz2_h-6:df32:fast2@model/int32"),
                ("oz2_b-6:df32:fast2", "oz2_b-6:df32:fast2@model/int32"),
                ("oz2_h-6:df32:fast2:fused",
                 "oz2_h-6:df32:fast2:fused@model/int32")):
            cfg = ozimmu.parse_spec(spec)
            assert cfg.split.endswith("_fast2"), spec
            ref = ozimmu.ozimmu_dot_general(a, b, dn, cfg)
            with set_mesh(mesh):
                got = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                    a, b, dn, ozimmu.parse_spec(sharded_spec)))(a, b)
            assert bool(jnp.all(ref == got)), spec
            print(spec, "sharded int32 bitwise OK")
        print("OK")
    """)


def test_sm_auto_sharded_int32_bitwise():
    """The sign-magnitude acceptance matrix cell: ``ozimmu_sm_h-auto`` is
    bit-identical across {XLA, :fused, @mesh/int32, rhs_presplit} — all
    jitted, so auto-k resolves the same static mantissa-coverage plan on
    every path, and the sm digit grid is pmax-agreed across shards with
    the signed products psum'd exactly in int32."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ozimmu, split_cache
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(17)
        def phi_mat(m, n, phi=2.0):
            u = rng.uniform(0, 1, (m, n)); z = rng.standard_normal((m, n))
            return (u - 0.5) * np.exp(phi * z)

        a = jnp.asarray(phi_mat(48, 256), jnp.float32)
        b = jnp.asarray(phi_mat(256, 64), jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        mesh = make_test_mesh(data=1, model=8)
        for stem in ("ozimmu_sm_h-auto:df32", "ozimmu_sm_b-auto:df32"):
            cfg = ozimmu.parse_spec(stem)
            ref = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                a, b, dn, cfg))(a, b)
            fused = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                a, b, dn, ozimmu.parse_spec(stem + ":fused")))(a, b)
            assert bool(jnp.all(ref == fused)), (stem, "fused")
            sp = split_cache.SplitCache().get(b, dn, cfg)
            pre = jax.jit(lambda a, b, sp: ozimmu.ozimmu_dot_general(
                a, b, dn, cfg, rhs_presplit=sp))(a, b, sp)
            assert bool(jnp.all(ref == pre)), (stem, "presplit")
            with set_mesh(mesh):
                mcfg = ozimmu.parse_spec(stem + "@model/int32")
                got = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                    a, b, dn, mcfg))(a, b)
                gotf = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                    a, b, dn,
                    ozimmu.parse_spec(stem + ":fused@model/int32")))(a, b)
            assert bool(jnp.all(ref == got)), (stem, "@mesh/int32")
            assert bool(jnp.all(ref == gotf)), (stem, "fused@mesh/int32")
            print(stem, "4-way bitwise OK")
        print("OK")
    """)


def test_presplit_sharded_bitwise_all_variants():
    """Serving split-cache x @mesh: a frozen B-side split entering the
    shard_map pre-sharded along the contraction axis is bit-identical to
    the sharded uncached path (int32 reduction) for every variant incl.
    :fused, and to the single-device presplit path — the cached
    full-matrix digit grid IS the pmax-agreed grid (docs/serving.md)."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ozimmu, split_cache
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(9)
        a = jnp.asarray(rng.standard_normal((24, 256)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((256, 32)), jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        mesh = make_test_mesh(data=1, model=8)
        cache = split_cache.SplitCache()
        FAST = {"oz2_h": True, "oz2_b": "fast2"}   # cover :fast AND :fast2
        for name in ("ozimmu", "ozimmu_rn", "ozimmu_ef", "ozimmu_h",
                     "ozimmu_sm_b", "ozimmu_sm_h", "oz2_b", "oz2_h"):
            for pallas in (False, "fused"):
                if pallas == "fused" and name == "ozimmu_rn":
                    continue  # adaptive RN has no fused splitter
                cfg = ozimmu.canonical_fast2(ozimmu.VARIANTS[name].with_(
                    k=5, accum_dtype="df32", use_pallas=pallas,
                    fast=FAST.get(name, False)))
                ref = ozimmu.ozimmu_dot_general(a, b, dn, cfg)
                with set_mesh(mesh):
                    mcfg = cfg.with_(mesh_axis="model")
                    sp = cache.get(b, dn, mcfg)
                    got = jax.jit(lambda a, b, sp: ozimmu.ozimmu_dot_general(
                        a, b, dn, mcfg, rhs_presplit=sp))(a, b, sp)
                    unc = jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                        a, b, dn, mcfg))(a, b)
                assert bool(jnp.all(got == unc)), (name, pallas)
                assert bool(jnp.all(got == ref)), (name, pallas)
            print(name, "presplit sharded bitwise OK")
        print("OK")
    """)


def test_serving_runtime_mesh_smoke():
    """The serving runtime end-to-end under a (data, model) mesh with an
    @model engine: generates finite tokens, split-cache active."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import configs
        from repro.distributed.sharding import use_rules
        from repro.launch.mesh import make_test_mesh, mesh_rules
        from repro.models import api
        from repro.serving import ServingRuntime

        arch = "internlm2_1_8b"
        mesh = make_test_mesh(data=2, model=4)
        cfg = configs.get_config(arch, smoke=True,
                                 engine_spec="ozimmu_h-4:df32@model")
        with jax.set_mesh(mesh), use_rules(mesh_rules(mesh, arch)):
            model = api.get_model(cfg)
            params, _ = model.init(jax.random.PRNGKey(0), cfg)
            rt = ServingRuntime(cfg, params, slots=2, max_len=32)
            rng = np.random.default_rng(0)
            prompts = [rng.integers(0, cfg.vocab, size=6, dtype=np.int32)
                       for _ in range(3)]
            outs = rt.generate(prompts, max_new=3)
        assert all(len(o) == 9 for o in outs)
        s = rt.metrics.summary()
        assert s["requests"]["finished"] == 3
        assert s["split_cache"]["weight_split_hit_rate"] == 1.0
        print("OK")
    """, x64=True)


def test_serving_prefix_chunked_paged_mesh_bitwise():
    """{paged, chunked prefill, prefix cache} under a (data, model) mesh
    with an @model engine reproduces the monolithic un-chunked mesh
    runtime per token — including across a prefix-hit second wave (the
    mesh key rides in the prefix keying, so entries published here can
    never alias a differently-sharded pipeline's)."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import configs
        from repro.distributed.sharding import use_rules
        from repro.launch.mesh import make_test_mesh, mesh_rules
        from repro.models import api
        from repro.serving import ServingRuntime

        arch = "internlm2_1_8b"
        mesh = make_test_mesh(data=2, model=4)
        cfg = configs.get_config(arch, smoke=True,
                                 engine_spec="ozimmu_h-4:df32@model")
        with jax.set_mesh(mesh), use_rules(mesh_rules(mesh, arch)):
            model = api.get_model(cfg)
            params, _ = model.init(jax.random.PRNGKey(0), cfg)
            rng = np.random.default_rng(0)
            prefix = rng.integers(0, cfg.vocab, size=9, dtype=np.int32)
            waves = [[np.concatenate([prefix,
                                      rng.integers(0, cfg.vocab, size=2,
                                                   dtype=np.int32)])
                      for _ in range(3)] for _ in range(2)]
            cold = ServingRuntime(cfg, params, slots=2, max_len=32)
            refs = [cold.generate([p.copy() for p in w], 3)
                    for w in waves]
            rt = ServingRuntime(cfg, params, slots=2, max_len=32,
                                page_block=4, prefill_chunk=3,
                                prefix_cache=True)
            outs = [rt.generate([p.copy() for p in w], 3) for w in waves]
        for o, r in zip(outs[0] + outs[1], refs[0] + refs[1]):
            assert np.array_equal(o, r), (o, r)
        assert rt.prefix.stats.hits >= 3          # wave 2 hit the prefix
        s = rt.metrics.summary()
        assert s["requests"]["finished"] == 6
        assert s["split_cache"]["weight_split_hit_rate"] == 1.0
        print("OK")
    """, x64=True)


def test_psum_df32_error_free_vs_plain_f32():
    """The compensated DF32 reduction keeps what a plain f32 psum rounds
    away: partials engineered so small terms vanish under f32 summation."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.accumulate import DF32
        from repro.distributed import collectives
        from jax import set_mesh, shard_map
        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh(data=8, model=1)
        # device i holds hi = (-1)^i * 2^24, lo = 0.5: the 2^24 terms cancel
        # pairwise, so the true sum is 4.0.  A plain f32 psum of (hi + lo)
        # collapses every partial to +-2^24 first (0.5 is under half an ulp
        # at 2^24, and -16777215.5 rounds half-to-even to -2^24 too) and
        # returns 0.0.
        his = jnp.asarray([(-1.0) ** i * 2.0 ** 24 for i in range(8)],
                          jnp.float32).reshape(8, 1)
        los = jnp.full((8, 1), 0.5, jnp.float32)

        def body(h, l):
            c = DF32(h[0], l[0])
            plain = jax.lax.psum(h[0] + l[0], "data")
            comp = collectives.psum_df32(c, "data")
            return plain[None], (comp.hi + comp.lo)[None]

        plain, comp = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data")), check_vma=False))(his, los)
        assert float(plain[0, 0]) == 0.0, plain     # f32 psum loses it
        assert float(comp[0, 0]) == 4.0, comp       # DF32 keeps it
        print("OK")
    """)


def test_ozimmu_sharded_df32_reduce_accuracy():
    """The @axis/df32 strategy (compensated partial-accumulator reduction)
    stays at the unsharded error level — no f32-psum accuracy cliff."""
    run_sub("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import ozimmu
        from jax import set_mesh
        from repro.launch.mesh import make_test_mesh

        rng = np.random.default_rng(4)
        a_np = rng.standard_normal((48, 512))
        b_np = rng.standard_normal((512, 32))
        exact = a_np @ b_np                      # numpy f64 reference
        a = jnp.asarray(a_np, jnp.float32)
        b = jnp.asarray(b_np, jnp.float32)
        dn = (((1,), (0,)), ((), ()))
        cfg = ozimmu.VARIANTS["ozimmu_h"].with_(k=6, accum_dtype="df32")
        ref = np.asarray(ozimmu.ozimmu_dot_general(a, b, dn, cfg),
                         np.float64)
        sharded = cfg.with_(mesh_axis="model", mesh_reduce="df32")
        mesh = make_test_mesh(data=1, model=8)
        with set_mesh(mesh):
            got = np.asarray(jax.jit(lambda a, b: ozimmu.ozimmu_dot_general(
                a, b, dn, sharded))(a, b), np.float64)
        scale = np.abs(exact).max()
        e_ref = np.abs(ref - exact).max() / scale
        e_got = np.abs(got - exact).max() / scale
        print("err unsharded", e_ref, "sharded/df32-reduce", e_got)
        # error-free reduction: sharded error within 2x of unsharded
        # (local per-shard scales can make it smaller, never psum-worse)
        assert e_got <= 2 * e_ref + 1e-7, (e_got, e_ref)
        print("OK")
    """)

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory/cost/collective analysis.

Run as a script: ``main`` adds ``--xla_force_host_platform_device_count=512``
to ``XLA_FLAGS`` before JAX initializes its backends (importing this
module changes nothing).

    PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek_7b \
        --shape train_4k --mesh single            # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
        --out experiments/dryrun                   # the full table

Each cell writes ``<out>/<arch>__<shape>__<mesh>.json`` with:
  memory_analysis (bytes per device), cost_analysis (FLOPs/bytes),
  per-kind collective bytes, the three roofline terms, and metadata.
"""
import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from dataclasses import replace as dataclasses_replace
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs, optim
from repro.configs import SHAPES
from repro.distributed.sharding import spec_tree, use_rules
from repro.launch import hlo_cost, roofline, steps
from repro.launch.mesh import make_production_mesh, mesh_rules
from repro.models import api

# Per-arch training knobs for the big cells: microbatch count at train_4k
# (global batch 256).  Derived from the memory iteration in EXPERIMENTS.md.
TRAIN_MICROBATCHES = {
    "deepseek_v2_236b": 16,
    "llama32_vision_11b": 8,
    "deepseek_moe_16b": 8,
    "deepseek_7b": 8,
    "recurrentgemma_9b": 8,
    "starcoder2_3b": 4,
    "phi4_mini_3_8b": 4,
    "internlm2_1_8b": 2,
    "mamba2_780m": 2,
    "seamless_m4t_medium": 2,
}

# master_f32 off for the very large configs (memory table in EXPERIMENTS.md)
NO_MASTER = {"deepseek_v2_236b", "llama32_vision_11b"}

# 236B-scale state-dtype policy on a single 256-chip pod (16 GiB/chip):
# bf16 params (f32 update computed on the fly), bf16 moments, bf16 grad
# accumulation, remat_block 10.  Documented trade-off in EXPERIMENTS §Perf;
# on >=2 pods the f32 policy fits via ZeRO over (pod, data).
# bf16 moments crash XLA:CPU ("Invalid binary instruction opcode
# copy" check failure) — a CPU-backend bug; policy documented in
# EXPERIMENTS §Perf, moments stay f32 in the dry-run.
TRAIN_STATE_DTYPE = {}
TRAIN_ACCUM_DTYPE = {}
PARAM_BF16 = set()
REMAT_BLOCK = {"deepseek_v2_236b": 10}


def opt_config_for(arch: str) -> optim.OptConfig:
    return optim.OptConfig(master_f32=arch not in NO_MASTER)


def _serving_dtype(pshapes):
    """Serving loads a bf16 checkpoint (params are never updated)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
        if jnp.issubdtype(s.dtype, jnp.floating) else s, pshapes)


def lower_cell(arch: str, shape_name: str, mesh, *, engine: str = "bf16",
               donate: bool = True, extra_overrides=None):
    """Lower one cell on ``mesh``; returns (lowered, meta dict)."""
    overrides = dict(extra_overrides or {})
    if shape_name == "train_4k" and arch in REMAT_BLOCK:
        overrides.setdefault("remat_block", REMAT_BLOCK[arch])
    cfg = configs.get_config(arch, engine_spec=engine, **overrides)
    shape = SHAPES[shape_name]
    rules = mesh_rules(mesh, arch)
    model = api.get_model(cfg)
    t0 = time.time()

    with jax.set_mesh(mesh), use_rules(rules):
        pshapes, axes = steps.params_shapes(cfg)
        n_params = roofline.count_params(pshapes)
        p_spec = spec_tree(axes, rules)

        if shape.kind == "train":
            if arch in PARAM_BF16:
                pshapes = _serving_dtype(pshapes)
            opt_cfg = opt_config_for(arch)
            state_dt = jnp.dtype(TRAIN_STATE_DTYPE.get(arch, "float32"))
            opt_cfg = dataclasses_replace(opt_cfg,
                                          state_dtype=str(state_dt))
            opt_axes = optim.zero_axes(axes, pshapes,
                                       mesh.shape.get("data", 1))
            tcfg = steps.TrainConfig(
                microbatches=TRAIN_MICROBATCHES.get(arch, 1),
                accum_dtype=TRAIN_ACCUM_DTYPE.get(arch, "float32"))
            train_step = steps.make_train_step(cfg, opt_cfg, tcfg,
                                               opt_axes=opt_axes)
            m_spec = spec_tree(opt_axes, rules)
            state_spec = steps.TrainState(
                p_spec,
                optim.OptState(m_spec, m_spec,
                               m_spec if opt_cfg.master_f32 else None, P()),
                P())
            state_shapes = jax.eval_shape(
                lambda: steps.TrainState(
                    pshapes,
                    optim.OptState(
                        jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                            s.shape, state_dt), pshapes),
                        jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                            s.shape, state_dt), pshapes),
                        (jax.tree.map(lambda s: jax.ShapeDtypeStruct(
                            s.shape, jnp.float32), pshapes)
                         if opt_cfg.master_f32 else None),
                        jax.ShapeDtypeStruct((), jnp.int32)),
                    jax.ShapeDtypeStruct((), jnp.int32)))
            batch = steps.batch_specs(cfg, shape)
            batch_spec = {k: P(rules["batch"]) for k in batch}
            state_spec = steps.evenize(state_spec, state_shapes, mesh)
            batch_spec = steps.evenize(batch_spec, batch, mesh)
            fn = jax.jit(
                train_step,
                in_shardings=(steps.named(mesh, state_spec),
                              steps.named(mesh, batch_spec)),
                out_shardings=(steps.named(mesh, state_spec), None),
                donate_argnums=(0,) if donate else ())
            lowered = fn.lower(state_shapes, batch)

        elif shape.kind == "prefill":
            pshapes = _serving_dtype(pshapes)      # bf16 serving checkpoint
            prefill = steps.make_prefill_step(cfg)
            batch = steps.batch_specs(cfg, shape)
            batch_spec = {k: P(rules["batch"]) for k in batch}
            p_spec_e = steps.evenize(p_spec, pshapes, mesh)
            batch_spec = steps.evenize(batch_spec, batch, mesh)
            fn = jax.jit(prefill,
                         in_shardings=(steps.named(mesh, p_spec_e),
                                       steps.named(mesh, batch_spec)),
                         out_shardings=None)
            lowered = fn.lower(pshapes, batch)

        else:  # decode
            pshapes = _serving_dtype(pshapes)      # bf16 serving checkpoint
            decode = steps.make_decode_step(cfg)
            cache, tokens, cur_len = steps.decode_input_specs(cfg, shape)
            cache_spec = spec_tree(model.cache_axes(cfg), rules)
            p_spec_e = steps.evenize(p_spec, pshapes, mesh)
            cache_spec = steps.evenize(cache_spec, cache, mesh)
            tok_spec = steps.evenize(P(rules["cache_batch"]), tokens, mesh)
            fn = jax.jit(
                decode,
                in_shardings=(steps.named(mesh, p_spec_e),
                              steps.named(mesh, cache_spec),
                              NamedSharding(mesh, tok_spec),
                              NamedSharding(mesh, P())),
                out_shardings=None,
                donate_argnums=(1,) if donate else ())
            lowered = fn.lower(pshapes, cache, tokens, cur_len)

    meta = {"arch": arch, "shape": shape_name, "n_params": n_params,
            "lower_s": time.time() - t0}
    return lowered, cfg, shape, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir=None, engine: str = "bf16", verbose: bool = True,
             extra_overrides=None):
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    lowered, cfg, shape, meta = lower_cell(arch, shape_name, mesh,
                                           engine=engine,
                                           extra_overrides=extra_overrides)
    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()          # loop-blind; recorded for ref
    hlo = compiled.as_text()
    percore = hlo_cost.analyze(hlo)          # loop-aware per-device totals
    flops = percore["flops"] * chips
    byts = percore["bytes"] * chips
    coll = {k: v * chips
            for k, v in percore["collective_operand_bytes"].items()}
    ici = percore["collective_ici_bytes"] * chips
    n_active = roofline.active_params(cfg, meta["n_params"])
    mflops = roofline.model_flops_for(cfg, shape, meta["n_params"], n_active)

    rl = roofline.Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=byts,
        coll_bytes=ici, coll_breakdown={k: int(v) for k, v in coll.items()},
        model_flops=mflops)

    mem_attrs = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_attrs[attr] = int(v)

    xla_flops, xla_bytes = roofline.cost_flops_bytes(cost)
    record = {
        **meta,
        "mesh": mesh_name, "chips": chips, "engine": engine,
        "compile_s": compile_s,
        "memory_analysis": mem_attrs or str(mem),
        "hlo_flops_global": flops, "hlo_bytes_global": byts,
        "collective_operand_bytes": {k: int(v) for k, v in coll.items()},
        "collective_ici_bytes": ici,
        "dot_flops_global": percore["dot_flops"] * chips,
        "xla_cost_analysis_flops_looplblind": xla_flops,
        "xla_cost_analysis_bytes_loopblind": xla_bytes,
        "roofline": rl.to_dict(),
        "n_active_params": n_active,
    }
    if verbose:
        print(f"[{arch} x {shape_name} x {mesh_name}] "
              f"compile {compile_s:.1f}s  "
              f"flops {flops:.3e}  bytes {byts:.3e}  "
              f"coll {sum(coll.values()):.3e}  "
              f"bottleneck {rl.bottleneck}  "
              f"t_bound {rl.t_bound * 1e3:.3f} ms  "
              f"mfu_bound {rl.mfu_bound:.3f}")
        print("  memory_analysis:", mem_attrs or mem)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir,
                            f"{arch}__{shape_name}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
    return record


def _force_host_devices(n: int = 512) -> None:
    """Give the CPU backend ``n`` devices for the production meshes; must
    run before JAX initializes a backend (it keeps other XLA flags)."""
    flag = f"--xla_force_host_platform_device_count={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if flag not in flags.split():
        os.environ["XLA_FLAGS"] = f"{flags} {flag}".strip()


def main(argv=None):
    _force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--engine", default="bf16")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)

    cells = (list(configs.arch_shape_cells()) if args.all
             else [(args.arch, args.shape)])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    for arch, shape in cells:
        skips = configs.skipped_shapes(arch)
        if shape in skips:
            print(f"[{arch} x {shape}] SKIP: {skips[shape]}")
            continue
        for mp in meshes:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            if args.skip_existing and args.out and os.path.exists(
                    os.path.join(args.out,
                                 f"{arch}__{shape}__{mesh_name}.json")):
                print(f"[{arch} x {shape} x {mesh_name}] exists, skipping")
                continue
            try:
                run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                         engine=args.engine)
            except Exception as e:
                traceback.print_exc()
                failures.append((arch, shape, mp, repr(e)))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        sys.exit(1)
    print("\nall requested cells lowered + compiled OK")


if __name__ == "__main__":
    main()

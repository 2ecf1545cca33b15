"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2_1_8b \
        --smoke --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

One process drives the devices it sees: the ``--mesh`` spec lays them out
(launch.mesh), the pipeline shards the host batch, checkpoints save
asynchronously, and on restart the trainer finds the latest checkpoint,
restores it (resharding onto the current mesh if it changed — elastic)
and continues from the saved step.  ``--smoke`` (the default) runs the
reduced configs used by the examples and tests; ``--full`` the published
widths.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, optim
from repro.checkpoint import Checkpointer
from repro.data import DataConfig, Pipeline
from repro.distributed.sharding import use_rules
from repro.launch import steps as S
from repro.launch.entry import prepare_entry_point
from repro.launch.mesh import mesh_rules, parse_mesh_spec
from repro.models import api


def train(arch: str, *, smoke: bool = True, n_steps: int = 100,
          global_batch: int = 8, seq_len: int = 256,
          ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
          microbatches: int = 1, engine: str = "bf16",
          mesh=None, seed: int = 0, log_every: int = 10,
          lr: float = 3e-3, profile_dir: Optional[str] = None,
          print_fn=print):
    cfg = configs.get_config(arch, smoke=smoke, engine_spec=engine)
    oz_cfg = cfg.engine.ozimmu_config
    if oz_cfg is not None:
        from repro.core import plan
        print_fn(f"[train] engine {engine}: "
                 f"{plan.describe_config(oz_cfg, cfg.d_model, cfg.d_model, cfg.d_model)}")
    model = api.get_model(cfg)
    opt_cfg = optim.OptConfig(lr=lr, warmup_steps=min(20, n_steps // 5 + 1),
                              total_steps=n_steps)
    tcfg = S.TrainConfig(microbatches=microbatches)

    rules = mesh_rules(mesh, arch) if mesh is not None else None
    data_cfg = DataConfig(seq_len=seq_len, global_batch=global_batch,
                          vocab=cfg.vocab, seed=seed,
                          vision_seq=cfg.vision_seq if cfg.family == "vlm" else 0,
                          frames=seq_len if cfg.family == "encdec" else 0,
                          d_model=cfg.d_model)
    pipe = Pipeline(data_cfg, host_id=jax.process_index(),
                    num_hosts=jax.process_count())

    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None

    import contextlib
    mesh_ctx = (jax.set_mesh(mesh) if mesh is not None
                else contextlib.nullcontext())
    with mesh_ctx, use_rules(rules):
        rng = jax.random.PRNGKey(seed)
        state, axes, opt_axes = S.init_state(
            rng, cfg, opt_cfg,
            zero_divisor=(mesh.shape.get("data", 1) if mesh else 1))
        start_step = 0
        if ckpt and ckpt.latest_step() is not None:
            state, start_step = ckpt.restore(state)
            print_fn(f"[train] resumed from step {start_step}")

        train_step = jax.jit(
            S.make_train_step(cfg, opt_cfg, tcfg, opt_axes=opt_axes),
            donate_argnums=(0,))

        from repro.core import plan as _plan
        from repro.obs import tracing as _tracing
        losses = []
        t0 = time.time()
        with _tracing.profile(profile_dir):
            for step in range(start_step, n_steps):
                batch = {k: jnp.asarray(v) for k, v in
                         pipe.batch_at(step).items()}
                state, metrics = train_step(state, batch)
                losses.append(float(metrics["loss"]))
                if step == start_step and len(_plan.get_ledger()):
                    # the first step traced every contraction: the ledger
                    # now holds one row per auto-k decision of the program
                    print_fn(f"[train] planner: "
                             f"{_plan.get_ledger().describe()}")
                if log_every and (step + 1) % log_every == 0:
                    dt = (time.time() - t0) / log_every
                    print_fn(f"[train] step {step + 1:5d}  "
                             f"loss {losses[-1]:.4f}  "
                             f"gnorm {float(metrics['grad_norm']):.3f}  "
                             f"lr {float(metrics['lr']):.2e}  "
                             f"{dt * 1e3:.0f} ms/step")
                    t0 = time.time()
                if ckpt and (step + 1) % ckpt_every == 0:
                    ckpt.save(step + 1, state)
        if ckpt:
            ckpt.save(n_steps, state, blocking=True)
    return state, losses


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--engine", "--matmul_engine", dest="engine",
                    default="bf16",
                    help="matmul engine spec, e.g. bf16, ozimmu_h-8:df32@model "
                         "or ozimmu_h-auto:df32:fused (auto-k planner + fused "
                         "Pallas pipeline; docs/engine.md)")
    ap.add_argument("--mesh", default=None,
                    help="mesh spec: 'data=2,model=4', 'single_pod', "
                         "'multi_pod'; default no mesh (single device)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax profiler trace of the training "
                         "loop into DIR (view with TensorBoard/Perfetto)")
    args = ap.parse_args(argv)
    _, losses = train(args.arch, smoke=args.smoke, n_steps=args.steps,
                      global_batch=args.batch, seq_len=args.seq,
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      microbatches=args.microbatches, engine=args.engine,
                      mesh=parse_mesh_spec(args.mesh),
                      lr=args.lr, profile_dir=args.profile_dir)
    k = max(1, len(losses) // 10)
    print(f"[train] first-{k} mean loss {np.mean(losses[:k]):.4f}  "
          f"last-{k} mean loss {np.mean(losses[-k:]):.4f}")


if __name__ == "__main__":
    prepare_entry_point()
    main()

"""AdamW with ZeRO-1-style sharded optimizer state.

Functional (no optax dependency): ``init(params, axes) -> OptState``;
``step(grads, params, state, cfg, schedule_step) -> (params, state)``.

ZeRO-1: first/second moments (and the optional f32 master copy) carry an
*extended* sharding — each param's logical axes are augmented so that the
largest currently-unsharded axis maps to the ``zero`` rule (the pure-DP mesh
axes).  Params/grads keep the model sharding (so forward/backward are
untouched); only the state and the update computation are partitioned, which
is exactly ZeRO-1.  XLA inserts the reduce-scatter/all-gather pair around
the update from the sharding mismatch.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import get_rules, logical_to_pspec, shard


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    betas: tuple = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    master_f32: bool = False        # keep f32 master params (off for huge cfgs)
    state_dtype: str = "float32"


class OptState(NamedTuple):
    mu: Any
    nu: Any
    master: Optional[Any]
    count: jax.Array


# ---------------------------------------------------------------------------
# ZeRO axis augmentation
# ---------------------------------------------------------------------------

def zero_axes(axes_tree, params, zero_divisor: int):
    """Augment each param's logical axes: the largest axis that is unsharded
    (logical name None or mapping to None) and divisible by the zero-axis
    size gets the logical name 'zero'.

    The effective divisor is derived from the live mesh + the 'zero' rule
    when available (it may span several mesh axes, e.g. (pod, data));
    ``zero_divisor`` is the fallback when no mesh is installed."""
    from jax.sharding import get_abstract_mesh
    rules = get_rules() or {}
    mesh = get_abstract_mesh()
    if rules.get("zero") and not mesh.empty:
        zr = rules["zero"]
        zr = (zr,) if isinstance(zr, str) else tuple(zr)
        prod = 1
        for a in zr:
            if a in mesh.axis_names:
                prod *= mesh.shape[a]
        if prod > 1:
            zero_divisor = prod

    def aug(axes, p):
        if not isinstance(axes, tuple):
            return axes
        mapped = [rules.get(a) if a else None for a in axes]
        best, best_dim = None, 0
        for i, (a, m) in enumerate(zip(axes, mapped)):
            if m is None and p.shape[i] % zero_divisor == 0 \
                    and p.shape[i] > best_dim:
                best, best_dim = i, p.shape[i]
        if best is None:
            return axes
        out = list(axes)
        out[best] = "zero"
        return tuple(out)

    return jax.tree.map(aug, axes_tree, params,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            e is None or isinstance(e, str) for e in x))


# ---------------------------------------------------------------------------
# init / step
# ---------------------------------------------------------------------------

def init(params, state_axes=None, cfg: OptConfig = OptConfig()) -> OptState:
    dt = jnp.dtype(cfg.state_dtype)
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params)
    mu, nu = zeros, jax.tree.map(jnp.copy, zeros)
    master = (jax.tree.map(lambda p: p.astype(jnp.float32), params)
              if cfg.master_f32 else None)
    if state_axes is not None:
        mu = _apply_axes(mu, state_axes)
        nu = _apply_axes(nu, state_axes)
        if master is not None:
            master = _apply_axes(master, state_axes)
    return OptState(mu, nu, master, jnp.zeros((), jnp.int32))


def _apply_axes(tree, axes_tree):
    return jax.tree.map(
        lambda x, a: shard(x, *a) if isinstance(a, tuple) else x,
        tree, axes_tree)


def lr_at(cfg: OptConfig, step: jax.Array) -> jax.Array:
    """Linear warmup + cosine decay to min_lr_frac."""
    s = step.astype(jnp.float32)
    warm = s / jnp.maximum(cfg.warmup_steps, 1)
    prog = jnp.clip((s - cfg.warmup_steps) /
                    jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * jnp.where(s < cfg.warmup_steps, warm, cos)


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(x.astype(jnp.float32) ** 2)
                        for x in jax.tree.leaves(tree)))


def step(grads, params, state: OptState, cfg: OptConfig,
         state_axes=None):
    """One AdamW update.  Returns (new_params, new_state, metrics)."""
    count = state.count + 1
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9))
    b1, b2 = cfg.betas
    lr = lr_at(cfg, state.count)
    c1 = 1 - b1 ** count.astype(jnp.float32)
    c2 = 1 - b2 ** count.astype(jnp.float32)

    def upd(g, p, m, v, master):
        g = g.astype(jnp.float32) * clip
        m = b1 * m + (1 - b1) * g.astype(m.dtype)
        v = b2 * v + (1 - b2) * (g * g).astype(v.dtype)
        mhat = m.astype(jnp.float32) / c1
        vhat = v.astype(jnp.float32) / c2
        base = master if master is not None else p.astype(jnp.float32)
        new = base - lr * (mhat / (jnp.sqrt(vhat) + cfg.eps)
                           + cfg.weight_decay * base)
        return new.astype(p.dtype), m, v, new

    masters = state.master if state.master is not None else jax.tree.map(
        lambda _: None, params, is_leaf=lambda x: x is None)
    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_v = treedef.flatten_up_to(state.nu)
    flat_ma = (treedef.flatten_up_to(state.master)
               if state.master is not None else [None] * len(flat_p))
    outs = [upd(g, p, m, v, ma)
            for g, p, m, v, ma in zip(flat_g, flat_p, flat_m, flat_v, flat_ma)]
    new_p = treedef.unflatten([o[0] for o in outs])
    new_m = treedef.unflatten([o[1] for o in outs])
    new_v = treedef.unflatten([o[2] for o in outs])
    new_master = (treedef.unflatten([o[3] for o in outs])
                  if state.master is not None else None)
    if state_axes is not None:
        new_m = _apply_axes(new_m, state_axes)
        new_v = _apply_axes(new_v, state_axes)
        if new_master is not None:
            new_master = _apply_axes(new_master, state_axes)
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, OptState(new_m, new_v, new_master, count), metrics

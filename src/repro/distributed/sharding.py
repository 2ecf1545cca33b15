"""Logical-axis sharding rules (MaxText-style).

Model code annotates params/activations with *logical* axis names
("embed", "mlp", "heads", "batch", ...).  A rules table — chosen by the
launcher per mesh — maps logical names to mesh axes.  Model code never
mentions physical axes, so the same model runs on the single-pod
(data, model) mesh, the multi-pod (pod, data, model) mesh, or a laptop
(no mesh: every annotation is a no-op).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import PartitionSpec as P

Rules = dict  # logical axis name -> mesh axis | tuple | None

# Default rules for the production meshes.  "batch" spans the pure-DP axes
# (pod + data); tensor-parallel dims map to "model"; ZeRO-1 optimizer-state
# sharding additionally uses "data" (see optim/).
SINGLE_POD_RULES: Rules = {
    "batch": ("data",),
    "seq": None,
    "embed": None,
    "mlp": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "vocab": "model",
    "experts": "model",
    "expert_mlp": None,
    "kv_lora": None,
    "state": None,
    "conv": None,
    "layers": None,
    "cache_batch": ("data",),
    "cache_heads": None,
    "cache_hd": None,
    "zero": ("data",),
}

MULTI_POD_RULES: Rules = dict(SINGLE_POD_RULES)
MULTI_POD_RULES.update({
    "batch": ("pod", "data"),
    "cache_batch": ("pod", "data"),
    "zero": ("pod", "data"),
})

_state = threading.local()


def get_rules() -> Optional[Rules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    prev = get_rules()
    _state.rules = rules
    try:
        yield
    finally:
        _state.rules = prev


def logical_to_pspec(axes: Sequence[Optional[str]],
                     rules: Optional[Rules] = None) -> P:
    rules = rules if rules is not None else get_rules()
    if rules is None:
        return P()
    out, used = [], set()
    for ax in axes:
        mesh_ax = rules.get(ax) if ax is not None else None
        if mesh_ax is None:
            out.append(None)
            continue
        mesh_axes = (mesh_ax,) if isinstance(mesh_ax, str) else tuple(mesh_ax)
        # a mesh axis may appear at most once in a PartitionSpec
        mesh_axes = tuple(a for a in mesh_axes if a not in used)
        used.update(mesh_axes)
        if not mesh_axes:
            out.append(None)
        elif len(mesh_axes) == 1:
            out.append(mesh_axes[0])
        else:
            out.append(mesh_axes)
    return P(*out)


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Annotate an activation with logical axes (no-op without mesh+rules).

    Requires the mesh installed via ``jax.set_mesh`` (a plain
    ``with mesh:`` does NOT set the abstract mesh, and this silently
    no-ops)."""
    if get_rules() is None:
        return x
    from jax.sharding import get_abstract_mesh
    mesh = get_abstract_mesh()
    if mesh.empty:
        return x
    axes = axes[:x.ndim]  # tolerate rank-reduced call sites (hint semantics)
    spec = logical_to_pspec(axes)
    # drop mesh axes that aren't on the current mesh (e.g. "pod" on 1 pod)
    names = set(mesh.axis_names)

    def keep(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            e2 = tuple(a for a in e if a in names)
            return e2 if e2 else None
        return e if e in names else None

    spec = P(*(keep(e) for e in spec))
    return jax.lax.with_sharding_constraint(x, spec)


def spec_tree(axes_tree, rules: Optional[Rules] = None):
    """Map a pytree of logical-axes tuples to a pytree of PartitionSpecs."""
    return jax.tree.map(
        lambda axes: logical_to_pspec(axes, rules), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x))

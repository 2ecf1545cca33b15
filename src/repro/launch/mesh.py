"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — required because the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax
initializes, while tests/benches run on the single real CPU device.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the partitioner places every
    intermediate, and sharding annotations are hints, not assertions
    (JAX 0.9 defaults ``make_mesh`` to Explicit axes)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: Optional[int] = None):
    """Small mesh over however many (host) devices tests forced."""
    if pod:
        return _auto_mesh((pod, data, model), ("pod", "data", "model"))
    return _auto_mesh((data, model), ("data", "model"))


def parse_mesh_spec(spec: Optional[str]):
    """CLI mesh grammar: ``"data=2,model=4"`` (ordered ``axis=size`` pairs)
    or the named presets ``"single_pod"`` / ``"multi_pod"``; ``None`` / ""
    -> no mesh (single device).

    Axis names must be mesh-rule axes the rest of the stack knows
    ("pod", "data", "model"); sizes must multiply to at most the available
    device count.  Returns a Mesh or None.
    """
    if not spec:
        return None
    if spec == "single_pod":
        return make_production_mesh()
    if spec == "multi_pod":
        return make_production_mesh(multi_pod=True)
    axes, sizes = [], []
    for part in spec.split(","):
        name, _, size = part.partition("=")
        name, size = name.strip(), size.strip()
        if name not in ("pod", "data", "model") or not size.isdigit() \
                or int(size) < 1 or name in axes:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected unique 'axis=size' pairs "
                f"with axes from pod/data/model and size >= 1, got {part!r}")
        axes.append(name)
        sizes.append(int(size))
    ndev = len(jax.devices())
    total = 1
    for s in sizes:
        total *= s
    if total > ndev:
        raise ValueError(f"mesh spec {spec!r} needs {total} devices, "
                         f"only {ndev} available")
    return _auto_mesh(sizes, axes)


def mesh_rules(mesh, arch: Optional[str] = None):
    """Pick the logical->mesh rule table for a mesh (+ per-arch overrides)."""
    from repro.distributed.sharding import MULTI_POD_RULES, SINGLE_POD_RULES
    rules = dict(MULTI_POD_RULES if "pod" in mesh.axis_names
                 else SINGLE_POD_RULES)
    if arch is not None:
        from repro import configs
        rules.update(configs.rules_overrides(arch))
    return rules

"""Window driver for emulated-GEMM cells on a mesh: one jitted
``repro.core.ozimmu_dot_general`` with a ``model`` mesh installed, its
contraction sharded over the mesh (``@model/int32``), called back to back.

Set-up builds the mesh over the cell's chips (``repro.launch.mesh``),
makes the operands on the devices, already sharded, from the seed (the
paper's test matrices as in ``bench/drivers/gemm.py``: A split along its
columns, B along its rows), compiles the call under the mesh and runs it
twice.  The window is ``gemm.py``'s: at most two calls in flight,
``gemm_tflops`` is 2*m*n*p of every call over the window.  The record's
``shape`` is one chip's group GEMM, (m, n / chips, p), so that the kernel
readers read one chip's work; ``flops`` stays the global product's.

The check has two parts:

* ``gemm_err_excess`` of ``gemm.py``, on entries drawn from the seed,
  against their double-double value (``bench/reference/dd.py``);
* ``sharded_mismatch``: the number of entries of a block of C, drawn from
  the seed, whose bits differ from the same block recomputed on one chip
  through the configuration's spec without the mesh, at the k the
  sharded call resolved.  The int32 sums are exact in any order, so the
  sharded product must equal the one-device emulation bit for bit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np

from bench import harness as H
from bench.drivers import gemm as G

TRAFFIC_KINDS = ("gemm_mesh",)
CONTROLS = G.CONTROLS
control = G.control


@dataclasses.dataclass
class State(G.State):
    mesh: Any = None
    k: Optional[int] = None
    groups_per_call: float = 0.0
    product_reduce_bytes_per_call: float = 0.0


def mesh_of(run: H.Run):
    from repro.launch.mesh import parse_mesh_spec
    axes = run.config["mesh"]
    return parse_mesh_spec(",".join(f"{a}={n}" for a, n in axes.items()))


def resolved_k(diff) -> Optional[int]:
    """The slice count of the contractions recorded in a registry diff
    (the ``k`` label of ``emulation.int8_gemms``)."""
    ks = {int(dict(labels)["k"]) for (name, labels), v in
          diff.counters.items() if name == "emulation.int8_gemms" and v}
    return ks.pop() if len(ks) == 1 else None


def setup(run: H.Run) -> State:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import ozimmu_dot_general, parse_spec
    from repro.obs import registry
    t = run.traffic
    m, n, p = int(t["m"]), int(t["n"]), int(t["p"])
    mesh = mesh_of(run)
    axis = next(iter(run.config["mesh"]))
    shardings = (NamedSharding(mesh, P(None, axis)),
                 NamedSharding(mesh, P(axis, None)))
    make = jax.jit(G.operands, static_argnums=(1, 2, 3, 4),
                   out_shardings=shardings)
    with run.span("operands"):
        a, b = make(H.seed_key(jax, run.seed), m, n, p,
                    float(run.config["operands"]["phi"]))
        jax.block_until_ready((a, b))
    cfg = parse_spec(run.config["spec"])
    reg = registry.get_registry()
    before = reg.snapshot()
    with jax.set_mesh(mesh):
        fn = jax.jit(lambda x, y: ozimmu_dot_general(x, y, G.DN, cfg))
        fn = fn.lower(a, b).compile()         # traces: the counters move
    diff = reg.snapshot().diff(before)
    mem = fn.memory_analysis()
    if mem is not None:
        H.log(f"compiled program per chip: arguments "
              f"{mem.argument_size_in_bytes}, outputs "
              f"{mem.output_size_in_bytes}, temporaries "
              f"{mem.temp_size_in_bytes}, code "
              f"{mem.generated_code_size_in_bytes} bytes")
    with run.span("warmup"):
        fn(a, b).block_until_ready()
    with run.span("warmup"):
        fn(a, b).block_until_ready()
    return State(fn, a, b, None, m, n, p,
                 diff.total("emulation.int8_gemms"), mesh=mesh,
                 k=resolved_k(diff),
                 groups_per_call=diff.total("emulation.highprec_adds"),
                 product_reduce_bytes_per_call=diff.total(
                     "emulation.product_reduce_bytes"))


def window(state: State, run: H.Run) -> dict:
    rec = G.window(state, run)
    chips = dict(state.mesh.shape)[next(iter(run.config["mesh"]))]
    rec.update(shape=[state.m, state.n // chips, state.p],
               groups_per_call=state.groups_per_call,
               product_reduce_bytes_per_call=(
                   state.product_reduce_bytes_per_call),
               device_kind=run.devices[0].device_kind)
    return rec


def block_of(seed: int, m: int, p: int, rows: int, cols: int):
    """The check's block of C, (first row, first column), from the seed."""
    rng = np.random.default_rng([int(seed), 1])
    return (int(rng.integers(0, m - rows + 1)),
            int(rng.integers(0, p - cols + 1)))


def one_chip_block(state: State, run: H.Run, r0: int, c0: int, rows: int,
                   cols: int):
    """C[r0:r0+rows, c0:c0+cols] recomputed on the first chip through the
    configuration's spec without the mesh, at the resolved k."""
    import jax

    from repro.core import ozimmu_dot_general, parse_spec
    spec = run.config["spec"].split("@")[0]
    if state.k is not None:
        spec = spec.replace("-auto", f"-{state.k}")
    dev = run.devices[0]
    a = jax.device_put(state.a[r0:r0 + rows], dev)
    b = jax.device_put(state.b[:, c0:c0 + cols], dev)
    cfg = parse_spec(spec)
    fn = jax.jit(lambda x, y: ozimmu_dot_general(x, y, G.DN, cfg))
    return np.asarray(fn(a, b))


def check(state: State, run: H.Run, rec: dict):
    ok, checks = G.check_product(state.out, state, run)
    chk = run.config["check"]["sharded_mismatch"]
    rows, cols = int(chk["rows"]), int(chk["cols"])
    r0, c0 = block_of(run.seed, state.m, state.p, rows, cols)
    got = np.asarray(state.out[r0:r0 + rows, c0:c0 + cols])
    want = one_chip_block(state, run, r0, c0, rows, cols)
    differ = int(np.count_nonzero(got.view(np.uint32)
                                  != want.view(np.uint32)))
    H.log(f"sharded check: block at ({r0}, {c0}) of {rows}x{cols}, "
          f"{differ} entries differ from one chip at k = {state.k}")
    entry = H.check_entry("sharded_mismatch", float(differ),
                          float(chk["limit"]))
    state.out = state.fn = state.a = state.b = None
    return ok and entry["ok"], checks + [entry]

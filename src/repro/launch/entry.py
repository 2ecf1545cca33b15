"""Process set-up shared by the entry points.

The entry points (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py``) call
:func:`prepare_entry_point` once when run as programs, before JAX starts
a backend.  It does two things:

* :func:`require_exact_rounding` turns off XLA's excess precision, so a
  ``:fused`` model and its XLA twin give bitwise the same tokens (see
  docs/engine.md, "Compiled or interpreted kernels");
* :func:`enable_compile_cache` turns on JAX's persistent compilation
  cache.

Library code and the tests do neither; a program that embeds the library
and wants the bitwise rule for whole models sets the flag itself.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax
from jax._src import xla_bridge

# one fixed directory inside the checkout (listed in .gitignore): the
# cache key includes the path, so it never depends on a temporary name
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

# By default XLA may keep a bf16 value in f32 between fused ops instead
# of rounding it, and which roundings it skips depends on the fusions
# around each Pallas call: a :fused program and its XLA twin then round
# differently outside the (bitwise equal) emulated contractions.
EXACT_ROUNDING_FLAG = "--xla_allow_excess_precision=false"


def require_exact_rounding() -> None:
    """Add :data:`EXACT_ROUNDING_FLAG` to ``XLA_FLAGS``.

    XLA reads the flags when the first backend starts, so this must run
    before anything touches a device; raises if a backend already started
    without the flag."""
    flags = os.environ.get("XLA_FLAGS", "")
    if EXACT_ROUNDING_FLAG in flags.split():
        return
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"a JAX backend started before {EXACT_ROUNDING_FLAG} was set; "
            f"call prepare_entry_point() before touching a device")
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (flags,
                                                     EXACT_ROUNDING_FLAG)))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already keeps the cache
    there and no other directory is set.  Otherwise the cache lives at
    ``<checkout>/.jax_cache``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    CHECKOUT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def prepare_entry_point() -> str:
    """:func:`require_exact_rounding`, then :func:`enable_compile_cache`;
    returns the cache directory."""
    require_exact_rounding()
    return enable_compile_cache()

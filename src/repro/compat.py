"""The few JAX API surfaces the repo pins in one place (jax 0.9).

Everything else in the repo goes through these helpers rather than naming
the JAX spellings inline:

  * :func:`enable_x64` — the 64-bit scope the packed triple keys need,
  * :func:`make_mesh` — meshes with explicit all-Auto axis types,
  * :func:`shard_map` — unchecked ``jax.shard_map``,
  * :func:`xla_cost_analysis` — ``Compiled.cost_analysis()`` as a dict,
  * :func:`use_compilation_cache` — the persistent compile cache of the
    chip-facing entry points,
  * ``Jaxpr`` / ``ClosedJaxpr`` — the jaxpr types the trace audit walks.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = [
    "ClosedJaxpr", "Jaxpr", "enable_x64", "make_mesh", "shard_map",
    "use_compilation_cache", "xla_cost_analysis",
]

# a fixed path inside the checkout: a directory named afresh on each run
# (a temporary name, a process id) would never be found again
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_x64():
    """Context manager: 64-bit dtypes on inside the ``with`` block."""
    return jax.enable_x64(True)


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with explicit-Auto axis types."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        **kwargs,
    )


def shard_map(fn, *, mesh, in_specs, out_specs):
    """Unchecked ``jax.shard_map``.

    VMA checking is disabled: the engine's round body mixes replicated and
    sharded outputs in ways the checker rejects (the all-gather/all_to_all
    exchanges are hand-verified instead).
    """
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def xla_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a flat dict (``{}`` when empty)."""
    return dict(compiled.cost_analysis() or {})


def use_compilation_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it itself;
    otherwise the cache lives at :data:`DEFAULT_CACHE_DIR`.  Call before the
    first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path

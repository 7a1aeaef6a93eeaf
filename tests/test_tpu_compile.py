"""Ahead-of-time compiles for a TPU v5e of the kernels and the snapshot build.

Nothing runs: each test lowers a function at real widths for a described
(not attached) ``v5e:2x2`` topology and compiles it with the chip's own
compiler, which refuses what interpret mode accepts (unsupported layouts,
reductions and 64-bit values inside a kernel).  The topology is described
inside a fixture, never at import, so that only the test process that is
given this file loads the TPU compiler.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.compat import enable_x64

# the arena ``chip_smoke.py`` gives its default-sized store: 4x its 84,000
# explicit facts rounded up to 2^19 rows, plus the trash row
SMOKE_ARENA_ROWS = (1 << 19) + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # the TPU compiler otherwise writes its logs to the temporary directory
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_dedup_order_compiles(one_chip):
    from repro.kernels.dedup import dedup_order

    with enable_x64():
        keys = _shape((1 << 14,), jnp.int64, one_chip)
        compiled = jax.jit(
            lambda k: dedup_order(k, interpret=False)
        ).lower(keys).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("under_x64", [False, True], ids=["x32", "x64"])
def test_rewrite_triples_compiles(one_chip, under_x64):
    """The engine calls the kernel inside ``enable_x64``; it must lower
    with no 64-bit value either way."""
    from repro.kernels.rewrite_triples import rewrite_owner

    spo = _shape((1 << 14, 3), jnp.int32, one_chip)
    rho = _shape((1 << 20,), jnp.int32, one_chip)

    def lower():
        return jax.jit(
            lambda a, b: rewrite_owner(a, b, 4, interpret=False)
        ).lower(spo, rho).compile()

    if under_x64:
        with enable_x64():
            compiled = lower()
    else:
        compiled = lower()
    assert _has_kernel(compiled)


def test_publish_snapshot_compiles(one_chip):
    from repro.core.engine_jax import _publish_snapshot

    n = SMOKE_ARENA_ROWS
    with enable_x64():
        compiled = jax.jit(_publish_snapshot).lower(
            _shape((n, 3), jnp.int32, one_chip),
            _shape((n,), jnp.int32, one_chip),
            _shape((n,), jnp.int64, one_chip),
        ).compile()
    mem = compiled.memory_analysis()
    # both sorted orders of the arena plus the argsort scratch fit one chip
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16 * 2**30

"""Pallas TPU kernel: fused triple rewrite ``out = rho[spo]`` + changed mask.

The bulk Algorithm-3 sweep: every triple's three positions are mapped through
the representative table and a per-row 'outdated' flag is produced in the
same pass.  Same one-hot-matmul gather as :mod:`repro.kernels.pointer_jump`.
Triples are laid out transposed, (3, n), so each position is one lane-dense
row and the kernel needs no in-register reshape.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# block index maps must return int32 even when traced under enable_x64,
# where a bare Python 0 would become an int64 constant Mosaic cannot lower
_ZERO = np.int32(0)


def _kernel(spo_ref, rho_ref, out_ref, changed_ref, *, tile: int):
    t = pl.program_id(1)
    spo = spo_ref[...]  # (3, B) int32: one row per triple position
    rho = rho_ref[...].astype(jnp.float32)  # (1, T)
    b = spo.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (tile, b), 0)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)
        changed_ref[...] = jnp.zeros_like(changed_ref)

    for pos in range(3):
        rel = spo[pos:pos + 1, :] - t * tile  # (1, B)
        # IDs outside this tile match no iota row and gather 0
        onehot = (rel == iota).astype(jnp.float32)  # (T, B)
        # f32 holds every ID below 2^24 exactly; HIGHEST keeps the MXU from
        # rounding the operands to bf16
        vals = jnp.dot(
            rho, onehot, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        ).astype(jnp.int32)  # (1, B)
        out_ref[pos:pos + 1, :] += vals
        # int32 max, not a bool any: Mosaic has no bool reduction
        in_tile = (rel >= 0) & (rel < tile)
        moved = (in_tile & (vals != spo[pos:pos + 1, :])).astype(jnp.int32)
        changed_ref[...] = jnp.maximum(changed_ref[...], moved)


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def rewrite_triples(
    spo: jnp.ndarray,
    rho: jnp.ndarray,
    *,
    block: int = 256,
    tile: int = 512,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (rho[spo], changed) for (n,3) int32 triples."""
    n = spo.shape[0]
    v = rho.shape[0]
    n_pad = -n % block
    v_pad = -v % tile
    # positions on sublanes, triples on lanes
    spo_t = jnp.pad(spo, ((0, n_pad), (0, 0))).T
    rho_p = jnp.pad(rho, (0, v_pad)).reshape(1, -1)
    grid = (spo_t.shape[1] // block, rho_p.shape[1] // tile)
    out, changed = pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((3, block), lambda i, t: (_ZERO, i)),
            pl.BlockSpec((1, tile), lambda i, t: (_ZERO, t)),
        ],
        out_specs=[
            pl.BlockSpec((3, block), lambda i, t: (_ZERO, i)),
            pl.BlockSpec((1, block), lambda i, t: (_ZERO, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(spo_t.shape, jnp.int32),
            jax.ShapeDtypeStruct((1, spo_t.shape[1]), jnp.int32),
        ],
        interpret=interpret,
    )(spo_t, rho_p)
    return out.T[:n], changed[0, :n].astype(bool)


def rewrite_owner(
    spo: jnp.ndarray, rho: jnp.ndarray, n_shards: int, **kw
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused ``(rho[spo], owner)`` where owner = subject representative mod
    the shard count — the routing key of the engine's owner-routed delta
    exchange.  Used by the incremental delete path to owner-sort tombstone
    seed queries before they are shipped to the mesh."""
    out, _changed = rewrite_triples(spo, rho, **kw)
    return out, out[:, 0] % jnp.int32(n_shards)

"""Pallas TPU kernel: one pointer-doubling step ``out[i] = table[idx[i]]``.

The union-find compression loop is ``rep = rep[rep]`` iterated
O(log depth) times.  On TPU there is no scalar gather from HBM worth its DMA
cost, so the gather is reformulated as **one-hot matmul over table tiles**:
for each VMEM-resident tile ``table[t0:t0+T]``, rows whose index falls inside
the tile contribute ``onehot(idx - t0) @ tile`` on the MXU; accumulating over
tiles yields the full gather.  Values are resource IDs < 2^21, which are exact
in float32, so the matmul is lossless.

Grid: ``(n_index_blocks, n_table_tiles)`` — the tile dimension iterates
fastest, so output accumulation is safe.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(idx_ref, table_ref, out_ref, *, tile: int):
    t = pl.program_id(1)
    idx = idx_ref[...]  # (B, 1) int32
    table = table_ref[...]  # (T, 1) int32
    b = idx.shape[0]
    rel = idx[:, 0] - t * tile  # (B,)
    in_tile = (rel >= 0) & (rel < tile)
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, tile), 1)
    onehot = jnp.where(in_tile[:, None], (rel[:, None] == iota), False)
    vals = jnp.dot(
        onehot.astype(jnp.float32),
        table.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )  # (B, 1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += vals.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def pointer_jump(
    idx: jnp.ndarray,
    table: jnp.ndarray,
    *,
    block: int = 512,
    tile: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """table[idx] for int32 1-D ``idx`` and ``table`` (padded to block/tile)."""
    n = idx.shape[0]
    v = table.shape[0]
    n_pad = -n % block
    v_pad = -v % tile
    idx_p = jnp.pad(idx, (0, n_pad)).reshape(-1, 1)
    table_p = jnp.pad(table, (0, v_pad)).reshape(-1, 1)
    grid = (idx_p.shape[0] // block, table_p.shape[0] // tile)
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, 1), lambda i, t: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((idx_p.shape[0], 1), idx.dtype),
        interpret=interpret,
    )(idx_p, table_p)
    return out[:n, 0]

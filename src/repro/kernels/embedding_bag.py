"""Pallas TPU kernel: EmbeddingBag (gather + per-sample reduce) for recsys.

JAX has no native EmbeddingBag; the assignment mandates building it.  For a
batch of per-field categorical IDs ``ids (B, F)`` and a table ``(V, K)``, the
bag output is ``out[b] = sum_f table[ids[b, f]]``.  TPU-native formulation:
tile the table over VMEM; for each tile, ``onehot(ids - t0) @ tile`` on the
MXU contributes the rows that live in the tile; sum over the field axis
happens in the same pass (fused reduce).

Grid: (n_batch_blocks, n_table_tiles); table tiles iterate fastest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(ids_ref, table_ref, out_ref, *, tile: int):
    t = pl.program_id(1)
    ids = ids_ref[...]  # (B, F) int32
    table = table_ref[...]  # (T, K)
    b, f = ids.shape
    rel = ids.reshape(b * f) - t * tile
    in_tile = (rel >= 0) & (rel < tile)
    iota = jax.lax.broadcasted_iota(jnp.int32, (b * f, tile), 1)
    onehot = jnp.where(in_tile[:, None], rel[:, None] == iota, False)
    gathered = jnp.dot(
        onehot.astype(table.dtype), table, preferred_element_type=jnp.float32
    )  # (B*F, K)
    bag = gathered.reshape(b, f, -1).sum(axis=1)  # fused field reduce

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += bag.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def embedding_bag(
    ids: jnp.ndarray,
    table: jnp.ndarray,
    *,
    block: int = 128,
    tile: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """out (B, K) = sum_f table[ids[:, f]] for int32 ids (B, F)."""
    b, f = ids.shape
    v, k = table.shape
    b_pad = -b % block
    v_pad = -v % tile
    ids_p = jnp.pad(ids, ((0, b_pad), (0, 0)), constant_values=v + v_pad)  # off-table
    table_p = jnp.pad(table, ((0, v_pad), (0, 0)))
    grid = (ids_p.shape[0] // block, table_p.shape[0] // tile)
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, f), lambda i, t: (i, 0)),
            pl.BlockSpec((tile, k), lambda i, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((block, k), lambda i, t: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((ids_p.shape[0], k), jnp.float32),
        interpret=interpret,
    )(ids_p, table_p)
    return out[:b].astype(table.dtype)

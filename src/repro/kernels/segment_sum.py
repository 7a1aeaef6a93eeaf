"""Pallas TPU kernel: segment-sum (the GNN message-passing scatter).

``out[s] = sum_{i : seg[i] == s} x[i]`` — the core aggregation of every
SpMM-regime GNN (GCN/GatedGCN/PNA message reduce) and of the EmbeddingBag
gradient.  TPU-native formulation: transpose-one-hot matmul per (segment
tile × input block): ``onehot(seg - s0)^T @ x`` on the MXU, accumulated over
input blocks.

Grid: (n_segment_tiles, n_input_blocks); input blocks iterate fastest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(seg_ref, x_ref, out_ref, *, stile: int):
    s = pl.program_id(0)
    i = pl.program_id(1)
    seg = seg_ref[...]  # (B, 1) int32
    x = x_ref[...]  # (B, K)
    b = seg.shape[0]
    rel = seg[:, 0] - s * stile
    in_tile = (rel >= 0) & (rel < stile)
    iota = jax.lax.broadcasted_iota(jnp.int32, (b, stile), 1)
    onehot = jnp.where(in_tile[:, None], rel[:, None] == iota, False)
    contrib = jnp.dot(
        onehot.astype(x.dtype).T, x, preferred_element_type=jnp.float32
    )  # (S_t, K)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += contrib.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_segments", "block", "stile", "interpret"))
def segment_sum(
    x: jnp.ndarray,
    seg: jnp.ndarray,
    n_segments: int,
    *,
    block: int = 512,
    stile: int = 512,
    interpret: bool,
) -> jnp.ndarray:
    """(N, K) values + (N,) int32 segment ids -> (n_segments, K) sums."""
    n, k = x.shape
    n_pad = -n % block
    s_pad = -n_segments % stile
    x_p = jnp.pad(x, ((0, n_pad), (0, 0)))
    seg_p = jnp.pad(seg, (0, n_pad), constant_values=n_segments + s_pad).reshape(-1, 1)
    n_seg_p = n_segments + s_pad
    grid = (n_seg_p // stile, x_p.shape[0] // block)
    out = pl.pallas_call(
        functools.partial(_kernel, stile=stile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, 1), lambda s, i: (i, 0)),
            pl.BlockSpec((block, k), lambda s, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((stile, k), lambda s, i: (s, 0)),
        out_shape=jax.ShapeDtypeStruct((n_seg_p, k), jnp.float32),
        interpret=interpret,
    )(seg_p, x_p)
    return out[:n_segments].astype(x.dtype)

"""Pallas TPU kernel: fused FM pairwise interaction (sum-square trick).

FM (Rendle, ICDM'10): sum_{i<j} <v_i, v_j> x_i x_j computed in O(F*K) as
``0.5 * sum_k ((sum_f x)^2 - sum_f x^2)``.  One fused VPU pass per batch
block: both reductions and the final combine never leave VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, out_ref, *, f: int, k: int):
    x = x_ref[...]  # (B, F*K)
    b = x.shape[0]
    xf = x.reshape(b, f, k).astype(jnp.float32)
    s = xf.sum(axis=1)  # (B, K)
    sq = (xf * xf).sum(axis=1)  # (B, K)
    out_ref[...] = (0.5 * (s * s - sq).sum(axis=1, keepdims=True)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fm_interact(
    x: jnp.ndarray,
    *,
    block: int = 256,
    interpret: bool,
) -> jnp.ndarray:
    """x (B, F, K) field embeddings (already scaled by feature values) ->
    (B,) second-order FM interaction term."""
    b, f, k = x.shape
    b_pad = -b % block
    x_p = jnp.pad(x.reshape(b, f * k), ((0, b_pad), (0, 0)))
    grid = (x_p.shape[0] // block,)
    out = pl.pallas_call(
        functools.partial(_kernel, f=f, k=k),
        grid=grid,
        in_specs=[pl.BlockSpec((block, f * k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x_p.shape[0], 1), x.dtype),
        interpret=interpret,
    )(x_p)
    return out[:b, 0]

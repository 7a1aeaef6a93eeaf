"""Pallas TPU kernel: batched sorted-set search bounds by *counting*.

The engine's merge joins need (lower, upper) bounds of query keys in a sorted
key column.  Classic binary search needs log(N) dependent gathers — hostile
to the VPU.  The TPU-idiomatic formulation: for sorted keys,

    lower[q] = #{k : k < q},     upper[q] = #{k : k <= q},

which is a tiled compare-and-reduce — pure VPU work, trivially blocked, and
accumulation-safe over key tiles.  Keys are the engine's packed int64 values
split into (hi, lo) int32 pairs compared lexicographically (lo unsigned).

Grid: (n_query_blocks, n_key_tiles); key tiles iterate fastest.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(qhi_ref, qlo_ref, khi_ref, klo_ref, lo_ref, hi_ref):
    t = pl.program_id(1)
    qhi = qhi_ref[...]  # (B, 1) int32
    qlo = qlo_ref[...].astype(jnp.uint32)
    khi = khi_ref[...]  # (T, 1) int32
    klo = klo_ref[...].astype(jnp.uint32)

    # lexicographic (hi, lo-unsigned) compare, broadcast (B, T)
    k_lt_q = (khi[None, :, 0] < qhi[:, :1]) | (
        (khi[None, :, 0] == qhi[:, :1]) & (klo[None, :, 0] < qlo[:, :1])
    )
    k_le_q = (khi[None, :, 0] < qhi[:, :1]) | (
        (khi[None, :, 0] == qhi[:, :1]) & (klo[None, :, 0] <= qlo[:, :1])
    )

    @pl.when(t == 0)
    def _init():
        lo_ref[...] = jnp.zeros_like(lo_ref)
        hi_ref[...] = jnp.zeros_like(hi_ref)

    lo_ref[...] += jnp.sum(k_lt_q, axis=1, keepdims=True).astype(jnp.int32)
    hi_ref[...] += jnp.sum(k_le_q, axis=1, keepdims=True).astype(jnp.int32)


def search_bounds(
    queries,
    keys,
    *,
    block: int = 256,
    tile: int = 1024,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(lower, upper) positions of int64 ``queries`` in sorted int64 ``keys``.

    The int64 -> (hi, lo) int32 split happens on the host with numpy so the
    kernel never needs the x64 flag.  Padding keys must sort above every real
    key: INT64_MAX, which the engine reserves as a sentinel.
    """
    import numpy as np

    queries = np.asarray(queries, np.int64)
    keys = np.asarray(keys, np.int64)
    n, v = queries.shape[0], keys.shape[0]
    n_pad = -n % block
    v_pad = -v % tile
    q = np.pad(queries, (0, n_pad))
    k = np.pad(keys, (0, v_pad), constant_values=(1 << 63) - 1)

    def split(x):
        hi = (x >> 32).astype(np.int32).reshape(-1, 1)
        lo = (x & np.int64((1 << 32) - 1)).astype(np.uint32)
        return jnp.asarray(hi), jnp.asarray(lo.astype(np.int32).reshape(-1, 1))

    qhi, qlo = split(q)
    khi, klo = split(k)
    lo, hi = _search_bounds_call(qhi, qlo, khi, klo, block=block, tile=tile, interpret=interpret)
    return lo[:n, 0], hi[:n, 0]


def prefix_range_bounds(
    prefix_cols,
    keys,
    *,
    block: int = 256,
    tile: int = 1024,
    interpret: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(start, end) index ranges of (s, p, o)-prefix queries in sorted keys.

    The kernel form of the persistent-index range probe the engine's
    head-bound joins issue per binding (``_expand_join_index`` /
    ``eval_plan_rederive``): an atom whose fixed positions form a length-k
    prefix of the packed (s, p, o) key order matches exactly the keys in
    ``[pack(prefix, 0...), pack(prefix, max...)]``, so its range is one
    lower bound of the low key and one upper bound of the high key — both
    produced by the same counting kernel in a single fused call (low and
    high queries concatenated).

    ``prefix_cols`` is an (n, k) int array of the leading fixed positions,
    1 <= k <= 3, values below ``2**21`` (the engine's ID width).  Returns
    int32 arrays with ``start[i]:end[i]`` the half-open match range of
    query ``i``.
    """
    import numpy as np

    pc = np.asarray(prefix_cols, np.int64)
    n, k = pc.shape
    if not 1 <= k <= 3:
        raise ValueError(f"prefix length must be 1..3, got {k}")
    maxid = np.int64((1 << 21) - 1)
    lo = np.zeros(n, np.int64)
    hi = np.zeros(n, np.int64)
    for j in range(3):
        lo = (lo << 21) | (pc[:, j] if j < k else 0)
        hi = (hi << 21) | (pc[:, j] if j < k else maxid)
    lower, upper = search_bounds(
        np.concatenate([lo, hi]), keys, block=block, tile=tile,
        interpret=interpret,
    )
    return lower[:n], upper[n:]


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def _search_bounds_call(qhi, qlo, khi, klo, *, block, tile, interpret):
    grid = (qhi.shape[0] // block, khi.shape[0] // tile)
    lo, hi = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, 1), lambda i, t: (i, 0)),
            pl.BlockSpec((block, 1), lambda i, t: (i, 0)),
            pl.BlockSpec((tile, 1), lambda i, t: (t, 0)),
            pl.BlockSpec((tile, 1), lambda i, t: (t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block, 1), lambda i, t: (i, 0)),
            pl.BlockSpec((block, 1), lambda i, t: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((qhi.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((qhi.shape[0], 1), jnp.int32),
        ],
        interpret=interpret,
    )(qhi, qlo, khi, klo)
    # padded keys sort above all queries, so counts need no correction;
    # padded queries produce garbage rows that the caller slices away.
    return lo, hi

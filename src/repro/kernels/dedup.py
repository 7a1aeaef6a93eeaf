"""Sort-free stable dedup order via a Pallas counting-rank kernel.

The engine's last per-round delta-width sorts (candidate-stream dedup in
``process_candidates`` step 7, binding-table grouping in ``_expand_join``)
only need the *stable ascending permutation* of a packed int64 key buffer —
nothing downstream wants a sorted array per se, only where each key would
land.  That rank is a counting problem:

    rank[i] = #{j : key[j] < key[i]} + #{j < i : key[j] == key[i]}

which tiles exactly like :mod:`repro.kernels.bsearch`'s counting kernel: a
(query-block x key-tile) grid accumulating per-query counts across key
tiles, with the int64 keys split into (hi, lo) int32 halves so the kernel
never touches a 64-bit lane (hi compares signed — packed keys are
non-negative — and lo compares unsigned).  The split uses
``lax.bitcast_convert_type``, a bit-level reinterpretation, NOT a narrowing
value conversion — the distinction DtypeSafety enforces.

Scattering ``iota`` through the rank then yields the permutation itself:

    order[rank[i]] = i      (== jnp.argsort(keys, stable=True))

O(n^2/p) work instead of O(n log n), with zero sort primitives — the right
trade for the short padded delta buffers of steady-state maintenance where
the XLA sort's dispatch/fusion overhead dominates, and the last piece the
fused round loop needs to lint clean under a no-sort budget.  Opt-in via
``JaxEngine(use_kernel=True)``; invalid slots ride along as KEY_MAX rows
and end up stably last, exactly as under the argsort they replace.
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


def _rank_kernel(qhi_ref, qlo_ref, khi_ref, klo_ref, rank_ref, *, block, tile):
    i = pl.program_id(0)
    t = pl.program_id(1)
    # queries are a (block, 1) column, keys a (1, tile) row: the compares
    # broadcast to (block, tile) with no in-kernel relayout
    qhi = qhi_ref[...]  # high halves, signed compare
    khi = khi_ref[...]
    # low halves compare UNSIGNED: reinterpret the int32 bits as uint32
    qlo = qlo_ref[...].astype(jnp.uint32)
    klo = klo_ref[...].astype(jnp.uint32)
    # global element indices tie-break equal keys by position (stability)
    q_idx = i * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    k_idx = t * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
    k_lt_q = (khi < qhi) | ((khi == qhi) & (klo < qlo))
    k_eq_q = (khi == qhi) & (klo == qlo)
    counts = (k_lt_q | (k_eq_q & (k_idx < q_idx))).astype(jnp.int32)

    @pl.when(t == 0)
    def _init():
        rank_ref[...] = jnp.zeros_like(rank_ref)

    rank_ref[...] += jnp.sum(counts, axis=1, keepdims=True, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def _rank_call(qhi, qlo, khi, klo, *, block, tile, interpret):
    n_q, n_k = qhi.shape[0], khi.shape[1]
    grid = (n_q // block, n_k // tile)
    return pl.pallas_call(
        functools.partial(_rank_kernel, block=block, tile=tile),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, 1), lambda i, t: (i, _ZERO)),
            pl.BlockSpec((block, 1), lambda i, t: (i, _ZERO)),
            pl.BlockSpec((1, tile), lambda i, t: (_ZERO, t)),
            pl.BlockSpec((1, tile), lambda i, t: (_ZERO, t)),
        ],
        out_specs=pl.BlockSpec((block, 1), lambda i, t: (i, _ZERO)),
        out_shape=jax.ShapeDtypeStruct((n_q, 1), jnp.int32),
        interpret=interpret,
    )(qhi, qlo, khi, klo)


def _split_halves(keys):
    """(n,) int64 -> ((n,1) hi int32, (n,1) lo int32) via bitcast.

    ``bitcast_convert_type`` to a narrower type adds a minor dimension of
    size 2 ordered low-half-first; no value conversion happens, so packed
    keys keep their 63 bits across the split.
    """
    parts = jax.lax.bitcast_convert_type(keys, jnp.int32)  # (n, 2)
    return parts[:, 1:2], parts[:, 0:1]


def dedup_order(keys, *, block: int = 128, tile: int = 128, interpret: bool):
    """Stable ascending permutation of ``keys`` ((n,) int64, non-negative).

    ``order = dedup_order(k)`` satisfies ``k[order] == jnp.sort(k)`` with
    ties kept in input order — a drop-in for
    ``jnp.argsort(keys, stable=True)`` built from counting + one
    delta-width scatter, no sort primitive.
    """
    n = keys.shape[0]
    kmax = jnp.asarray((1 << 63) - 1, keys.dtype)
    q_pad = -n % block
    k_pad = -n % tile
    q = jnp.concatenate([keys, jnp.full((q_pad,), kmax)]) if q_pad else keys
    k = jnp.concatenate([keys, jnp.full((k_pad,), kmax)]) if k_pad else keys
    qhi, qlo = _split_halves(q)
    khi, klo = _split_halves(k)
    khi, klo = khi.reshape(1, -1), klo.reshape(1, -1)
    # key-side padding never perturbs real ranks: a pad is >= every key and
    # its tie-break index >= n, so it counts into no query slot below n
    rank = _rank_call(
        qhi, qlo, khi, klo, block=block, tile=tile, interpret=interpret
    )[:n, 0]
    iota = jnp.arange(n, dtype=jnp.int32)
    return jnp.zeros((n,), jnp.int32).at[rank].set(iota)

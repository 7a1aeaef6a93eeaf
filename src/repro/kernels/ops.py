"""Public wrappers for the Pallas kernels: the one place interpret mode is
chosen.

The raw kernels take ``interpret`` as a required keyword.  These wrappers
fill it from :func:`interpret_mode` at call time: Mosaic on a TPU backend,
the Pallas interpreter (the kernel body run as traced JAX ops, same
semantics) everywhere else.  An explicit ``interpret=`` still wins.
"""

from __future__ import annotations

import jax

from .bsearch import (
    prefix_range_bounds as _prefix_range_bounds,
    search_bounds as _search_bounds,
)
from .dedup import dedup_order as _dedup_order
from .embedding_bag import embedding_bag as _embedding_bag
from .flash_attention import flash_attention_bhsd as _flash_attention_bhsd
from .fm_interact import fm_interact as _fm_interact
from .pointer_jump import pointer_jump as _pointer_jump
from .rewrite_triples import (
    rewrite_owner as _rewrite_owner,
    rewrite_triples as _rewrite_triples,
)
from .segment_sum import segment_sum as _segment_sum


def interpret_mode() -> bool:
    """Whether kernels must run interpreted: on every backend but the TPU."""
    return jax.default_backend() != "tpu"


def pointer_jump(idx, table, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _pointer_jump(idx, table, **kw)


def rewrite_triples(spo, rho, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _rewrite_triples(spo, rho, **kw)


def rewrite_owner(spo, rho, n_shards, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _rewrite_owner(spo, rho, n_shards, **kw)


def search_bounds(queries, keys, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _search_bounds(queries, keys, **kw)


def prefix_range_bounds(prefix_cols, keys, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _prefix_range_bounds(prefix_cols, keys, **kw)


def dedup_order(keys, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _dedup_order(keys, **kw)


def embedding_bag(ids, table, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _embedding_bag(ids, table, **kw)


def fm_interact(x, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _fm_interact(x, **kw)


def segment_sum(x, seg, n_segments, **kw):
    kw.setdefault("interpret", interpret_mode())
    return _segment_sum(x, seg, n_segments, **kw)


def flash_attention(q, k, v, causal=True, q_offset=0, **kw):
    """q (B,S,H,D), k/v (B,T,KV,D) -> (B,S,H,D); GQA flash attention."""
    kw.setdefault("interpret", interpret_mode())
    out = _flash_attention_bhsd(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        q_offset,
        causal=causal,
        **kw,
    )
    return out.transpose(0, 2, 1, 3)

"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("n,v", [(7, 13), (100, 100), (513, 1000), (2000, 257)])
@pytest.mark.parametrize("block,tile", [(128, 128), (512, 512), (64, 256)])
def test_pointer_jump_sweep(n, v, block, tile):
    idx = jnp.asarray(RNG.integers(0, v, n), jnp.int32)
    table = jnp.asarray(RNG.integers(0, 1 << 20, v), jnp.int32)
    out = ops.pointer_jump(idx, table, block=block, tile=tile)
    np.testing.assert_array_equal(out, ref.pointer_jump_ref(idx, table))


@pytest.mark.parametrize("n,v", [(5, 9), (300, 512), (1025, 700)])
def test_rewrite_triples_sweep(n, v):
    spo = jnp.asarray(RNG.integers(0, v, (n, 3)), jnp.int32)
    rho = jnp.asarray(np.arange(v), jnp.int32)
    # merge ~30% of resources
    merges = RNG.integers(0, v, v // 3)
    rho = rho.at[merges].set(jnp.asarray(RNG.integers(0, v, v // 3), jnp.int32))
    out, changed = ops.rewrite_triples(spo, rho)
    ref_out, ref_changed = ref.rewrite_triples_ref(spo, rho)
    np.testing.assert_array_equal(out, ref_out)
    np.testing.assert_array_equal(changed, ref_changed)


@pytest.mark.parametrize("nq,nk", [(10, 64), (257, 1000), (1000, 3)])
@pytest.mark.parametrize("big", [False, True])
def test_search_bounds_sweep(nq, nk, big):
    hi_bits = 62 if big else 20  # exercise >32-bit keys (the packed-key case)
    keys = np.sort(RNG.integers(0, 1 << hi_bits, nk).astype(np.int64))
    queries = np.concatenate(
        [RNG.choice(keys, nq // 2), RNG.integers(0, 1 << hi_bits, nq - nq // 2)]
    ).astype(np.int64)
    lo, hi = ops.search_bounds(queries, keys)
    rlo, rhi = ref.search_bounds_ref(queries, keys)
    np.testing.assert_array_equal(lo, rlo)
    np.testing.assert_array_equal(hi, rhi)


@pytest.mark.parametrize("nq,nk", [(9, 50), (300, 1000)])
@pytest.mark.parametrize("k", [1, 2, 3])
def test_prefix_range_bounds_sweep(nq, nk, k):
    """The bound-head probe of the targeted rederive join: a length-k
    (s, p, o) prefix matches one contiguous range of the sorted packed-key
    column.  IDs are drawn small so ranges are frequently non-empty."""
    ids = RNG.integers(0, 12, (nk, 3)).astype(np.int64)
    keys = np.sort((ids[:, 0] << 42) | (ids[:, 1] << 21) | ids[:, 2])
    prefixes = RNG.integers(0, 14, (nq, k)).astype(np.int32)
    start, end = ops.prefix_range_bounds(prefixes, keys)
    rstart, rend = ref.prefix_range_bounds_ref(prefixes, keys)
    np.testing.assert_array_equal(start, rstart)
    np.testing.assert_array_equal(end, rend)
    assert (end >= start).all()
    # spot-check: every row inside a range actually carries the prefix
    shift = 21 * (3 - k)
    packed_pref = np.zeros(nq, np.int64)
    for j in range(k):
        packed_pref = (packed_pref << 21) | prefixes[:, j]
    for i in range(min(nq, 32)):
        rows = keys[start[i]:end[i]]
        assert ((rows >> shift) == packed_pref[i]).all()


@pytest.mark.parametrize(
    "n,block,tile", [(7, 128, 128), (128, 128, 128), (513, 128, 256), (1000, 64, 128)]
)
@pytest.mark.parametrize("dup", [0.0, 0.5, 1.0])
def test_dedup_order_sweep(n, block, tile, dup):
    """Stable rank permutation == jnp.argsort(stable) over packed keys with
    duplicates and KEY_MAX padding slots (the delta-stream dedup shape).

    Unlike the other int64 kernels, dedup_order is called INSIDE traced
    engine code (the fused round loop), so it takes traced int64 keys under
    the engine's x64 scope — the test mirrors that calling convention."""
    from repro.compat import enable_x64

    keys = RNG.integers(0, 1 << 62, n).astype(np.int64)
    n_dup = int(n * dup)
    if n_dup:
        keys[RNG.integers(0, n, n_dup)] = RNG.choice(keys, n_dup)
    keys[-max(n // 8, 1):] = (1 << 63) - 1  # invalid-slot sentinels
    with enable_x64():
        order = ops.dedup_order(jnp.asarray(keys), block=block, tile=tile)
        # ranking ties by position IS argsort stability
        want = jnp.argsort(jnp.asarray(keys), stable=True)
        np.testing.assert_array_equal(order, np.asarray(want, np.int32))
    np.testing.assert_array_equal(order, ref.dedup_order_ref(keys))


@pytest.mark.parametrize("b,f,v,k", [(4, 3, 50, 8), (130, 39, 1000, 10), (64, 26, 513, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_sweep(b, f, v, k, dtype):
    ids = jnp.asarray(RNG.integers(0, v, (b, f)), jnp.int32)
    table = jnp.asarray(RNG.normal(size=(v, k)), dtype)
    out = ops.embedding_bag(ids, table)
    expected = ref.embedding_bag_ref(ids, table)
    rtol = 1e-6 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expected, np.float32), rtol=rtol, atol=1e-3
    )


@pytest.mark.parametrize("b,f,k", [(3, 5, 4), (300, 39, 10), (1024, 26, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fm_interact_sweep(b, f, k, dtype):
    x = jnp.asarray(RNG.normal(size=(b, f, k)), dtype)
    out = ops.fm_interact(x)
    expected = ref.fm_interact_ref(x)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(expected, np.float32),
        rtol=1e-5 if dtype == jnp.float32 else 5e-2,
        atol=1e-2,
    )


@pytest.mark.parametrize("n,s,k", [(10, 4, 8), (1000, 100, 16), (513, 700, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_sum_sweep(n, s, k, dtype):
    x = jnp.asarray(RNG.normal(size=(n, k)), dtype)
    seg = jnp.asarray(RNG.integers(0, s, n), jnp.int32)
    out = ops.segment_sum(x, seg, s)
    # oracle in f32: the kernel accumulates in f32 (preferred_element_type),
    # which is *more* precise than a bf16 jnp chain — compare to ground truth
    expected = ref.segment_sum_ref(x.astype(jnp.float32), seg, s)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(expected, np.float32),
        rtol=1e-5 if dtype == jnp.float32 else 1e-1,
        atol=1e-2 if dtype == jnp.float32 else 1e-1,
    )


def test_pointer_jump_converges_like_uf():
    """Kernel-driven pointer doubling reaches the union-find fixpoint."""
    from repro.core.uf import compress_np

    v = 300
    rep = np.arange(v, dtype=np.int32)
    for a, b in RNG.integers(0, v, (40, 2)):
        ra, rb = rep[a], rep[b]
        if ra != rb:
            rep[max(ra, rb)] = min(ra, rb)
    cur = jnp.asarray(rep)
    for _ in range(12):
        cur = ops.pointer_jump(cur, cur)
    np.testing.assert_array_equal(np.asarray(cur), compress_np(rep))


# ---------------------------------------------------------------------------
# flash attention (fwd) vs the naive oracle
# ---------------------------------------------------------------------------

def _attn_inputs(b, s, t, h, kv, d, dtype):
    q = jnp.asarray(RNG.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, t, kv, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, t, kv, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("s,t,h,kv,d", [
    (64, 64, 4, 2, 32),     # GQA g=2
    (48, 48, 3, 3, 16),     # MHA, non-pow2 seq (padding path)
    (128, 128, 8, 2, 64),   # GQA g=4
    (17, 33, 2, 1, 8),      # MQA, ragged blocks
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(s, t, h, kv, d, causal):
    from repro.models.layers import naive_attention

    q, k, v = _attn_inputs(2, s, t, h, kv, d, jnp.float32)
    ref_out = naive_attention(q, k, v, causal=causal)
    out = ops.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, atol):
    from repro.models.layers import naive_attention

    q, k, v = _attn_inputs(1, 32, 32, 4, 2, 32, dtype)
    ref_out = naive_attention(q, k, v, causal=True)
    out = ops.flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32), atol=atol
    )


def test_flash_attention_decode_offset():
    """Single-token decode against a prefix cache: q_offset masks the tail."""
    from repro.models.layers import naive_attention

    t, pos = 64, 37
    q, k, v = _attn_inputs(2, 1, t, 4, 2, 32, jnp.float32)
    # oracle: only cache entries < pos+1 are attendable
    ref_out = naive_attention(q, k[:, : pos + 1], v[:, : pos + 1], causal=True,
                              q_offset=pos)
    out = ops.flash_attention(q, k, v, causal=True, q_offset=pos,
                              block_q=8, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5)


def test_flash_matches_chunked_xla_path():
    """The Pallas kernel and the XLA chunked path are interchangeable."""
    from repro.models.layers import gqa_attention

    q, k, v = _attn_inputs(2, 64, 64, 4, 2, 32, jnp.float32)
    a = gqa_attention(q, k, v, causal=True, chunk=16)
    b = ops.flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


@pytest.mark.parametrize("na,nb", [(16, 4), (100, 100), (1000, 37), (257, 0)])
@pytest.mark.parametrize("dup", [False, True])
def test_merge_sorted_sweep(na, nb, dup):
    """Rank-merge of two sorted key/value columns == numpy mergesort, with
    sentinel padding and (cross-column) duplicate keys."""
    from repro.compat import enable_x64

    from repro.kernels.merge import merge_ranks, merge_sorted

    KEY_MAX = np.int64((1 << 63) - 1)
    hi = 1 << 10 if dup else 1 << 60  # force duplicates in the small space
    with enable_x64():
        a = np.sort(RNG.integers(0, hi, na).astype(np.int64))
        b = np.sort(RNG.integers(0, hi, nb).astype(np.int64))
        a[na // 2 :] = KEY_MAX  # sentinel-padded tails, like the arena index
        av = np.arange(na, dtype=np.int32)
        bv = np.arange(nb, dtype=np.int32) + 10_000
        mk, mv = merge_sorted(
            jnp.asarray(a), jnp.asarray(av), jnp.asarray(b), jnp.asarray(bv),
            out_len=na + nb,
        )
        mk, mv = np.asarray(mk), np.asarray(mv)
        assert (np.diff(mk) >= 0).all()
        np.testing.assert_array_equal(np.sort(np.concatenate([a, b])), mk)
        # every (key, val) pair survives the merge exactly once
        want = sorted(zip(a.tolist() + b.tolist(), av.tolist() + bv.tolist()))
        got = sorted(zip(mk.tolist(), mv.tolist()))
        assert want == got
        # truncation keeps a prefix of the merged order
        tk, _ = merge_sorted(
            jnp.asarray(a), jnp.asarray(av), jnp.asarray(b), jnp.asarray(bv),
            out_len=na,
        )
        np.testing.assert_array_equal(np.asarray(tk), mk[:na])
        # merge_ranks positions are a collision-free permutation
        pa, pb = merge_ranks(jnp.asarray(a), jnp.asarray(b))
        pos = np.concatenate([np.asarray(pa), np.asarray(pb)])
        assert np.array_equal(np.sort(pos), np.arange(na + nb))

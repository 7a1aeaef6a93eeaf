"""Planted-violation fixtures: toy fns each pass must catch.

Negative coverage for the audit — every fixture reproduces, in miniature,
the exact bug class its pass exists to block, at the same probe geometry
(``ARENA`` rows vs ``CAP``-width streams) the real audit traces at.  The
CLI's ``--fixture NAME`` mode and ``tests/test_analysis.py`` both trace
these and assert the expected pass fires with a useful location; a pass
that stops seeing its fixture is broken, whatever the inventory says.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ARENA = 4096   # the "arena" length of the toy fns
CAP = 256      # delta-stream width, strictly smaller


def arena_sort(keys, q):
    """Plants a NoArenaSort violation: re-sorts the full arena per probe.

    The pre-PR-4 membership idiom — ``argsort`` over all ``ARENA`` keys on
    every call instead of maintaining the persistent sorted index.
    """
    perm = jnp.argsort(keys)                       # <- arena-length sort
    srt = keys[perm]
    pos = jnp.searchsorted(srt, q, method="scan_unrolled")
    return srt[jnp.clip(pos, 0, ARENA - 1)] == q


def arena_scatter(dst, vals):
    """Plants a NoArenaScatter violation: arena-length updates stream.

    Rewrites every arena row per call — the write traffic the stable
    partition/rank-merge maintenance exists to avoid.
    """
    idx = jnp.arange(ARENA, dtype=jnp.int32)
    return dst.at[idx].max(vals)                   # <- arena-length scatter


def int32_key(s, p, o):
    """Plants a DtypeSafety violation: packed key truncated to int32.

    Packs 3 x 21-bit IDs into an int64 (the engine's ``_pack3`` idiom)
    then casts the product down — bit-identical on small test IDs,
    corrupt beyond 2^31.
    """
    key = (
        s.astype(jnp.int64) << jnp.int64(42)
    ) | (p.astype(jnp.int64) << jnp.int64(21)) | o.astype(jnp.int64)
    return key.astype(jnp.int32)                   # <- silent truncation


def host_callback(x):
    """Plants a NoHostCallback violation: a debug print left in a hot fn."""
    jax.debug.callback(lambda v: None, x[0])       # <- host round trip
    return x * 2


def nested_cond_sort(keys, q, flag):
    """Plants an arena sort inside a ``cond`` branch.

    Exercises the traversal depth the historical helper missed: the
    violation is only reachable through the branch tuple of a ``cond``
    eqn's params, so a walker that skips tuple-of-ClosedJaxpr params
    reports this fixture clean.
    """

    def probe(args):
        k, qq = args
        perm = jnp.argsort(k)                      # <- sort inside branch
        return k[perm][jnp.clip(qq, 0, ARENA - 1)]

    def skip(args):
        return jnp.int64(0)

    return jax.lax.cond(flag, probe, skip, (keys, q))


def _trace(fn, *avals):
    return jax.make_jaxpr(fn)(*avals)


def trace_fixture(name: str):
    """Trace a fixture by name; returns ``(label, jaxpr, arena_rows)``."""
    from repro.compat import enable_x64

    i64 = jnp.int64
    i32 = jnp.int32
    with enable_x64():
        if name == "arena_sort":
            jx = _trace(arena_sort, jnp.zeros(ARENA, i64), jnp.zeros(CAP, i64))
        elif name == "arena_scatter":
            jx = _trace(
                arena_scatter, jnp.zeros(ARENA, i32), jnp.zeros(ARENA, i32)
            )
        elif name == "int32_key":
            jx = _trace(
                int32_key, jnp.zeros(CAP, i32), jnp.zeros(CAP, i32),
                jnp.zeros(CAP, i32),
            )
        elif name == "host_callback":
            jx = _trace(host_callback, jnp.zeros(CAP, i32))
        elif name == "nested_cond_sort":
            jx = _trace(
                nested_cond_sort, jnp.zeros(ARENA, i64), jnp.zeros((), i64),
                jnp.zeros((), jnp.bool_),
            )
        else:
            raise ValueError(f"unknown fixture {name!r} (have {FIXTURES})")
    return f"fixture:{name}", jx, ARENA


FIXTURES = (
    "arena_sort", "arena_scatter", "int32_key", "host_callback",
    "nested_cond_sort",
)

# the pass each fixture must trip — the CLI asserts the report names it
EXPECTED_PASS = {
    "arena_sort": "NoArenaSort",
    "arena_scatter": "NoArenaScatter",
    "int32_key": "DtypeSafety",
    "host_callback": "NoHostCallback",
    "nested_cond_sort": "NoArenaSort",
}

"""Fixtures of the benchmark's CPU tests."""

from __future__ import annotations

import pytest


@pytest.fixture(autouse=True)
def _jax_cache_restored(tmp_path_factory, monkeypatch):
    """A run turns on the persistent compile cache for its process; keep it
    in one temporary directory for these tests and switch it off after
    each, so no other test of the same process sees it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    path = tmp_path_factory.getbasetemp() / "perfbench_jax_cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()

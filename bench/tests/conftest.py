"""Fixtures the benchmark's tests share."""
import pytest


@pytest.fixture
def restore_jax_cache(monkeypatch, tmp_path):
    """The harness points JAX's process-wide persistent cache at its
    directory; put the process back as it was afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    prev = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    yield
    for k, v in prev.items():
        jax.config.update(k, v)
    cc.reset_cache()

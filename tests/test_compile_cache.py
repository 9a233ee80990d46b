"""Placement of JAX's persistent compilation cache (``repro.compile_cache``)."""

import jax
import pytest

from repro.compile_cache import REPO_CACHE_DIR, setup_compile_cache

_KEYS = (
    "jax_compilation_cache_dir",
    "jax_persistent_cache_min_compile_time_secs",
    "jax_persistent_cache_min_entry_size_bytes",
)


@pytest.fixture
def restore_config():
    """Put the cache settings back, so later tests in this worker compile
    with the cache as it was (no compile runs while they are changed)."""
    from jax.experimental.compilation_cache import compilation_cache

    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_cache_dir_from_env_or_checkout(monkeypatch, restore_config, tmp_path,
                                        env_dir):
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env_dir))
    got = setup_compile_cache()
    # With the variable set, JAX owns the path: nothing is set in code.
    assert got == (str(REPO_CACHE_DIR) if env_dir is None else before)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_repo_cache_dir_is_fixed_and_ignored():
    root = REPO_CACHE_DIR.parent
    assert (root / "src" / "repro" / "compile_cache.py").exists()
    ignored = (root / ".gitignore").read_text().split()
    assert REPO_CACHE_DIR.name + "/" in ignored

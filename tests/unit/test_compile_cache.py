"""The one compile-cache placement rule (``utils.configure_compile_cache``)."""

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from unionml_tpu.utils import configure_compile_cache

REPO_ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def restore_cache_config():
    """Leave the suite's own cache placement as conftest made it."""
    before = jax.config.jax_compilation_cache_dir
    yield
    if jax.config.jax_compilation_cache_dir != before:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_env_var_places_the_cache_and_code_sets_no_directory(monkeypatch, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)  # as a fresh process without code would have it
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert configure_compile_cache() == str(tmp_path)
    # JAX reads the variable itself at import; the function must not write a
    # directory over it — here there is none to read back, so None stays None
    assert jax.config.jax_compilation_cache_dir is None


def test_default_is_the_checkout_and_stable_across_calls(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = configure_compile_cache()
    assert first == str(REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert configure_compile_cache() == first  # no temp name, pid or timestamp in it
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

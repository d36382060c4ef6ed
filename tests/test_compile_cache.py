"""The persistent compilation cache lands in exactly one directory:
``JAX_COMPILATION_CACHE_DIR`` where it is set, else ``<checkout>/.jax_cache``.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_dir(from_env, tmp_path, monkeypatch, restore_cache_config):
    if from_env:
        want = str(tmp_path / "env_cache")
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = os.path.join(compile_cache.CHECKOUT_ROOT, ".jax_cache")
        # the checkout root: where chip_smoke.py and pyproject.toml live
        assert (compile_cache.CHECKOUT_ROOT / "pyproject.toml").is_file()
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_compiles_are_written_to_env_dir(tmp_path, monkeypatch,
                                         restore_cache_config):
    want = tmp_path / "env_cache"
    monkeypatch.setenv(compile_cache.ENV_VAR, str(want))
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    fn = jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.0)
    fn(jnp.arange(7.0)).block_until_ready()
    assert any(want.iterdir())

"""Where the persistent compilation cache goes: the environment's
directory when it names one, else a fixed directory in the checkout."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env", [None, "/srv/jax-cache"])
def test_compile_cache_dir(monkeypatch, cache_config, env):
    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    got = compile_cache.enable_compile_cache()
    if env is None:
        assert got == str(compile_cache.REPO_CACHE)
        assert compile_cache.REPO_CACHE.parent == \
            compile_cache.Path(__file__).resolve().parents[1]
        assert jax.config.jax_compilation_cache_dir == got
    else:
        # JAX reads the variable itself; the helper sets nothing
        assert got == env
        assert jax.config.jax_compilation_cache_dir == before

"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.runtime import compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(compile_cache.DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == got
    assert compile_cache.DEFAULT_DIR.parent.joinpath("chip_smoke.py").is_file()
    assert compile_cache.enable_compile_cache() == got


def test_import_sets_no_cache_dir():
    import subprocess
    import sys
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, repro, repro.serving, repro.runtime.compile_cache; "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, check=True,
        env={"PATH": "", "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": str(compile_cache.DEFAULT_DIR.parent / "src")})
    assert out.stdout.strip() == "None"

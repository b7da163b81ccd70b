"""Launcher plumbing: the persistent compilation cache helper."""

import jax

from repro.launch import compile_cache


def _enabled_dir(monkeypatch, env):
    """Run the helper with ``JAX_COMPILATION_CACHE_DIR`` = ``env`` (None:
    unset) and return (returned dir, jax's configured dir); the config
    is restored afterwards, so no test compile ever writes a cache."""
    prev = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
        jax.config.update("jax_compilation_cache_dir", env)
    try:
        got = compile_cache.enable_compile_cache()
        return got, jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    got, configured = _enabled_dir(monkeypatch, None)
    assert got == configured == str(compile_cache.CHECKOUT / ".jax_cache")
    # a fixed path: the cache key includes it, so it must not move
    assert _enabled_dir(monkeypatch, None)[0] == got


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path):
    env = str(tmp_path / "cc")
    got, configured = _enabled_dir(monkeypatch, env)
    assert got == configured == env


def test_compile_cache_dir_is_gitignored():
    ignore = (compile_cache.CHECKOUT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignore

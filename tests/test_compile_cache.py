"""Where ``enable_compile_cache`` puts JAX's persistent compilation cache:
the directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (and no
other), else one fixed, git-ignored directory of the checkout; and every
compile is cached, however short."""
from pathlib import Path

import jax
import pytest

from repro.common import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_enable_xla_caches")
    saved = [getattr(jax.config, n) for n in names]
    yield
    for n, v in zip(names, saved):
        jax.config.update(n, v)


def test_env_var_directory_is_used_as_is(monkeypatch, restore_config,
                                         tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX read the variable itself at import; the helper sets no other
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_default_directory_is_fixed_and_ignored(monkeypatch, restore_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_a_moved_cache_is_still_hit(monkeypatch, restore_config, tmp_path):
    """Entries written under one directory are found after the directory
    is moved: no cache key depends on where the cache lives."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    hits = []

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits.append(event)

    def compile_in(path):
        monkeypatch.setenv(compile_cache.ENV_VAR, str(path))
        jax.config.update("jax_compilation_cache_dir", str(path))
        compile_cache.enable_compile_cache()
        cc.reset_cache()
        jax.clear_caches()
        x = jax.ShapeDtypeStruct((5,), jax.numpy.int32)
        jax.jit(lambda x: x * 7 + 3).lower(x).compile()
        n = len(hits)
        cc.reset_cache()
        return n

    jax.monitoring.register_event_listener(on_event)
    try:
        assert compile_in(tmp_path / "a") == 0
        (tmp_path / "a").rename(tmp_path / "b")
        assert compile_in(tmp_path / "b") == 1
    finally:
        jax.monitoring.unregister_event_listener(on_event)

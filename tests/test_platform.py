"""utils/platform: where the persistent compile cache goes, and the watchdog
arm-condition. Device selection itself is JAX's — nothing here to test."""

import os

import jax
import pytest

from ddim_cold_tpu.utils import platform as plat


@pytest.fixture
def cache_config():
    """Hand the test a clean slate for the cache dir and put back what the
    suite configured (tests/conftest.py) afterwards."""
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


class _BareEngine:
    """The surface ``serve.warmup`` touches when it is given no configs."""
    buckets = (2,)
    stats = {"compiles": 0}
    _programs: dict = {}


def _via_enable(path):
    return plat.enable_compile_cache(path)


def _via_warmup(path):
    from ddim_cold_tpu.serve.warmup import warmup

    return warmup(_BareEngine(), [], cache_dir=path)["cache_dir"]


def _via_build_replica(path):
    from ddim_cold_tpu.serve.replica_main import build_replica

    build_replica("r0", {"backend": "stub", "cache_dir": path})
    return plat.enable_compile_cache()


@pytest.mark.parametrize("place", [_via_enable, _via_warmup,
                                   _via_build_replica])
def test_cache_dir_from_outside_wins(place, monkeypatch, tmp_path,
                                     cache_config):
    """JAX_COMPILATION_CACHE_DIR set ⇒ no caller ever writes
    ``jax_compilation_cache_dir``: the config stays what JAX read from the
    environment, and the directory named in code is ignored."""
    outside, inside = str(tmp_path / "outside"), str(tmp_path / "inside")
    monkeypatch.setenv(plat.CACHE_ENV, outside)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, value: (updates.append(name), real_update(name, value)))
    before = jax.config.jax_compilation_cache_dir
    assert place(inside) == outside
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(inside)


@pytest.mark.parametrize("place", [_via_enable, _via_warmup,
                                   _via_build_replica])
def test_cache_dir_named_in_code_is_used_when_env_unset(place, monkeypatch,
                                                        tmp_path, cache_config):
    monkeypatch.delenv(plat.CACHE_ENV, raising=False)
    inside = str(tmp_path / "inside")
    assert place(inside) == inside
    assert jax.config.jax_compilation_cache_dir == inside
    assert os.path.isdir(inside)
    # a later caller that names no directory keeps it (one cache per process)
    assert plat.enable_compile_cache() == inside


def test_cache_dir_default_is_fixed(monkeypatch, cache_config):
    """Env unset and nothing configured ⇒ ``<checkout>/.jax_cache``, the same
    path on every call (the directory is part of the cache key)."""
    monkeypatch.delenv(plat.CACHE_ENV, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    first = plat.enable_compile_cache()
    assert first == os.path.join(repo, ".jax_cache") == plat.default_cache_dir()
    assert plat.enable_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first


def test_cache_dir_that_cannot_be_made_raises(monkeypatch, tmp_path,
                                              cache_config):
    """A cache that cannot be set up is a fault to see, not to swallow."""
    monkeypatch.delenv(plat.CACHE_ENV, raising=False)
    blocker = tmp_path / "a_file"
    blocker.write_text("")
    with pytest.raises(OSError):
        plat.enable_compile_cache(str(blocker / "cache"))


@pytest.mark.parametrize("env,platforms,want", [
    ("7.5", "cpu", 7.5),     # an explicit value always wins …
    ("0", "tpu", 0.0),       # … including 0 = disarmed
    (None, "cpu", 0.0),      # configured cpu: never armed
    (None, "cpu,tpu", 0.0),  # first entry decides
    (None, "tpu", 600.0),    # an accelerator: the caller's default
    ("", "tpu", 600.0),      # empty string counts as unset
])
def test_watchdog_stall_s(env, platforms, want, monkeypatch):
    if env is None:
        monkeypatch.delenv("DDIM_COLD_TEST_STALL_S", raising=False)
    else:
        monkeypatch.setenv("DDIM_COLD_TEST_STALL_S", env)
    monkeypatch.setattr(plat, "effective_platforms", lambda: platforms)
    assert plat.watchdog_stall_s("DDIM_COLD_TEST_STALL_S", 600.0) == want

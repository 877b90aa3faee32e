"""utils/hostcache.enable_compile_cache: the compile cache can be placed
from outside, and is otherwise one fixed directory of the checkout."""
import os

import jax

from mmlspark_tpu.utils import hostcache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


def _config():
    return {k: getattr(jax.config, k) for k in _KEYS}


def test_env_placed_cache_changes_no_jax_config(monkeypatch, tmp_path):
    monkeypatch.setenv(hostcache.CACHE_DIR_ENV, str(tmp_path))
    before = _config()
    assert hostcache.enable_compile_cache() is None
    assert _config() == before


def test_default_cache_is_one_fixed_directory_of_the_checkout(monkeypatch):
    monkeypatch.delenv(hostcache.CACHE_DIR_ENV, raising=False)
    before = _config()
    try:
        first = hostcache.enable_compile_cache()
        assert hostcache.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
        root, leaf = os.path.split(first)
        assert os.path.realpath(root) == os.path.join(_REPO, ".jax_cache")
        assert leaf.startswith("host-") and str(os.getpid()) not in leaf
    finally:
        # the session's own cache directory (conftest) must survive
        for k, v in before.items():
            jax.config.update(k, v)

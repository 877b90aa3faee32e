"""Where this checkout keeps JAX's persistent compilation cache.

One helper, `enable_compile_cache()`, used by tests/conftest.py,
chip_smoke.py and the child script of tests/test_supervisor.py:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads the variable itself and this
  module touches no JAX config at all — the cache can be placed from
  outside (the chip tool's output directory, a CI volume).
- unset: `<checkout>/.jax_cache/host-<fingerprint>`, fixed per host and
  checkout — never a temp name, a pid or a time.

Two facts the layout rests on. (1) Persistent-cache entries embed the
compiling host's vector ISA; loading an entry compiled for a different
host aborts or deadlocks XLA:CPU, so the directory is namespaced by a host
fingerprint. (2) JAX hashes the cache directory's path STRING into every
cache key (through the autotune-cache debug option it derives from it), so
the checkout root below is deliberately NOT normalized: conftest loads this
file by the path `tests/../mmlspark_tpu/utils/hostcache.py`, which keeps
the test session's directory spelled `<checkout>/tests/../.jax_cache/...`
as it always was — respelling it would turn every entry of a warm cache
into a miss.

Stdlib-only imports at module level: conftest must be able to load this
file BEFORE the jax backend initializes (it does so by path, skipping the
package __init__, which pulls the full framework)."""
import hashlib
import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))


def host_cache_dir(root: str) -> str:
    """`root`/host-<sha1 of jaxlib version + cpuinfo flags>."""
    try:
        import jaxlib
        tag = jaxlib.__version__
    except Exception:  # noqa: BLE001 - fingerprint degrades, never fails
        tag = "nojaxlib"
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith("flags"):
                    tag += line
                    break
    except OSError:
        pass
    fp = hashlib.sha1(tag.encode()).hexdigest()[:12]
    return os.path.join(root, f"host-{fp}")


def enable_compile_cache():
    """Point JAX's persistent compilation cache at this checkout's fixed
    directory and return that path — or, when `JAX_COMPILATION_CACHE_DIR`
    is set, change nothing and return None (JAX honors the variable)."""
    if os.environ.get(CACHE_DIR_ENV):
        return None
    import jax
    path = host_cache_dir(os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return path

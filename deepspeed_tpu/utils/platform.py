"""Where this process keeps JAX's persistent compilation cache.

One helper, :func:`enable_compile_cache`, used by the training engine,
the inference engine, ``chip_smoke.py`` and ``benchmarks/run.py``, so
that every entry point of a checkout shares one cache directory:

- ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX reads it
  itself; nothing is set in code and the helper only reports it.
- unset: the cache lives at :data:`DEFAULT_COMPILE_CACHE_DIR`, one fixed
  path inside the checkout (git-ignored). The path is part of the cache
  key, so it is derived from the package's own location — never from
  ``~``, a temp name, a pid or the time.

Device choice needs no helper: ``JAX_PLATFORMS=cpu`` (plus
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for a virtual
mesh) in the environment is honoured by JAX itself.
"""

import os

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache(cache_dir=None, min_compile_secs=1.0) -> str:
    """Make JAX's persistent compilation cache active for this process
    and return the directory it lives in, so re-runs (a second smoke,
    benchmark runs, resumed jobs, repeated CLI launches) load compiled
    executables from disk instead of compiling again.

    Precedence: the ``JAX_COMPILATION_CACHE_DIR`` environment variable
    (then this is a no-op that reports it), else the directory already
    active in this process (jax's cache dir is global: the first caller
    wins), else ``cache_dir`` (a user's ``compile_cache.dir``), else
    :data:`DEFAULT_COMPILE_CACHE_DIR`.
    """
    env_dir = os.environ.get(COMPILE_CACHE_ENV)
    if env_dir:
        return env_dir
    import jax
    active = jax.config.jax_compilation_cache_dir
    if active:
        return active
    cache_dir = os.path.expanduser(cache_dir) if cache_dir \
        else DEFAULT_COMPILE_CACHE_DIR
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir

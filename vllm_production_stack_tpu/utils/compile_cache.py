"""Where JAX's persistent compilation cache lives — the one place that says.

A serving engine's program set is minutes of XLA compiles; the persistent
cache turns every later boot into reloads. The cache's path is part of its
key, so a directory that moves never hits: the location is either the
caller's (`JAX_COMPILATION_CACHE_DIR`, which JAX reads itself, or the
engine's `--compilation-cache-dir`) or ONE fixed directory inside the
checkout. Nothing here is derived from a temporary name, a pid or the time.

`compile_cache_dir` is importable without JAX, so a parent process that must
stay off the chip (chip_smoke.py) can look at the
directory its children will use.
"""

from __future__ import annotations

import os

from .logging import init_logger

logger = init_logger(__name__)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def compile_cache_dir(flag_dir: str | None = None) -> str | None:
    """The directory in effect: the environment's, else the flag's, else the
    fixed in-checkout one. An empty flag means "no cache" (None)."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    if flag_dir == "":
        return None
    return flag_dir or DEFAULT_DIR


def configure_compile_cache(flag_dir: str | None = None) -> str | None:
    """Point THIS process's JAX at the persistent cache; returns the
    directory in effect, None when the cache is off. Call once, before the
    first compile."""
    import jax

    if jax.default_backend() == "cpu":
        # this image's XLA:CPU executable reload is broken (intermittent
        # segfaults on cache READS — tests/conftest.py); the CPU backend
        # only runs tests and rehearsals, which compile cold
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    # the serving program set is all multi-second compiles; cache
    # everything that costs more than a second
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        if flag_dir:
            logger.warning(
                "%s=%s is set: ignoring --compilation-cache-dir %s",
                ENV_VAR, env_dir, flag_dir,
            )
        return env_dir  # JAX reads it; set nothing in code
    cache_dir = compile_cache_dir(flag_dir)
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir

"""One placement rule for JAX's persistent compilation cache.

Entry-point scripts (``chip_smoke.py``, ``perfbench/run.py``) call
:func:`configure_compile_cache` before their first use of JAX. The
cache directory is part of the cache key, so it must not move between
runs: it is ``JAX_COMPILATION_CACHE_DIR`` when the environment sets one
(JAX reads that variable itself; nothing is set in code), and otherwise
the fixed ``<checkout>/.jax_cache`` — never a tempfile, pid or
time-derived path.
"""

import os

import jax

_CACHE_DIR_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE_DIRNAME = ".jax_cache"


def configure_compile_cache(checkout: str) -> str:
    """Place the persistent compile cache; returns the directory in use.

    JAX by default persists only programs that took >= 1 s to compile.
    The take path compiles hundreds of programs far below that (one
    eager ``slice_in_dim`` per transfer chunk, one copy and one
    fingerprint program per leaf shape), so the threshold drops to 0
    and a warm process recompiles none of them.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from_env = os.environ.get(_CACHE_DIR_ENV_VAR)
    if from_env:
        return from_env
    cache_dir = os.path.join(os.path.abspath(checkout), _CHECKOUT_CACHE_DIRNAME)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir

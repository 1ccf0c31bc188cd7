"""Where jax's persistent compilation cache lives — decided once, at
package import (`distributed_groth16_tpu/__init__.py`), nowhere else.

  * `DG16_NO_JAX_CACHE=1`           -> cache off (the CPU test suite).
  * `JAX_COMPILATION_CACHE_DIR` set -> jax reads the variable itself; this
    module sets NO directory, so whoever launches the process (the chip
    tool, a deployment) decides where compiled programs are kept.
  * otherwise                       -> `<checkout>/.jax_cache`, a fixed
    path. The directory is part of what a later process has to find again,
    so nothing that varies between two runs of the same checkout (machine
    fingerprint, flags, pid, time) may appear in it.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def disable_compile_cache(jax) -> None:
    """Hard-disable jax's persistent compilation cache for this process.

    The XLA:CPU AOT loader in this jax build can segfault *reading* a cache
    entry (inside compilation_cache.get_executable_and_time) — observed
    deterministically late in a long single-process test run, and Python
    cannot catch it. The test suite runs with `DG16_NO_JAX_CACHE=1` for
    that reason; the cache read path is then never entered.
    """
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_compilation_cache_dir", None)


def setup_compile_cache(jax) -> str:
    """Apply the placement rule in the module docstring; returns the
    directory in use ("" when the cache is off)."""
    from . import config as _config

    if _config.env_flag("DG16_NO_JAX_CACHE"):
        disable_compile_cache(jax)
        return ""
    # caching floor: tiny executables recompile in under a second anyway,
    # so keeping them out of the cache costs nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if external:
        return external
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR

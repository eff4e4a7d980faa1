"""The one rule for where this repo keeps JAX's persistent compilation cache.

A fresh JAX process re-pays every XLA compile; the persistent on-disk
cache (`jax_compilation_cache_dir`) survives processes. Its directory is
part of the cache key, so a directory that moves from run to run never
hits. Hence ONE placement rule, shared by both drivers, `chip_smoke.py`,
`benches/*` and `tests/conftest.py`:

- ``JAX_COMPILATION_CACHE_DIR`` set: that directory and no other — whoever
  runs the program places the cache, and no config field overrides it.
- unset: a caller-given path if there is one, else the fixed
  ``<checkout>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed path inside the checkout: photon_tpu/utils/ -> repo root
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def resolve_cache_dir(param: Optional[str] = None,
                      output_dir: Optional[str] = None) -> Optional[str]:
    """Where the cache lives for a caller's knob value: ``""`` disables
    (None); otherwise the environment variable when set, else ``param``
    (relative paths land under ``output_dir``), else `DEFAULT_CACHE_DIR`."""
    if param == "":
        return None
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    if param is not None:
        return (param if os.path.isabs(param) or output_dir is None
                else os.path.join(output_dir, param))
    return DEFAULT_CACHE_DIR


def enable_compilation_cache(param: Optional[str] = None,
                             output_dir: Optional[str] = None,
                             min_compile_secs: float = 0.0) -> Optional[str]:
    """Turn the persistent cache on at `resolve_cache_dir`'s verdict and
    return the directory (None: ``param == ""``, cache left off).

    ``min_compile_secs`` is jax's store threshold: 0 caches every
    program — a driver run is many small programs whose compiles add up —
    while the test suite passes a higher gate to keep tiny jits out."""
    path = resolve_cache_dir(param, output_dir)
    if path is None:
        return None
    import jax

    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    return path

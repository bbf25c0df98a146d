"""Where a checkout keeps what it caches between runs.

One resolver, so a directory that moves can never make a cache miss
(JAX keys its persistent compilation cache on the path):

- the XLA compilation cache lives where ``JAX_COMPILATION_CACHE_DIR``
  says — JAX reads that variable itself, so when it is set no code here
  or anywhere else in the package touches ``jax_compilation_cache_dir``
  — and otherwise at ``<checkout>/.scratch/xla_cache`` (git-ignored),
  never at a name made from a temporary directory, a pid or the time;
- the per-machine measurement caches (calibration, per-op costs, the
  search's cost cache) live with it, under ``flexflow_tpu/``, unless
  ``FLEXFLOW_TPU_CACHE`` names another root;
- profiler traces default to ``<checkout>/.scratch/trace``.

Entry points (``chip_smoke.py``, ``bench.py``, ``python -m
flexflow_tpu``, the ``tools/*_bench.py`` mains) call
``arm_compile_cache()`` once, before the first compile. Nothing here
runs at import.
"""

from __future__ import annotations

import os
from typing import Tuple

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def scratch_dir(*parts: str) -> str:
    """``<checkout>/.scratch/<parts...>`` (not created)."""
    return os.path.join(_CHECKOUT, ".scratch", *parts)


def compile_cache_dir() -> str:
    """The directory JAX's persistent compilation cache uses."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or scratch_dir("xla_cache"))


def arm_compile_cache() -> Tuple[str, bool]:
    """Point JAX's persistent compilation cache at
    ``compile_cache_dir()`` and return ``(dir, was_empty)``. With
    ``JAX_COMPILATION_CACHE_DIR`` set JAX has already read it and this
    only reports. Either way the cache's key covers the operations'
    metadata."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX strips an operation's metadata (named scopes, source lines)
    # from the cache key by default, so a cache filled before a scope
    # existed would hand back an executable whose profiler trace
    # cannot be attributed (benchmark/lib/program_trace.py reads the
    # scopes). With the metadata in the key such an entry is a miss.
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    try:
        with os.scandir(path) as it:
            # files only: the measurement caches' subdirectory is not
            # a compiled program
            empty = not any(e.is_file() for e in it)
    except FileNotFoundError:
        empty = True
    return path, empty


def measurement_cache_dir() -> str:
    """Root of the per-machine measurement caches."""
    return (os.environ.get("FLEXFLOW_TPU_CACHE")
            or os.path.join(compile_cache_dir(), "flexflow_tpu"))

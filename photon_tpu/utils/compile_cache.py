"""One persistent XLA compile cache, placeable from outside.

Every process that compiles (each driver ``main``, ``bench.py``,
``chip_smoke.py`` and its children) calls :func:`configure_compile_cache`
before its first compile. The directory is part of what makes a cache
useful across processes and across runs: it is either the one the
environment names (``JAX_COMPILATION_CACHE_DIR``, which JAX reads by itself —
this module then sets no directory) or ONE fixed path beside the package,
never a temporary directory, a pid or a timestamp.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the shared directory and
    return the directory in use. Touches configuration only — no backend is
    initialized, so it is safe before ``fork`` and before device selection.

    The solve cache compiles one small executable per shape bucket; JAX's
    default of persisting only compiles slower than a second would leave
    most of them out, so the threshold is lowered to zero unless the
    environment sets its own.
    """
    import jax

    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def cache_entry_count(directory: str) -> int:
    """Number of compiled executables persisted under ``directory`` (0 when
    it does not exist yet)."""
    try:
        return sum(1 for name in os.listdir(directory) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0

"""Process set-up shared by everything that runs on the chip.

Both helpers are called by an entry point's ``main`` before its first
compile, never at import: importing this module touches no JAX state.
"""

from __future__ import annotations

import os

#: the persistent compile cache's directory when the environment names
#: none.  A fixed path: the directory is part of each entry's key, so a
#: cache that moves between runs never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    left alone; otherwise the cache goes to ``<repo>/.jax_cache``."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def device_line() -> str:
    """The devices JAX will run on, as every chip run prints them."""
    import jax
    devs = jax.devices()
    return (f"[device] platform {devs[0].platform}, kind "
            f"{devs[0].device_kind}, count {len(devs)}, jax {jax.__version__}")

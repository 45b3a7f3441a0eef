"""Process start-up for every entry point that touches JAX: where the
persistent compile cache lives, and bringing the backend up before any
work is accepted.

A process asks for the CPU with ``JAX_PLATFORMS=cpu``; nothing here, or
anywhere else in the package, picks a platform for it or falls back to
another one.  A platform that cannot initialise raises out of
``init()`` and the process exits non-zero.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache: derived from where the package lives, so two
# processes started from the same checkout share it.  JAX keys cache
# entries on the directory, so a path that moved (tempfile, a pid, the
# time) would never hit.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; call before first JAX
    use.  ``JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it
    itself and no other directory is set in code.  Returns the
    directory in effect."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    # the grid/bucket programs compile in well under JAX's 1 s default
    # threshold; a restart should not pay for them again either
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return placed or CHECKOUT_CACHE_DIR


def init() -> dict:
    """Place the compile cache, start counting compiles, and initialise
    the backend by running one operation on it.  Returns what
    chip_smoke.py and the server's start-up line report; raises when
    the requested platform cannot initialise."""
    cache_dir = configure_compile_cache()
    from opengemini_tpu.utils import devobs

    devobs.watch_compiles()
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    jnp.ones((8,), jnp.float32).sum().block_until_ready()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "count": len(devices),
        "compile_cache_dir": cache_dir,
    }

"""Where JAX keeps its persistent compilation cache for ``chip_smoke.py`` and
``bench.py``.

The cache key includes the directory, so a path that moves between runs (a
temp dir, a pid, a timestamp) never hits. There is exactly one place:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads it
itself), else ``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from typing import Optional

#: the fixed in-checkout cache directory used when the environment names none
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    '.jax_cache')


def configure_compile_cache(platform: str) -> Optional[str]:
    """Turn on the persistent compilation cache for a process whose backend is
    ``platform``; returns the directory in use, or None when it stays off.

    Call before the first compile. With ``JAX_COMPILATION_CACHE_DIR`` set this
    sets nothing. On the CPU the cache stays off: cached XLA:CPU executables
    encode the host's CPU features and can die with SIGILL on a host whose
    features differ, and CPU compiles are cheap."""
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    if platform == 'cpu':
        return None
    import jax
    jax.config.update('jax_compilation_cache_dir', REPO_CACHE_DIR)
    return REPO_CACHE_DIR

"""Where JAX's persistent compilation cache lives.

Every entry point that compiles for a chip (``chip_smoke.py``,
``benchmarks/run.py``, the worker and launcher CLIs) calls
:func:`configure` once before its first compile.  The directory is part
of the cache key's world: a path that moves between runs never hits, so it
is never derived from ``tempfile``, a pid or the clock.

This is JAX's own cache, and the only one.  A process pinned to the CPU
(the test suite and the workers it spawns, rehearsals) is left alone:
serializing the 8-device CPU executables crashed pytest
(``tests/conftest.py``).
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_OPTION = "jax_compilation_cache_dir"

# <checkout>/.jax_cache, from this file's own location
# (<checkout>/theanompi_tpu/utils/jax_cache.py); listed in .gitignore
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> Optional[str]:
    """Point JAX's persistent compilation cache at a fixed directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and this
    sets nothing (returns ``None``); likewise in a process whose platform
    is pinned to ``cpu``.  Otherwise the cache goes to :data:`DEFAULT_DIR`
    and that path is returned.
    """
    if os.environ.get(ENV_VAR):
        return None
    import jax
    if jax.config.jax_platforms == "cpu":
        return None
    jax.config.update(_OPTION, DEFAULT_DIR)
    return DEFAULT_DIR

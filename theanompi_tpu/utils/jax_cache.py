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
# JAX's key leaves an instruction's metadata out by default, so a program
# that differs from a cached one in its names alone (a ``jax.named_scope``
# added, a layer renamed) loads that executable, and with it that compile's
# ``op_name``s: the text the benchmark's scope join reads then names nothing
# the program wrote (PERF.md section 6, PR 39).  The names are part of what
# a cached executable holds here, so they are part of its key.
_NAMES_IN_KEY = "jax_compilation_cache_include_metadata_in_key"

# <checkout>/.jax_cache, from this file's own location
# (<checkout>/theanompi_tpu/utils/jax_cache.py); listed in .gitignore
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> Optional[str]:
    """Point JAX's persistent compilation cache at a fixed directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and this
    sets no directory (returns ``None``); otherwise the cache goes to
    :data:`DEFAULT_DIR` and that path is returned.  Either way an entry's
    key holds the program's names (``_NAMES_IN_KEY``).  A process whose
    platform is pinned to ``cpu`` is left alone.
    """
    import jax
    if jax.config.jax_platforms == "cpu":
        return None
    jax.config.update(_NAMES_IN_KEY, True)
    if os.environ.get(ENV_VAR):
        return None
    jax.config.update(_OPTION, DEFAULT_DIR)
    return DEFAULT_DIR

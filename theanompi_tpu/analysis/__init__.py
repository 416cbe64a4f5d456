"""tpulint — whole-program invariant checkers for the framework's hot
paths.

docs/design.md §6 promises the invariants are machine-checked; §12 lists
the ones a static pass can hold: tracing safety inside fused ``lax.scan``
bodies, ``jax.random`` key discipline, and donation rules — each
closed over the repo-wide call graph
(``analysis/engine.py``) — plus SPMD collective discipline (axis-name
validity, rank-divergent branches, async start/done pairing),
PartitionSpec/shard_map schema checks, ``exchange_body`` collective
symmetry, the ``jax_compat`` shim boundary, the one-attribute-check
telemetry hot-path contract, and the telemetry/recorder schema sync.
Each is a :class:`~.core.Checker` registered here; ``scripts/lint.py``
is the CLI and ``scripts/tier1.sh`` runs it (``--check-baseline``)
before pytest, so a host-side leak into a compiled hot path fails the
gate in seconds (sub-second on a ``.tpulint_cache/`` hit) instead of
surfacing as a silent throughput regression after a 270-second TPU
compile.

The package is stdlib-only (plus numpy transitively via the schema-drift
checker's live probe) and deliberately importable WITHOUT jax:
``scripts/lint.py`` bootstraps it under a synthetic parent package so
the repo-wide walk never drags a backend in.

Suppression: append ``# tpulint: disable=<check>[,<check>...]`` to the
flagged line (or put it on its own line directly above).  Grandfathered
findings live in ``tpulint_baseline.json`` (one justification per entry,
regenerated deterministically by ``scripts/lint.py --update-baseline``).
"""

from . import checkers as _checkers  # noqa: F401  (registers the suite)
from .core import (  # noqa: F401
    CHECKERS,
    Checker,
    Finding,
    SourceFile,
    collect_files,
    compare_baseline,
    load_baseline,
    run_lint,
    save_baseline,
)

__all__ = [
    "CHECKERS", "Checker", "Finding", "SourceFile", "collect_files",
    "compare_baseline", "load_baseline", "run_lint", "save_baseline",
]

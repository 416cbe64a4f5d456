"""The project-specific checker suite — importing this package registers
every checker with :data:`~..core.CHECKERS` (docs/design.md §12).

The dataflow checkers (trace-purity, rng-discipline, donation-safety,
collective-discipline, sharding-schema, exchange-symmetry) run on the
whole-program engine (``analysis/engine.py``); the host-concurrency pass
(shared-state-race, lock-ordering, signal-safety, daemon-discipline)
runs on the engine's thread-role inference; the protocol pass
(wire-contract, retry-safety, state-machine) runs on the declared
endpoint model (``analysis/protocol.py``, docs/design.md §21);
compat-boundary and telemetry-hot-path stay per-file (their invariants
are lexical); schema-drift is the live-object project probe, and
oracle-pair is the disk-scoped project probe pinning every ops/ Pallas
kernel to a registered jnp oracle with an equality test.  The
compile-surface pass (retrace-hazard, dtype-flow) guards the traced
programs: silent-recompile call shapes and low-precision wire numerics
(docs/design.md §26).
"""

from . import (  # noqa: F401
    collective_discipline,
    compat_boundary,
    compile_surface,
    donation_safety,
    exchange_symmetry,
    host_concurrency,
    oracle_pair,
    protocol_conformance,
    rng_discipline,
    schema_drift,
    sharding_schema,
    telemetry_hot_path,
    trace_purity,
)

#: ``--only``/``--disable`` group aliases: ``--only concurrency`` runs
#: just the host-concurrency pass, ``--only protocol`` the distributed-
#: protocol conformance pass (scripts/lint.py expands these before
#: checker-name validation, so the cache keys on the real names).
CHECK_GROUPS = {
    "compile-surface": ("dtype-flow", "retrace-hazard"),
    "concurrency": ("daemon-discipline", "lock-ordering",
                    "shared-state-race", "signal-safety"),
    "protocol": ("wire-contract", "retry-safety", "state-machine"),
}

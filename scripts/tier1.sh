#!/usr/bin/env bash
# The tier-1 verify gate with the driver's pytest flags, so the builder and
# the reviewer run the SAME command (one place to keep the pytest flags, the
# timeout, and the DOTS_PASSED accounting in sync).
#   scripts/tier1.sh
# Exits with pytest's return code; prints DOTS_PASSED=<n> as the last line.
#
# Preceded by the tpulint suite (scripts/lint.py --check-baseline): the
# whole-program invariant checkers of docs/design.md §12 — trace purity
# and rng/donation discipline closed over the repo-wide call graph
# (analysis/engine.py), SPMD collective discipline (axis names,
# rank-divergent branches, start/done pairing), PartitionSpec/shard_map
# schema checks, exchange_body symmetry, the jax_compat shim boundary,
# the telemetry hot-path enabled-guard contract, the recorder/
# telemetry schema sync, the host-concurrency pass (thread-role
# inference; shared-state races, lock-order cycles, signal safety,
# daemon discipline — design.md §16), and the distributed-protocol
# conformance pass (design.md §21: client/server wire op-table diffs,
# DedupWindow claim dominance on every mutating handler path, §15
# retry-verdict/close-taxonomy checks, membership state-machine
# exhaustiveness incl. reactor hooks and the versioned wire-header
# field vocabulary), and the compile-surface pass (design.md §26:
# retrace hazards like fresh-lambda jit identity, jit-in-loop and
# non-static shape params, and bf16-wire dtype-flow discipline incl.
# the per-module NONBITEXACT round-trip registry).  Any finding not covered by
# tpulint_baseline.json — or a stale baseline entry — fails the gate
# here, without importing jax, before pytest.  An unchanged tree is a
# .tpulint_cache/ hit: the gate costs well under a second.
cd "$(dirname "$0")/.."
python scripts/lint.py --check-baseline || { echo "tier1: tpulint gate FAILED (run scripts/lint.py for details)" >&2; exit 9; }
# The simfleet determinism gate (docs/design.md §18): same seed must
# produce a byte-identical event log, a different seed must not, and a
# 512-worker invariant suite (kills, wedges, stragglers, net windows
# through the REAL membership/reactor/dedup logic on a virtual clock)
# must pass inside a CPU-seconds budget.  No subprocesses, no sockets,
# no jax execution — it runs before pytest so a broken survivability
# refactor fails in seconds.
python scripts/simfleet_run.py --gate --budget 120 || { echo "tier1: simfleet gate FAILED (run scripts/simfleet_run.py --gate for details)" >&2; exit 8; }
# The driver's pytest flags (`commands` in /root/TESTS_LAST_RUN.json): six
# xdist workers, one file to one worker, junit counts.  The driver also sets
# ALLOW_MULTIPLE_LIBTPU_LOAD=1 for its own run here; no file of the
# repository does.
set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c); exit $rc

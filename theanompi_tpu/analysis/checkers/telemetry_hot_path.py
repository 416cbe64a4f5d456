"""telemetry-hot-path: recording calls on hot paths guard on ``enabled``.

The invariant (docs/design.md §11 cost contract, machine-checked per
§12): disabled telemetry must cost ONE attribute check per hot-path
site.  ``telemetry.active()`` returns the inert ``DISABLED`` singleton,
and every recording call (``counter``/``gauge``/``observe``/``phase``/
``event``/...) in the four hot-path files — ``parallel/steps.py``,
``models/data/prefetch.py``, ``parallel/exchanger.py``, ``worker.py``
— must sit under an ``if <handle>.enabled:`` (or an ``... if
x.enabled else ...`` expression).  An unguarded call still "works"
(the DISABLED methods are no-ops) which is exactly why review misses
it: the cost is a per-iteration method dispatch + argument
construction that only shows up as throughput noise at pod scale.

Handles are found by dataflow: names assigned from
``telemetry.active()`` / ``telemetry.init(...)`` / ``self.telemetry``,
the dotted ``self.telemetry`` itself, and direct module-level
``telemetry.<record>()`` calls.  The guard test must mention
``.enabled`` (``if tm.enabled``, ``if rec and telem.enabled``); the
accessors (``active``/``init``/``install_signal_hooks``) and plain
``.enabled`` reads are free.

Round 16 extends the pass to the span-emission API
(``utils/tracing.py``, docs/design.md §17): tracer handles come from
``tracing.active()``/``tracing.init(...)``, ``Tracer.begin`` is the
recording gate (a Span minted under a guard only exists on the enabled
path, so ``Span.end``/``note`` need no separate check), and the
module-level ``tracing.emit_wire_span``/``emit_server_span`` one-shot
emitters are recording calls — an unguarded hot-path span is a lint
finding.  The hot set grows the wire/center/island files the span API
rides through.

The always-on span ring (``telemetry.span`` / ``telemetry.count`` and the
readers beside them, :data:`ALWAYS_ON`) is the one sanctioned exception:
its contract is a bounded cost per call (about a microsecond, no lock, no
I/O), not a guard, because the benchmark's metrics and a ``trace_dir``
capture read it whatever the configuration says.  Those module-level
calls need no ``.enabled`` check — and only those: the registry's
``counter``/``gauge``/``observe``/``event`` still do, including when they
stand right next to a span.
"""

from __future__ import annotations

import ast
from typing import List, Set

from ..core import Checker, Finding, ImportResolver, SourceFile, register

HOT_BASENAMES = {"steps.py", "prefetch.py", "exchanger.py", "worker.py",
                 "async_easgd.py", "wire.py", "center_server.py",
                 "fleetmon.py", "numerics.py"}

TELEMETRY_MODULE = "theanompi_tpu.utils.telemetry"
TRACING_MODULE = "theanompi_tpu.utils.tracing"
FLEETMON_MODULE = "theanompi_tpu.utils.fleetmon"
NUMERICS_MODULE = "theanompi_tpu.utils.numerics"

# methods that record (cost when disabled = wasted work); the accessors
# and `.enabled` reads are the sanctioned unguarded surface.  `begin`
# (Tracer) and the emit_* one-shot helpers are the §17 span API;
# `emit_alert` is the §20 fleet-health alert emitter (fleetmon.py joins
# the hot set — its streamer/collector record into the same registry);
# `record` is the §25 numerics report emitter (numerics.py joins too).
RECORDING = {"counter", "gauge", "observe", "phase", "event",
             "system_snapshot", "dump_flight", "tail", "summary", "close",
             "begin", "emit_wire_span", "emit_server_span", "emit_alert",
             "record"}

# the always-on ring's module-level surface (utils/telemetry.py): never a
# finding, guarded or not.  Kept disjoint from RECORDING so that adding a
# name to one cannot silently exempt or flag the other.
ALWAYS_ON = {"span", "count", "spans", "totals", "on_trace_clock",
             "watch_compiles", "open_bracket", "close_bracket",
             "drop_bracket"}
assert not ALWAYS_ON & RECORDING

HANDLE_SOURCES = {TELEMETRY_MODULE + ".active", TELEMETRY_MODULE + ".init",
                  TRACING_MODULE + ".active", TRACING_MODULE + ".init"}


def _test_mentions_enabled(test: ast.AST) -> bool:
    """True when the test DOMINATES on ``enabled``: the body is only
    reachable with the check true.  That's the bare read, an ``and``
    chain with an enabled conjunct, or an ``or`` whose EVERY alternative
    guards — `other() or tm.enabled` does NOT guard (the body runs with
    telemetry off through the left arm)."""
    if isinstance(test, ast.Attribute) and test.attr == "enabled":
        return True
    if isinstance(test, ast.Name) and test.id == "enabled":
        return True
    if isinstance(test, ast.BoolOp):
        if isinstance(test.op, ast.And):
            return any(_test_mentions_enabled(v) for v in test.values)
        return all(_test_mentions_enabled(v) for v in test.values)
    return False


def _test_negates_enabled(test: ast.AST) -> bool:
    """``not tm.enabled`` (the early-exit guard idiom)."""
    return isinstance(test, ast.UnaryOp) and \
        isinstance(test.op, ast.Not) and \
        _test_mentions_enabled(test.operand)


def _ends_control_flow(stmts) -> bool:
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Continue, ast.Break, ast.Raise))


@register
class TelemetryHotPathChecker(Checker):
    name = "telemetry-hot-path"
    description = ("telemetry/span-emission calls in steps/prefetch/"
                   "exchanger/worker/async_easgd/wire/center_server not "
                   "dominated by an `enabled` check")

    def applies_to(self, path: str) -> bool:
        return path.rsplit("/", 1)[-1] in HOT_BASENAMES

    def check_file(self, sf: SourceFile):
        handles = self._collect_handles(sf)
        findings: List[Finding] = []
        self._scan_block(sf, sf.tree.body, handles, False, findings)
        return findings

    # -- handle discovery --------------------------------------------------

    def _collect_handles(self, sf: SourceFile) -> Set[str]:
        handles: Set[str] = {"self.telemetry"}
        changed = True
        while changed:
            changed = False
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Assign):
                    continue
                src = None
                v = node.value
                if isinstance(v, ast.Call):
                    resolved = sf.resolver.resolve(v.func)
                    if resolved in HANDLE_SOURCES:
                        src = True
                elif isinstance(v, (ast.Name, ast.Attribute)):
                    if ImportResolver.dotted(v) in handles:
                        src = True
                if not src:
                    continue
                for t in node.targets:
                    name = ImportResolver.dotted(t)
                    if name and name not in handles:
                        handles.add(name)
                        changed = True
        return handles

    # -- guarded walk ------------------------------------------------------
    # Block-based so DOMINANCE is modeled, not just lexical nesting:
    # `if tm.enabled:` guards its body, `if not tm.enabled: return`
    # guards the REST of the enclosing block (the early-exit idiom), an
    # `elif tm.enabled:` arm guards its own body (If nodes in orelse
    # lists get the same treatment as top-level ones), and
    # `x if tm.enabled else y` guards its true arm.

    def _scan_block(self, sf, stmts, handles: Set[str], guarded: bool,
                    findings: List[Finding]) -> None:
        for st in stmts:
            if isinstance(st, ast.If):
                self._scan_expr(sf, st.test, handles, guarded, findings)
                body_guarded = guarded or _test_mentions_enabled(st.test)
                neg = _test_negates_enabled(st.test)
                self._scan_block(sf, st.body, handles, body_guarded,
                                 findings)
                self._scan_block(sf, st.orelse, handles, guarded or neg,
                                 findings)
                if neg and _ends_control_flow(st.body):
                    # `if not tm.enabled: return` — everything after is
                    # only reachable with telemetry on
                    guarded = True
                continue
            # other statements: scan expressions, recurse into any
            # nested blocks (loops, with, try, function/class bodies —
            # a def under a guard inherits it: the closure is only
            # created on the enabled path)
            for fieldname, value in ast.iter_fields(st):
                if isinstance(value, list) and value and \
                        isinstance(value[0], ast.stmt):
                    self._scan_block(sf, value, handles, guarded, findings)
                elif isinstance(value, list):
                    for v in value:
                        if isinstance(v, ast.excepthandler):
                            self._scan_block(sf, v.body, handles, guarded,
                                             findings)
                        elif isinstance(v, ast.AST):
                            self._scan_expr(sf, v, handles, guarded,
                                            findings)
                elif isinstance(value, ast.AST):
                    self._scan_expr(sf, value, handles, guarded, findings)

    def _scan_expr(self, sf, node, handles, guarded, findings) -> None:
        if node is None:
            return
        if isinstance(node, ast.IfExp):
            self._scan_expr(sf, node.test, handles, guarded, findings)
            body_guarded = guarded or _test_mentions_enabled(node.test)
            self._scan_expr(sf, node.body, handles, body_guarded, findings)
            self._scan_expr(sf, node.orelse, handles,
                            guarded or _test_negates_enabled(node.test),
                            findings)
            return
        if isinstance(node, ast.Call):
            self._check_call(sf, node, handles, guarded, findings)
        for child in ast.iter_child_nodes(node):
            self._scan_expr(sf, child, handles, guarded, findings)

    def _check_call(self, sf, node, handles, guarded, findings) -> None:
        if guarded:
            return
        func = node.func
        if not isinstance(func, ast.Attribute) or \
                func.attr not in RECORDING:
            return
        base = ImportResolver.dotted(func.value)
        resolved_base = sf.resolver.resolve(func.value)
        is_handle = (base in handles) or \
            (resolved_base in (TELEMETRY_MODULE, TRACING_MODULE,
                               FLEETMON_MODULE, NUMERICS_MODULE))
        if is_handle:
            findings.append(Finding(
                self.name, sf.path, node.lineno, node.col_offset,
                f"unguarded telemetry call `{base}.{func.attr}(...)` on a "
                "hot path — wrap in `if <handle>.enabled:` (one attribute "
                "check when disabled, docs/design.md §11)"))

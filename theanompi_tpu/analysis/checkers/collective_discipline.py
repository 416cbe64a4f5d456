"""collective-discipline: named-axis collectives must be well-formed SPMD.

Three invariants (docs/design.md §12):

1. **Axis names are real.**  Every ``lax.psum`` / ``pmean`` /
   ``ppermute`` / ``all_gather`` / ``all_to_all`` / ``axis_index`` /
   ``psum_scatter`` (and ``jax_compat`` shim) call whose axis argument
   is statically evaluable must name an axis the program can actually
   bind: the axes ``parallel/mesh.py`` declares (``*_AXIS`` module
   constants — the one source of truth, read live from the parsed file)
   plus any axis literally declared in the SAME file (``Mesh(devs,
   ("workers", "seq"))``, ``axis_name="seq"``).  A typo'd axis traces
   fine and deadlocks (or mis-reduces) at run time on the pod — the
   static check catches it in seconds.  Unknown (parameter-passed,
   computed) axis arguments are SKIPPED, never guessed.

2. **No collectives under rank-divergent branches.**  A collective
   lexically inside a Python ``if``/``while``/conditional-expression
   whose test dataflows from ``lax.axis_index`` / ``jax.process_index``
   is a divergence hazard: under multi-host SPMD each process traces
   its own program, so a rank-dependent Python branch makes some hosts
   issue a collective others never reach — the canonical SPMD deadlock.
   The same applies to a ``lax.cond``/``lax.switch`` whose predicate is
   rank-derived when a branch (transitively) issues collectives.
   Dataflow is per-function: names assigned from the two APIs taint,
   taint propagates through assignments.

3. **Paired start/done APIs match — the pairing probe.**  Async
   collective pairs (``lax.<x>_start`` / ``lax.<x>_done`` and the
   ``jax_compat.ppermute_start`` / ``ppermute_done`` shim that
   ``parallel/pipeline.py``'s per-slot hop issues) must balance within
   one function scope: a start with no done leaks an in-flight
   collective, a done with no start is undefined.  Additionally a
   ``<x>_start`` whose ticket is DISCARDED (a bare expression
   statement) is flagged even when another start/done pair balances
   the counts — the in-flight token must reach its done.  The
   shim-definition module itself (``theanompi_tpu/jax_compat.py``) is
   exempt: each shim half lexically contains its one-sided underlying
   ``lax`` call by construction — that file IS the pairing boundary.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..core import Checker, Finding, SourceFile, register
from ..engine import (COLLECTIVES, FuncRecord, ProgramIndex, axis_values,
                      body_walk, collective_name)

MESH_MODULE = "theanompi_tpu.parallel.mesh"

# fallback when parallel/mesh.py is not in the linted file set (single
# -file fixture runs) — mirrors its *_AXIS declarations
DEFAULT_DECLARED = ("workers", "model", "pipe", "seq")

RANK_SOURCES = {
    "jax.lax.axis_index", "jax.process_index",
    "theanompi_tpu.jax_compat.axis_index",
}

_ASYNC_MODULES = ("jax.lax.", "theanompi_tpu.jax_compat.")

# the module DEFINING the async shims: each `<x>_start`/`<x>_done` half
# wraps its one-sided underlying lax call, so pairing is structurally
# one-sided there by construction — exempt from the balance probe
_SHIM_MODULE = "theanompi_tpu.jax_compat"


def _async_pair(resolved: Optional[str]) -> Optional[Tuple[str, str]]:
    """('prefix', 'start'|'done') of an async collective API name."""
    if not resolved:
        return None
    for mod in _ASYNC_MODULES:
        if resolved.startswith(mod):
            simple = resolved[len(mod):]
            for suffix in ("start", "done"):
                if simple.endswith("_" + suffix):
                    return simple[:-(len(suffix) + 1)], suffix
    return None


@register
class CollectiveDisciplineChecker(Checker):
    name = "collective-discipline"
    description = ("collective axis names must be declared mesh axes; no "
                   "collectives under rank-derived branches; start/done "
                   "pairs must balance")
    needs_engine = True

    def check_program(self, index: ProgramIndex):
        declared = self._declared_axes(index)
        self._index_consts = index._module_constants
        findings: List[Finding] = []
        for sf in index.files:
            valid = declared | self._file_axes(sf)
            module_consts = {
                name.rsplit(".", 1)[-1]: v
                for name, v in index._module_constants.items()
                if name.startswith(sf.resolver.module + ".")
                and isinstance(v, str)}
            # module scope + every function scope
            scopes: List[Optional[ast.AST]] = [None]
            scopes += [n for n in ast.walk(sf.tree)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
            for scope in scopes:
                self._check_scope(index, sf, scope, valid, module_consts,
                                  findings)
        return findings

    # -- axis vocabulary ---------------------------------------------------

    def _declared_axes(self, index: ProgramIndex) -> Set[str]:
        axes = {v for name, v in index._module_constants.items()
                if name.startswith(MESH_MODULE + ".")
                and name.rsplit(".", 1)[-1].endswith("_AXIS")
                and isinstance(v, str)}
        return axes or set(DEFAULT_DECLARED)

    def _file_axes(self, sf: SourceFile) -> Set[str]:
        """Axes literally declared in this file: ``Mesh(devs, (...))``
        axis tuples and ``axis_name=``/``axis_names=`` kwarg literals."""
        out: Set[str] = set()
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = sf.resolver.resolve(node.func)
            is_mesh = (resolved or "").endswith("sharding.Mesh") or \
                (isinstance(node.func, ast.Name) and
                 node.func.id == "Mesh") or \
                (isinstance(node.func, ast.Attribute) and
                 node.func.attr == "Mesh")
            if is_mesh and len(node.args) > 1:
                out.update(self._str_literals(node.args[1]))
            # `axis_name=` on a BINDER (Mesh/worker_mesh/pmap/...)
            # declares an axis; on a COLLECTIVE it is the argument under
            # validation — harvesting it there would self-whitelist the
            # very typo this checker exists to catch
            if collective_name(resolved) is not None:
                continue
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis_names"):
                    out.update(self._str_literals(kw.value))
        return out

    @staticmethod
    def _str_literals(node: ast.AST) -> Set[str]:
        out: Set[str] = set()
        for sub in ast.walk(node):
            if isinstance(sub, ast.Constant) and \
                    isinstance(sub.value, str):
                out.add(sub.value)
        return out

    # -- per-scope checks --------------------------------------------------

    def _scope_stmts(self, sf: SourceFile, scope: Optional[ast.AST]):
        """Statements belonging to this scope only (no nested defs)."""
        body = sf.tree.body if scope is None else scope.body
        stack = list(body)
        while stack:
            st = stack.pop()
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            yield st
            for fieldname in ("body", "orelse", "finalbody"):
                stack.extend(getattr(st, fieldname, []) or [])
            for h in getattr(st, "handlers", []):
                stack.extend(h.body)

    def _check_scope(self, index: ProgramIndex, sf: SourceFile, scope,
                     valid: Set[str], module_consts: Dict[str, str],
                     findings: List[Finding]) -> None:
        local_consts = dict(module_consts)
        tainted = self._tainted_names(sf, scope, local_consts)
        stmts = list(self._scope_stmts(sf, scope))
        seen_hazard: Set[Tuple[int, int]] = set()

        # 1 + 3: axis validity and start/done balance (the pairing
        # probe).  Each call is visited exactly once: through the
        # expression roots of its own statement (nested block statements
        # are yielded separately).  The shim-definition module is exempt
        # from pairing — each shim half is one-sided by construction.
        check_pairs = sf.resolver.module != _SHIM_MODULE
        pairs: Dict[str, Dict[str, List[ast.Call]]] = {}
        ticket_assigns: List[Tuple[str, ast.Call, str]] = []
        for st in stmts:
            for expr in self._stmt_exprs(st):
                for call in self._calls(expr):
                    resolved = sf.resolver.resolve(call.func)
                    cname = collective_name(resolved)
                    if cname is not None:
                        for axis in axis_values(call, cname, sf.resolver,
                                                index, local_consts):
                            if isinstance(axis, str) and axis not in valid:
                                findings.append(Finding(
                                    self.name, sf.path, call.lineno,
                                    call.col_offset,
                                    f"collective `{cname}` over "
                                    f"undeclared mesh axis '{axis}' "
                                    "(declared: "
                                    f"{', '.join(sorted(valid))})"))
                    ap = _async_pair(resolved)
                    if ap is not None and check_pairs:
                        pairs.setdefault(ap[0], {}).setdefault(
                            ap[1], []).append(call)
                        if ap[1] == "start" and \
                                isinstance(st, ast.Assign) and \
                                len(st.targets) == 1 and \
                                isinstance(st.targets[0], ast.Name) and \
                                st.value is call:
                            # candidate for the dead-ticket probe below
                            ticket_assigns.append(
                                (st.targets[0].id, call, ap[0]))
                        if ap[1] == "start" and isinstance(st, ast.Expr) \
                                and st.value is call:
                            # ticket discarded on the floor: even with the
                            # counts balanced elsewhere, THIS in-flight
                            # collective can never be awaited
                            findings.append(Finding(
                                self.name, sf.path, call.lineno,
                                call.col_offset,
                                f"leaked in-flight collective: "
                                f"`{ap[0]}_start` ticket is discarded "
                                f"(bare expression statement) — it can "
                                f"never reach `{ap[0]}_done`"))
        unbalanced: Set[str] = set()
        for prefix, sides in sorted(pairs.items()):
            starts = sides.get("start", [])
            dones = sides.get("done", [])
            if len(starts) != len(dones):
                unbalanced.add(prefix)
                anchor = (starts or dones)[0]
                findings.append(Finding(
                    self.name, sf.path, anchor.lineno, anchor.col_offset,
                    f"unbalanced async collective pair: "
                    f"{len(starts)}x `{prefix}_start` vs {len(dones)}x "
                    f"`{prefix}_done` in the same scope"))

        # 3b: dead-ticket probe (round 10, the per-schedule-slot hop of
        # the interleaved pipeline scan body): a name assigned from a
        # `<x>_start` and never read again cannot reach its done even
        # when the scope's start/done COUNTS balance through other pairs
        # (e.g. a typo'd done consuming the wrong ticket twice) — that
        # schedule slot's hop is leaked in-flight every tick.  Loads are
        # collected over the whole scope subtree (nested defs included)
        # so a ticket consumed by a closure never false-positives; an
        # unbalanced prefix is already reported above, so the probe only
        # speaks when the counts LOOK healthy.
        if check_pairs and ticket_assigns:
            loaded: Set[str] = set()
            for sub in ast.walk(scope if scope is not None else sf.tree):
                if isinstance(sub, ast.Name) and \
                        isinstance(sub.ctx, ast.Load):
                    loaded.add(sub.id)
            for tname, call, prefix in ticket_assigns:
                if tname not in loaded and prefix not in unbalanced:
                    findings.append(Finding(
                        self.name, sf.path, call.lineno, call.col_offset,
                        f"dropped hop ticket: `{tname}` holds the "
                        f"`{prefix}_start` in-flight collective but is "
                        f"never consumed — this slot's hop can never "
                        f"reach `{prefix}_done`"))

        # 2: collectives under rank-derived branches
        for st in stmts:
            if isinstance(st, (ast.If, ast.While)) and \
                    self._test_tainted(sf, st.test, tainted):
                for arm in (st.body, st.orelse):
                    self._flag_collectives_under(
                        index, sf, scope, arm, st, seen_hazard, findings)
            for expr in self._stmt_exprs(st):
                for node in ast.walk(expr):
                    if isinstance(node, ast.IfExp) and \
                            self._test_tainted(sf, node.test, tainted):
                        self._flag_collectives_under(
                            index, sf, scope, [node.body, node.orelse],
                            node, seen_hazard, findings)
                    elif isinstance(node, ast.Call):
                        resolved = sf.resolver.resolve(node.func)
                        if resolved in ("jax.lax.cond",
                                        "jax.lax.switch") \
                                and node.args and self._test_tainted(
                                    sf, node.args[0], tainted):
                            self._flag_cond_branches(index, sf, scope,
                                                     node, findings)

    @staticmethod
    def _calls(node: ast.AST):
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                yield sub

    @staticmethod
    def _exec_calls(node: ast.AST):
        """Calls executed when this subtree runs: descends lambdas
        (tree.map bodies run here) but not nested function DEFINITIONS
        (merely defining one issues nothing)."""
        stack = [node]
        while stack:
            sub = stack.pop()
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if isinstance(sub, ast.Call):
                yield sub
            stack.extend(ast.iter_child_nodes(sub))

    @staticmethod
    def _stmt_exprs(st: ast.stmt):
        """Expression roots of one statement — its non-statement AST
        children (nested statement blocks are separate scope items)."""
        for _, value in ast.iter_fields(st):
            if isinstance(value, ast.AST) and not isinstance(value,
                                                             ast.stmt):
                yield value
            elif isinstance(value, list):
                for v in value:
                    if isinstance(v, ast.AST) and \
                            not isinstance(v, (ast.stmt,
                                               ast.excepthandler)):
                        yield v

    def _tainted_names(self, sf: SourceFile, scope,
                       local_consts: Dict[str, str]) -> Set[str]:
        """Names whose value dataflows from axis_index/process_index —
        and, on the way, fold string-literal assignments into
        ``local_consts`` (the axis-name constant propagation)."""
        tainted: Set[str] = set()
        stmts = list(self._scope_stmts(sf, scope))

        def expr_tainted(expr: ast.AST) -> bool:
            for sub in ast.walk(expr):
                if isinstance(sub, ast.Call) and \
                        sf.resolver.resolve(sub.func) in RANK_SOURCES:
                    return True
                if isinstance(sub, ast.Name) and sub.id in tainted and \
                        isinstance(sub.ctx, ast.Load):
                    return True
            return False

        def fold_consts(target: ast.AST, value: ast.AST) -> None:
            if isinstance(target, ast.Name):
                if isinstance(value, ast.Constant) and \
                        isinstance(value.value, str):
                    local_consts[target.id] = value.value
                elif isinstance(value, (ast.Name, ast.Attribute)):
                    resolved = sf.resolver.resolve(value)
                    if resolved:
                        # imported mesh-axis constant
                        v = self._index_consts.get(resolved)
                        if isinstance(v, str):
                            local_consts[target.id] = v
                    elif isinstance(value, ast.Name) and \
                            value.id in local_consts:
                        local_consts[target.id] = local_consts[value.id]
            elif isinstance(target, (ast.Tuple, ast.List)) and \
                    isinstance(value, (ast.Tuple, ast.List)) and \
                    len(target.elts) == len(value.elts):
                for t, v in zip(target.elts, value.elts):
                    fold_consts(t, v)

        changed = True
        passes = 0
        while changed and passes < 10:
            changed = False
            passes += 1
            for st in stmts:
                targets, value = [], None
                if isinstance(st, ast.Assign):
                    targets, value = st.targets, st.value
                elif isinstance(st, ast.AnnAssign) and st.value is not None:
                    targets, value = [st.target], st.value
                elif isinstance(st, ast.AugAssign):
                    targets, value = [st.target], st.value
                if value is None:
                    continue
                for t in targets:
                    fold_consts(t, value)
                if expr_tainted(value):
                    for t in targets:
                        for sub in ast.walk(t):
                            if isinstance(sub, ast.Name) and \
                                    sub.id not in tainted:
                                tainted.add(sub.id)
                                changed = True
        return tainted

    def _test_tainted(self, sf: SourceFile, test: ast.AST,
                      tainted: Set[str]) -> bool:
        for sub in ast.walk(test):
            if isinstance(sub, ast.Name) and sub.id in tainted and \
                    isinstance(sub.ctx, ast.Load):
                return True
            if isinstance(sub, ast.Call) and \
                    sf.resolver.resolve(sub.func) in RANK_SOURCES:
                return True
        return False

    def _flag_collectives_under(self, index: ProgramIndex, sf: SourceFile,
                                scope, arm, branch_node,
                                seen_hazard: Set[Tuple[int, int]],
                                findings: List[Finding]) -> None:
        nodes = arm if isinstance(arm, list) else [arm]
        for n in nodes:
            for call in self._exec_calls(n):
                if (call.lineno, call.col_offset) in seen_hazard:
                    continue
                resolved = sf.resolver.resolve(call.func)
                cname = collective_name(resolved)
                via = None
                if cname is None:
                    fidx = index.file_index[sf.path]
                    enc = fidx.enclosing.get(id(call.func), scope)
                    for tgt in index.resolve_call(sf, call.func, enc):
                        ts = index.transitive_summary(tgt)
                        if ts.issues_collective:
                            cname = "/".join(sorted(
                                ts.collective_names)) or "collective"
                            via = tgt.name
                            break
                if cname is None:
                    continue
                msg = (f"divergence hazard: collective `{cname}` under a "
                       f"branch whose condition derives from "
                       f"axis_index/process_index (line "
                       f"{branch_node.lineno}) — some ranks may never "
                       "issue it")
                if via:
                    msg = (f"divergence hazard: call to `{via}` (issues "
                           f"`{cname}`) under a branch whose condition "
                           f"derives from axis_index/process_index "
                           f"(line {branch_node.lineno}) — some ranks "
                           "may never issue it")
                seen_hazard.add((call.lineno, call.col_offset))
                findings.append(Finding(self.name, sf.path, call.lineno,
                                        call.col_offset, msg))

    def _flag_cond_branches(self, index: ProgramIndex, sf: SourceFile,
                            scope, cond_call: ast.Call,
                            findings: List[Finding]) -> None:
        fidx = index.file_index[sf.path]
        for arg in cond_call.args[1:]:
            targets: List[FuncRecord] = []
            if isinstance(arg, ast.Lambda):
                rec = index.record_for(arg)
                if rec is not None:
                    targets = [rec]
            elif isinstance(arg, (ast.Name, ast.Attribute)):
                enc = fidx.enclosing.get(id(arg), scope)
                targets = index.resolve_call(sf, arg, enc)
            for tgt in targets:
                ts = index.transitive_summary(tgt)
                if ts.issues_collective:
                    names = "/".join(sorted(ts.collective_names)) or \
                        "collective"
                    findings.append(Finding(
                        self.name, sf.path, cond_call.lineno,
                        cond_call.col_offset,
                        f"divergence hazard: `lax.cond`/`lax.switch` "
                        f"with a rank-derived predicate selects branch "
                        f"`{tgt.name}` issuing `{names}` — predicate "
                        "must be uniform across ranks"))
                    return

    # constants from the engine, stashed per run by check_program's caller
    _index_consts: Dict[str, object] = {}

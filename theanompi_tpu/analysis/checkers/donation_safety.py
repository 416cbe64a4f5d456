"""donation-safety: a donated buffer must not be read after the call —
plus shard-rebuild-dominance, the update-sharding escape gate.

The invariant (docs/design.md §12):
``jax.jit(..., donate_argnums=...)`` hands the argument's HBM to the
callee — after the call the old array is invalid, and reading it is
use-after-free that jax only sometimes catches.

Per-scope analysis: the checker records names bound to
``jax.jit(..., donate_argnums=...)`` with their donated positional
indices (literal argnums, or argnames mapped through an inline
lambda's signature; an unresolvable spec is skipped rather than
guessed — a wrong guess would flag the wrong argument), then scans the
scope linearly —
a call through such a name marks the argument names/dotted paths at the
donated positions as dead, a store revives them, and any later read is
a finding.  The ``state = train_fn(state, ...)`` rebind idiom is
recognized: consuming and rebinding in one statement is the sanctioned
in-place-update shape.  Branch bodies scan against a state copy, so
exclusive arms cannot poison each other.

Interprocedural (the whole-program engine): module-level donating
callables are collected REPO-WIDE and resolved through each file's
import table, so ``from train import step_fn`` — where ``train.py``
holds ``step_fn = jax.jit(g, donate_argnums=0)`` — flags a
read-after-donate at the importing call site too.

shard-rebuild-dominance (docs/design.md §23): the update-sharding
wrapper (``parallel/update_sharding.py``) cuts worker-local chunks out
of full buffers (``slice_chunk``/``shard_tree``) that are only valid
shard-wide — under the ``_build_exchange_fn`` ``donate_argnums=(0,)``
contract, a function that lets such a chunk ESCAPE (return it) without
its allgather rebuild silently replaces a donated full buffer with a
1/N-sized local shard.  The checker taints names bound from the named
producers, propagates through arithmetic/containers (never through
arbitrary calls — an optimizer update of a chunk is a new value the
schema owns), clears taint only when a rebuild
(``all_gather_chunks``/``unshard_tree``/``all_gather``) DOMINATES the
return — a rebind inside one branch of an ``if`` does not count — and
exempts the schema's own named producer helpers (``shard_*``,
``reshard_*``, ``slice_*``, ``chunk_*``), whose very job is returning
chunks.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from ..core import Checker, Finding, ImportResolver, SourceFile, register
from ..engine import ProgramIndex

_JIT_NAMES = {"jax.jit"}


def _donated_indices(call: ast.Call) -> Optional[Set[int]]:
    """Donated positional indices of a jax.jit call, or None when the
    call donates nothing — or when the spec cannot be resolved
    STATICALLY (non-literal argnums, argnames against an opaque
    callee): guessing an index would flag the wrong argument while
    waving the donated one through, so unresolvable specs are skipped.
    ``donate_argnames`` resolves when the jitted callee is an inline
    lambda/visible signature (names map to positional slots)."""
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return {v.value}
            if isinstance(v, (ast.Tuple, ast.List)):
                idx = {e.value for e in v.elts
                       if isinstance(e, ast.Constant)
                       and isinstance(e.value, int)}
                if idx:
                    return idx
            return None
        if kw.arg == "donate_argnames":
            names = _literal_names(kw.value)
            params = _callee_params(call)
            if names and params:
                idx = {params.index(n) for n in names if n in params}
                if idx:
                    return idx
            return None
    return None


def _literal_names(v: ast.AST) -> Set[str]:
    if isinstance(v, ast.Constant) and isinstance(v.value, str):
        return {v.value}
    if isinstance(v, (ast.Tuple, ast.List)):
        return {e.value for e in v.elts
                if isinstance(e, ast.Constant)
                and isinstance(e.value, str)}
    return set()


def _callee_params(call: ast.Call) -> Optional[list]:
    """Positional parameter names of the jitted callee, when visible
    (an inline lambda)."""
    if call.args and isinstance(call.args[0], ast.Lambda):
        a = call.args[0].args
        return [p.arg for p in list(a.posonlyargs) + list(a.args)]
    return None


@register
class DonationSafetyChecker(Checker):
    name = "donation-safety"
    description = ("a name passed through a donate_argnums call site and "
                   "read afterwards in the same scope (donating callables "
                   "resolved repo-wide)")
    needs_engine = True

    def check_program(self, index: ProgramIndex):
        # module-level donating callables, repo-wide, by absolute dotted
        # name — visible through any file's import table
        self._global_fns: Dict[str, Set[int]] = {}
        for sf in index.files:
            module = sf.resolver.module
            for name, idx in self._collect_donating_fns(sf,
                                                        sf.tree).items():
                if "." not in name:    # dotted targets stay file-local
                    self._global_fns[f"{module}.{name}"] = idx
        findings: List[Finding] = []
        for sf in index.files:
            findings.extend(self._check_one(sf))
        return findings

    def _check_one(self, sf: SourceFile):
        findings: List[Finding] = []
        # module-level donating names (`f = jax.jit(g, donate_argnums=0)`
        # at top level) are visible from every function scope — merge
        # them under each scope's own collection
        module_fns = self._collect_donating_fns(sf, sf.tree)
        scopes = [sf.tree] + [n for n in ast.walk(sf.tree)
                              if isinstance(n, (ast.FunctionDef,
                                                ast.AsyncFunctionDef))]
        for scope in scopes:
            donated_fns = dict(module_fns)
            if scope is not sf.tree:
                donated_fns.update(self._collect_donating_fns(sf, scope))
            body = scope.body if isinstance(scope.body, list) else []
            self._scan_block(sf, body, donated_fns, {}, findings)
        return findings

    # -- pass 1: which names are donating jitted callables -----------------

    def _collect_donating_fns(self, sf: SourceFile, scope
                              ) -> Dict[str, Set[int]]:
        out: Dict[str, Set[int]] = {}
        for st in self._shallow_stmts(scope):
            if isinstance(st, ast.Assign) and isinstance(st.value, ast.Call):
                resolved = sf.resolver.resolve(st.value.func)
                if resolved in _JIT_NAMES:
                    idx = _donated_indices(st.value)
                    if idx:
                        for t in st.targets:
                            name = ImportResolver.dotted(t)
                            if name:
                                out[name] = idx
        return out

    @staticmethod
    def _shallow_stmts(scope):
        """Statements of this scope, not descending into nested defs."""
        stack = list(scope.body) if isinstance(scope.body, list) else []
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

    # -- pass 2: linear scan for read-after-donate -------------------------

    def _scan_block(self, sf, stmts, donated_fns: Dict[str, Set[int]],
                    dead: Dict[str, int], findings: List[Finding]) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue
            if isinstance(st, (ast.If, ast.For, ast.AsyncFor, ast.While,
                               ast.Try, ast.With, ast.AsyncWith)):
                header = getattr(st, "test", None) or getattr(st, "iter",
                                                              None)
                if header is not None:
                    self._scan_stmt(sf, header, donated_fns, dead, findings,
                                    stores=())
                for fieldname in ("body", "orelse", "finalbody"):
                    sub = getattr(st, fieldname, None)
                    if sub:
                        self._scan_block(sf, sub, donated_fns, dict(dead),
                                         findings)
                for h in getattr(st, "handlers", []):
                    self._scan_block(sf, h.body, donated_fns, dict(dead),
                                     findings)
                for n in self._stored_names(st):
                    dead.pop(n, None)
                continue
            stores = tuple(self._stored_names(st))
            self._scan_stmt(sf, st, donated_fns, dead, findings, stores)
            for n in stores:
                dead.pop(n, None)

    def _scan_stmt(self, sf, node, donated_fns, dead, findings,
                   stores) -> None:
        """Reads first (a read of a dead name fires even when the same
        statement rebinds it later — ``y = x + f(x_dead)``), then the
        donations this statement performs."""
        # 1. reads of dead names (a dead name in callee position is fine
        #    — only a donated fn's DATA args die, not the callable)
        call_funcs = {id(sub.func) for sub in ast.walk(node)
                      if isinstance(sub, ast.Call)}
        for sub in ast.walk(node):
            if isinstance(sub, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(sub, "ctx", None), ast.Load):
                name = ImportResolver.dotted(sub)
                if name in dead and id(sub) not in call_funcs:
                    findings.append(Finding(
                        self.name, sf.path, sub.lineno, sub.col_offset,
                        f"`{name}` read after being donated on line "
                        f"{dead[name]} (donate_argnums hands its buffer "
                        "to the callee; rebind the result instead)"))
                    dead.pop(name)      # report once per donation
        # 2. donations performed by calls in this statement
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            idx: Optional[Set[int]] = None
            fname = ImportResolver.dotted(sub.func)
            if fname and fname in donated_fns:
                idx = donated_fns[fname]
            elif isinstance(sub.func, ast.Call):
                resolved = sf.resolver.resolve(sub.func.func)
                if resolved in _JIT_NAMES:
                    idx = _donated_indices(sub.func)
            else:
                # a donating callable imported from another module
                resolved = sf.resolver.resolve(sub.func)
                if resolved is not None:
                    idx = getattr(self, "_global_fns", {}).get(resolved)
            if not idx:
                continue
            for i in idx:
                if i < len(sub.args):
                    name = ImportResolver.dotted(sub.args[i])
                    # rebind-in-place (`state = fn(state)`) is the
                    # sanctioned donation shape — not dead afterwards
                    if name and name not in stores:
                        dead[name] = sub.lineno

    @staticmethod
    def _stored_names(node):
        for sub in ast.walk(node):
            if isinstance(sub, (ast.Name, ast.Attribute)) and \
                    isinstance(getattr(sub, "ctx", None),
                               (ast.Store, ast.Del)):
                name = ImportResolver.dotted(sub)
                if name:
                    yield name


# ---------------------------------------------------------------------------
# shard-rebuild-dominance
# ---------------------------------------------------------------------------

#: functions that CUT a worker-local chunk out of a full buffer — their
#: results are only valid shard-wide (matched on the dotted name's last
#: segment so ``update_sharding.slice_chunk`` and a bare import both hit)
_SHARD_PRODUCERS = {"slice_chunk", "shard_tree"}
#: functions that REBUILD the full buffer from every worker's chunk —
#: binding through one of these cleanses the result
_SHARD_REBUILDS = {"all_gather_chunks", "unshard_tree", "all_gather"}
#: the schema's own producer helpers: returning a chunk is their JOB
_EXEMPT_FN = re.compile(r"^(shard|reshard|slice|chunk)_")


def _last_segment(func: ast.AST) -> Optional[str]:
    name = ImportResolver.dotted(func)
    return name.rsplit(".", 1)[-1] if name else None


@register
class ShardRebuildDominanceChecker(Checker):
    name = "shard-rebuild-dominance"
    description = ("a worker-local shard (slice_chunk/shard_tree result) "
                   "escaping a function without its allgather rebuild "
                   "dominating the return")
    needs_engine = False

    def check_file(self, sf: SourceFile):
        findings: List[Finding] = []
        for fn in ast.walk(sf.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if _EXEMPT_FN.match(fn.name):
                continue
            self._scan(sf, fn, fn.body, {}, findings, top=True)
        return findings

    def _scan(self, sf, fn, stmts, tainted: Dict[str, int],
              findings: List[Finding], top: bool) -> None:
        """Linear scan; ``tainted`` maps name → producer line.  Nested
        control-flow bodies scan with ``top=False``: taint they ADD is
        real (it may reach the return), but a rebuild there does NOT
        clear — it doesn't dominate the paths that skip the branch."""
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.ClassDef)):
                continue        # nested defs are scanned as their own fns
            if isinstance(st, ast.Return) and st.value is not None:
                hit = self._expr_taint(st.value, tainted)
                if hit is not None:
                    name, line = hit
                    findings.append(Finding(
                        self.name, sf.path, st.lineno, st.col_offset,
                        f"`{name}` holds a worker-local shard (produced "
                        f"on line {line}) escaping `{fn.name}` without "
                        "its allgather rebuild (all_gather_chunks/"
                        "unshard_tree must dominate the return)"))
                continue
            if isinstance(st, (ast.If, ast.For, ast.AsyncFor, ast.While,
                               ast.Try, ast.With, ast.AsyncWith)):
                for fieldname in ("body", "orelse", "finalbody"):
                    sub = getattr(st, fieldname, None)
                    if sub:
                        self._scan(sf, fn, sub, tainted, findings,
                                   top=False)
                for h in getattr(st, "handlers", []):
                    self._scan(sf, fn, h.body, tainted, findings,
                               top=False)
                continue
            if isinstance(st, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = st.value
                if value is None:
                    continue
                hit = self._expr_taint(value, tainted)
                targets = st.targets if isinstance(st, ast.Assign) \
                    else [st.target]
                names = [n for t in targets
                         for n in self._target_names(t)]
                if hit is not None:
                    for n in names:
                        tainted[n] = hit[1]
                elif top:
                    # a clean rebind cleanses — but only here at the
                    # function's top level, where it dominates the return
                    for n in names:
                        tainted.pop(n, None)

    def _expr_taint(self, node, tainted: Dict[str, int]
                    ) -> Optional[Tuple[str, int]]:
        """(name, producer line) when the expression carries a shard:
        a producer call, a tainted name, or either propagated through
        arithmetic/containers/subscripts.  Arbitrary calls STOP taint —
        their result is a new value (the inner optimizer's elementwise
        update of a chunk is the schema's own business)."""
        if isinstance(node, ast.Call):
            last = _last_segment(node.func)
            if last in _SHARD_REBUILDS:
                return None
            if last in _SHARD_PRODUCERS:
                return (f"{last}(...)", node.lineno)
            return None
        if isinstance(node, (ast.Name, ast.Attribute)):
            name = ImportResolver.dotted(node)
            if name in tainted:
                return (name, tainted[name])
            return None
        if isinstance(node, ast.BinOp):
            return (self._expr_taint(node.left, tainted)
                    or self._expr_taint(node.right, tainted))
        if isinstance(node, ast.UnaryOp):
            return self._expr_taint(node.operand, tainted)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            for e in node.elts:
                hit = self._expr_taint(e, tainted)
                if hit:
                    return hit
            return None
        if isinstance(node, ast.Dict):
            for v in node.values:
                if v is not None:
                    hit = self._expr_taint(v, tainted)
                    if hit:
                        return hit
            return None
        if isinstance(node, (ast.Subscript, ast.Starred)):
            return self._expr_taint(node.value, tainted)
        if isinstance(node, ast.IfExp):
            return (self._expr_taint(node.body, tainted)
                    or self._expr_taint(node.orelse, tainted))
        return None

    @staticmethod
    def _target_names(t) -> List[str]:
        if isinstance(t, (ast.Tuple, ast.List)):
            return [n for e in t.elts
                    for n in ShardRebuildDominanceChecker._target_names(e)]
        if isinstance(t, ast.Starred):
            return ShardRebuildDominanceChecker._target_names(t.value)
        name = ImportResolver.dotted(t)
        return [name] if name else []


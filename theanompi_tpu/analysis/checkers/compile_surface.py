"""Compile-surface discipline: retrace hazards and mixed-precision dtype
flow (docs/design.md §26).

Trace-reachable code must not silently recompile per step or silently
change numerics.  Two checkers hold that:

``retrace-hazard``
    Call shapes that silently recompile per step: a fresh
    ``lambda``/``functools.partial`` at a ``jax.jit`` boundary (jit
    caches by function identity), ``jax.jit`` invoked inside a loop, a
    jit-boundary parameter spent in a shape-static slot without
    ``static_argnums`` (concretization-error-or-per-value-retrace bait),
    and host values (clocks, ``os.environ``, host RNG) feeding shape
    arithmetic in trace-reachable code.

``dtype-flow``
    Low-precision wire numerics: a collective whose operand is
    statically cast to bf16/f16 must re-upcast before any accumulate
    (``+``/``sum``/``mean``); a wire cast applied to the packed vector
    before bucketing breaks the §19 per-bucket contract; and any
    deliberate non-bit-exact rounding (a direct
    ``.astype(a).astype(b)`` round-trip) must be registered in the
    module's pure-literal ``NONBITEXACT = {"Class.method": "reason"}``
    registry (the ``PALLAS_ORACLES`` pattern) — unregistered round-trips
    and stale registry entries are both findings.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..core import (Checker, Finding, ImportResolver, SourceFile,
                    register)
from ..engine import (HOST_CLOCKS, LOW_PRECISION_DTYPES, ProgramIndex,
                      body_walk, collective_name, static_dtype)

#: Function simple names whose bodies build traced programs — the
#: retrace-hazard seeds beside the jit boundaries.  Matched by simple
#: name so single-file fixture runs resolve the same way the repo tree
#: does.
TRACE_SURFACES = (
    "compile_iter_fns", "build_train_step", "build_val_step",
    "build_schedule", "plan_tree", "exchange_body",
)

NONBITEXACT_NAME = "NONBITEXACT"

_SHARD_MAPS = ("jax.shard_map", "jax.experimental.shard_map.shard_map",
               "theanompi_tpu.jax_compat.shard_map")


# ---------------------------------------------------------------------------
# retrace hazards
# ---------------------------------------------------------------------------

def _jit_static_names(call: ast.Call, params: List[str]) -> Set[str]:
    """Parameter names covered by static_argnums/static_argnames."""
    out: Set[str] = set()
    for kw in call.keywords:
        if kw.arg not in ("static_argnums", "static_argnames"):
            continue
        vals = kw.value.elts if isinstance(
            kw.value, (ast.Tuple, ast.List)) else [kw.value]
        for v in vals:
            if not isinstance(v, ast.Constant):
                continue
            if isinstance(v.value, int) and v.value < len(params):
                out.add(params[v.value])
            elif isinstance(v.value, str):
                out.add(v.value)
    return out


def _host_value_desc(node: ast.AST, resolver: ImportResolver
                     ) -> Optional[str]:
    """A description when ``node`` produces a host value that varies
    across calls (clock, environment, host RNG), else None."""
    if isinstance(node, ast.Call):
        resolved = resolver.resolve(node.func)
        if resolved in HOST_CLOCKS:
            return f"`{resolved}()`"
        if resolved and resolved.startswith("numpy.random."):
            return f"`{resolved}()`"
    dotted = ImportResolver.dotted(node)
    if dotted and (dotted == "os.environ" or
                   dotted.startswith("os.environ.")):
        return "`os.environ`"
    return None


@register
class RetraceHazardChecker(Checker):
    name = "retrace-hazard"
    description = ("jit boundaries that silently recompile per step: "
                   "fresh lambda/partial identity, jit in a loop, "
                   "non-static shape params, host values in shape "
                   "arithmetic")
    needs_engine = True

    def check_program(self, index: ProgramIndex) -> Iterable[Finding]:
        findings: List[Finding] = []
        seen: Set[Tuple[str, int, str]] = set()

        def emit(path: str, node: ast.AST, msg: str) -> None:
            key = (path, node.lineno, msg)
            if key not in seen:
                seen.add(key)
                findings.append(Finding(self.name, path, node.lineno,
                                        node.col_offset, msg))

        boundaries: List[Tuple] = []   # (rec, static names, kind)
        for sf in index.files:
            # tests build throwaway jits (cache probes, identity
            # checks) on purpose — the contract binds the library
            if sf.path.startswith("tests/"):
                continue
            self._scan_file(index, sf, emit, boundaries)
        for rec, static_names, kind in boundaries:
            params = rec.params()
            for i in sorted(index.shaping_params(rec)):
                p = params[i]
                if p in static_names:
                    continue
                emit(rec.sf.path, rec.node,
                     f"{kind} function `{rec.name}` spends parameter "
                     f"`{p}` in a shape-static slot (reshape/arange/"
                     f"scan length) — a traced value there is "
                     f"concretization-error-or-retrace bait; mark it "
                     f"static (and expect a recompile per distinct "
                     f"value) or derive it from aval shapes")
        # host values feeding shape arithmetic, over the trace-reachable
        # closure (trace surfaces + jit boundaries)
        seeds = [rec for rec in index.records.values()
                 if rec.name in TRACE_SURFACES]
        seeds += [rec for rec, _s, _k in boundaries]
        for rec in index.reachable(seeds):
            if isinstance(rec.node, ast.Lambda) or \
                    rec.sf.path.startswith("tests/"):
                continue
            fidx = index.file_index[rec.sf.path]
            if fidx.parent_func.get(id(rec.node)) is not None:
                continue
            resolver = rec.sf.resolver
            for expr, why in index.shaping_use_sites(rec, deep=True):
                for n in ast.walk(expr):
                    desc = _host_value_desc(n, resolver)
                    if desc is not None:
                        emit(rec.sf.path, n,
                             f"host value {desc} feeds shape arithmetic "
                             f"({why} in `{rec.name}`) — shapes derived "
                             f"from host state retrace whenever it "
                             f"drifts; hoist it to a build-time "
                             f"constant")
        return findings

    def _scan_file(self, index: ProgramIndex, sf: SourceFile, emit,
                   boundaries: List[Tuple]) -> None:
        resolver = sf.resolver
        fidx = index.file_index[sf.path]

        def note_boundary(fn_expr, call: Optional[ast.Call],
                          kind: str) -> None:
            if not isinstance(fn_expr, (ast.Name, ast.Attribute)):
                return
            enc = fidx.enclosing.get(id(fn_expr))
            for tgt in index.resolve_call(sf, fn_expr, enc):
                statics = _jit_static_names(call, tgt.params()) \
                    if call is not None else set()
                boundaries.append((tgt, statics, kind))

        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    dres = resolver.resolve(dec)
                    dcall = dec if isinstance(dec, ast.Call) else None
                    if dcall is not None:
                        fres = resolver.resolve(dcall.func)
                        if fres == "jax.jit":
                            dres = "jax.jit"
                        elif fres == "functools.partial" and dcall.args \
                                and resolver.resolve(dcall.args[0]) == \
                                "jax.jit":
                            dres = "jax.jit"
                    if dres == "jax.jit":
                        rec = index.record_for(node)
                        if rec is not None:
                            statics = _jit_static_names(
                                dcall, rec.params()) if dcall else set()
                            boundaries.append((rec, statics,
                                               "jit-decorated"))
                continue
            if not isinstance(node, ast.Call):
                continue
            resolved = resolver.resolve(node.func)
            if resolved == "jax.jit" and node.args:
                a0 = node.args[0]
                if isinstance(a0, ast.Lambda):
                    emit(sf.path, node,
                         "fresh lambda at a jax.jit boundary — jit "
                         "caches by function identity, so every call "
                         "of the enclosing code re-traces; hoist the "
                         "lambda to a def")
                elif isinstance(a0, ast.Call) and resolver.resolve(
                        a0.func) == "functools.partial":
                    emit(sf.path, node,
                         "functools.partial built inline at a jax.jit "
                         "boundary — each partial is a fresh identity, "
                         "defeating jit's cache; bind the partial once "
                         "and jit the bound name")
                else:
                    note_boundary(a0, node, "jitted")
            elif resolved in _SHARD_MAPS and node.args:
                note_boundary(node.args[0], None, "shard-mapped")
        # jax.jit invoked inside a loop body: a new jitted callable (and
        # trace) per iteration
        def visit(node: ast.AST, in_loop: bool) -> None:
            for fname, val in ast.iter_fields(node):
                children = val if isinstance(val, list) else [val]
                for c in children:
                    if not isinstance(c, ast.AST):
                        continue
                    flag = in_loop
                    if isinstance(node, (ast.For, ast.AsyncFor,
                                         ast.While)) and \
                            fname in ("body", "orelse"):
                        flag = True
                    if isinstance(c, (ast.FunctionDef,
                                      ast.AsyncFunctionDef, ast.Lambda)):
                        flag = False   # def bodies run when called
                    if flag and isinstance(c, ast.Call) and \
                            resolver.resolve(c.func) == "jax.jit":
                        emit(sf.path, c,
                             "jax.jit called inside a loop — every "
                             "iteration builds a new jitted callable "
                             "and re-traces; hoist the jit out of the "
                             "loop")
                    visit(c, flag)

        visit(sf.tree, False)


# ---------------------------------------------------------------------------
# dtype flow
# ---------------------------------------------------------------------------

def _low_collective_dtype(call: ast.Call, resolver: ImportResolver
                          ) -> Optional[str]:
    """The statically-resolved low-precision dtype of a collective's
    operand, or None."""
    cname = collective_name(resolver.resolve(call.func))
    if cname is None or not call.args:
        return None
    for n in ast.walk(call.args[0]):
        if isinstance(n, ast.Call) and \
                isinstance(n.func, ast.Attribute) and \
                n.func.attr == "astype" and n.args:
            dt = static_dtype(n.args[0], resolver)
            if dt in LOW_PRECISION_DTYPES:
                return dt
    return None


def _accumulate_desc(node: ast.AST, resolver: ImportResolver
                     ) -> Optional[str]:
    """A description when ``node`` is an accumulate context, else None."""
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        return "`+`"
    if isinstance(node, ast.AugAssign) and isinstance(node.op, ast.Add):
        return "`+=`"
    if isinstance(node, ast.Call):
        resolved = resolver.resolve(node.func)
        if resolved in ("jax.numpy.sum", "jax.numpy.mean",
                        "jax.numpy.add", "jax.numpy.cumsum"):
            return f"`{resolved.rsplit('.', 1)[-1]}`"
    return None


def nonbitexact_registry(sf: SourceFile):
    """``(entries, line, problem)`` for a module's ``NONBITEXACT``
    registry: the literal dict (or {}), the assignment line, and an
    error message when the value is not a pure ``{str: str}`` literal."""
    for node in sf.tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = [node.target]
        if not any(isinstance(t, ast.Name) and t.id == NONBITEXACT_NAME
                   for t in targets):
            continue
        try:
            val = ast.literal_eval(node.value)
        except (ValueError, SyntaxError):
            val = None
        if not isinstance(val, dict) or not all(
                isinstance(k, str) and isinstance(v, str) and v.strip()
                for k, v in val.items()):
            return {}, node.lineno, (
                f"{NONBITEXACT_NAME} must be a pure literal "
                f"{{\"Class.method\": \"reason\"}} dict — computed "
                f"registries cannot be audited statically")
        return val, node.lineno, None
    return {}, 0, None


@register
class DtypeFlowChecker(Checker):
    name = "dtype-flow"
    description = ("bf16/f16 collective results must re-upcast before "
                   "accumulating; wire casts are per-bucket; deliberate "
                   "astype round-trips must be registered in "
                   "NONBITEXACT")

    def applies_to(self, path: str) -> bool:
        # tests mirror wire-rounding chains in their oracles; the
        # contract binds the library
        return not path.startswith("tests/")

    def check_file(self, sf: SourceFile) -> Iterable[Finding]:
        findings: List[Finding] = []
        registry, reg_line, reg_problem = nonbitexact_registry(sf)
        if reg_problem:
            findings.append(Finding(self.name, sf.path, reg_line, 0,
                                    reg_problem))

        # enclosing "Class.method" / "func" site names for registry keys
        site_of: Dict[int, str] = {}

        def map_sites(node: ast.AST, site: Optional[str],
                      cls: Optional[str]) -> None:
            for child in ast.iter_child_nodes(node):
                child_site, child_cls = site, cls
                if isinstance(child, ast.ClassDef):
                    child_cls = child.name
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)):
                    if site is None:
                        child_site = f"{cls}.{child.name}" if cls \
                            else child.name
                site_of[id(child)] = child_site
                map_sites(child, child_site, child_cls)

        map_sites(sf.tree, None, None)

        chain_sites: Set[str] = set()
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "astype" and \
                    isinstance(node.func.value, ast.Call) and \
                    isinstance(node.func.value.func, ast.Attribute) and \
                    node.func.value.func.attr == "astype":
                site = site_of.get(id(node)) or "<module>"
                chain_sites.add(site)
                if site not in registry:
                    findings.append(Finding(
                        self.name, sf.path, node.lineno, node.col_offset,
                        f"non-bit-exact astype round-trip in `{site}` — "
                        f"deliberate wire rounding/reassociation must "
                        f"be registered in this module's "
                        f"{NONBITEXACT_NAME} registry with a one-line "
                        f"reason (docs/design.md §26)"))
        for key in sorted(set(registry) - chain_sites):
            findings.append(Finding(
                self.name, sf.path, reg_line, 0,
                f"stale {NONBITEXACT_NAME} entry '{key}': no astype "
                f"round-trip remains at that site — drop the entry so "
                f"the registry keeps matching reality"))

        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                findings.extend(self._check_function(sf, node))
        return findings

    def _check_function(self, sf: SourceFile, fn: ast.AST):
        resolver = sf.resolver
        parents: Dict[int, ast.AST] = {}
        for sub in body_walk(fn):
            for c in ast.iter_child_nodes(sub):
                parents[id(c)] = sub
        for c in ast.iter_child_nodes(fn):
            parents.setdefault(id(c), fn)

        def accumulate_above(node: ast.AST) -> Optional[Tuple[ast.AST,
                                                              str]]:
            """First accumulate ancestor before an .astype re-wrap."""
            cur = node
            while True:
                p = parents.get(id(cur))
                if p is None:
                    return None
                if isinstance(p, ast.Attribute) and p.attr == "astype":
                    return None        # re-upcast wraps the value
                desc = _accumulate_desc(p, resolver)
                if desc is not None:
                    return p, desc
                cur = p

        low_vars: Dict[str, str] = {}   # name -> wire dtype
        upcast_vars: Set[str] = set()
        findings: List[Finding] = []
        for sub in body_walk(fn):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr == "astype" and \
                    isinstance(sub.func.value, ast.Name):
                upcast_vars.add(sub.func.value.id)
            if not isinstance(sub, ast.Call):
                continue
            dt = _low_collective_dtype(sub, resolver)
            if dt is None:
                continue
            hit = accumulate_above(sub)
            if hit is not None:
                node, desc = hit
                findings.append(Finding(
                    self.name, sf.path, sub.lineno, sub.col_offset,
                    f"{dt} collective result accumulated via {desc} "
                    f"without re-upcasting — low-precision "
                    f"accumulation compounds rounding error; "
                    f"`.astype()` back up immediately after the "
                    f"collective (the strategies.py pattern)"))
                continue
            p = parents.get(id(sub))
            if isinstance(p, ast.Assign):
                for t in p.targets:
                    if isinstance(t, ast.Name):
                        low_vars[t.id] = dt
        if low_vars:
            for sub in body_walk(fn):
                if not (isinstance(sub, ast.Name) and
                        isinstance(sub.ctx, ast.Load) and
                        sub.id in low_vars and
                        sub.id not in upcast_vars):
                    continue
                hit = accumulate_above(sub)
                if hit is not None:
                    node, desc = hit
                    findings.append(Finding(
                        self.name, sf.path, sub.lineno, sub.col_offset,
                        f"{low_vars[sub.id]} collective result "
                        f"`{sub.id}` accumulated via {desc} without "
                        f"re-upcasting — low-precision accumulation "
                        f"compounds rounding error; `.astype()` back "
                        f"up immediately after the collective (the "
                        f"strategies.py pattern)"))

        # §19: the wire cast happens per bucket, not on the packed
        # vector before bucketing
        cast_vars: Dict[str, int] = {}
        for sub in body_walk(fn):
            if isinstance(sub, ast.Assign) and \
                    isinstance(sub.value, ast.Call) and \
                    isinstance(sub.value.func, ast.Attribute) and \
                    sub.value.func.attr == "astype" and \
                    sub.value.args and \
                    static_dtype(sub.value.args[0], resolver) in \
                    LOW_PRECISION_DTYPES:
                for t in sub.targets:
                    if isinstance(t, ast.Name):
                        cast_vars[t.id] = sub.lineno

        def iter_hits(iter_expr, body_nodes):
            if not (isinstance(iter_expr, ast.Name) and
                    iter_expr.id in cast_vars):
                return
            for bn in body_nodes:
                for n in ast.walk(bn):
                    if isinstance(n, ast.Call) and collective_name(
                            resolver.resolve(n.func)) is not None:
                        findings.append(Finding(
                            self.name, sf.path, n.lineno, n.col_offset,
                            f"collective over buckets of "
                            f"`{iter_expr.id}`, which was wire-cast "
                            f"BEFORE bucketing — §19 requires the "
                            f"bf16 cast per bucket so monolithic and "
                            f"bucketed paths stay bit-identical"))
                        return

        for sub in body_walk(fn):
            if isinstance(sub, (ast.For, ast.AsyncFor)):
                iter_hits(sub.iter, sub.body)
            elif isinstance(sub, (ast.ListComp, ast.SetComp,
                                  ast.GeneratorExp)):
                for gen in sub.generators:
                    iter_hits(gen.iter, [sub.elt])
        return findings

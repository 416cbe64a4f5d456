"""tpulint whole-program engine: repo-wide call graph + summaries.

PR-5's checkers were per-file AST walks — the trace-purity closure
stopped at same-file calls, so a host-side ``time.time()`` hidden one
module away from a ``lax.scan`` body was invisible.  This module builds
ONE :class:`ProgramIndex` per lint invocation on top of the existing
one-parse-per-file :class:`~.core.SourceFile` cache and exposes:

* a **call graph** over every function and method in scope — module
  functions resolved through :class:`~.core.ImportResolver` (relative
  imports included), ``self.<m>``/``cls.<m>`` resolved through the class
  hierarchy INCLUDING subclass overrides (the ``exchange_body`` family),
  and ``obj.<m>`` resolved when the method name is owned by exactly one
  class hierarchy in scope (the *unique-family* rule — ``exchange_body``
  qualifies, ``update`` does not) or when ``obj`` was assigned from a
  visible constructor.  Callables passed by keyword or decorator count
  as references (an edge), matching how trace wrappers consume them.
* **transitive reachability** (:meth:`ProgramIndex.reachable`) so a
  checker can close a seed set over the whole repo instead of one file.
* a **per-function summary lattice** (:class:`FuncSummary`, all facts
  monotone unions): reads-host-state, consumes-key (which parameter
  positions a function spends as jax.random keys — directly or by
  passing them into a consuming callee), issues-collective (which
  ``lax`` collectives with which statically-known axis names), donates.
  :meth:`ProgramIndex.transitive_summary` unions a function's summary
  over everything it can reach.
* **thread-role inference** (round 15, docs/design.md §16): every host
  concurrency entry point in scope — ``threading.Thread(target=…)`` /
  ``Timer``, ``run()`` overrides of ``threading.Thread`` subclasses,
  ``signal.signal`` handlers, ``atexit`` hooks, ``socketserver``
  request-handler classes, executor ``submit``/``add_done_callback``
  callables — becomes a :class:`ThreadRole` whose members are the
  functions reachable from its entry, PLUS the implicit ``main`` role
  (everything reachable from non-entry top-level functions/methods).
  Role closures cut the *spawn edges*: referencing ``self._producer``
  at a ``Thread(target=self._producer)`` site hands the function to the
  new thread, it does not call it on the spawning one — so a producer
  body stays out of ``main`` unless something actually calls it there.
  :meth:`ProgramIndex.role_map` is what the host-concurrency checkers
  (shared-state-race, lock-ordering, signal-safety, daemon-discipline)
  consume.

The engine is deliberately STATIC-only (stdlib ``ast``): resolution that
would need type inference returns the empty list rather than guessing —
a checker migrating onto this API keeps per-file behavior on single-file
fixture runs (cross-file targets simply are not in scope) and gains the
interprocedural closure on repo-wide runs.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, FrozenSet, Iterable, List, Optional, Sequence, Set, Tuple

from .core import ImportResolver, SourceFile

_FuncDef = (ast.FunctionDef, ast.AsyncFunctionDef)
_FuncLike = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)

# ---------------------------------------------------------------------------
# shared vocabulary (checkers import these instead of re-declaring)
# ---------------------------------------------------------------------------

HOST_CLOCKS = {"time.time", "time.perf_counter", "time.monotonic",
               "time.process_time", "time.sleep"}
SYNC_CALLS = {"jax.device_get"}

# jax.random.<fn> that CONSUME their key argument (split consumes: two
# splits of one key collide; fold_in derives and is deliberately absent —
# the §8 fused-cadence contract).
KEY_CONSUMERS = {
    "ball", "bernoulli", "beta", "binomial", "bits", "categorical",
    "cauchy", "chisquare", "choice", "dirichlet", "double_sided_maxwell",
    "exponential", "f", "gamma", "generalized_normal", "geometric",
    "gumbel", "laplace", "loggamma", "logistic", "lognormal", "maxwell",
    "multinomial", "multivariate_normal", "normal", "orthogonal",
    "pareto", "permutation", "poisson", "rademacher", "randint",
    "rayleigh", "split", "t", "triangular", "truncated_normal",
    "uniform", "wald", "weibull_min",
}

# named-axis collectives: maps the simple name to the positional index of
# the axis-name argument in the jax.lax signature
COLLECTIVES = {
    "psum": 1, "pmean": 1, "pmax": 1, "pmin": 1,
    "ppermute": 1, "pshuffle": 1, "all_gather": 1,
    "all_gather_invariant": 1, "all_to_all": 1, "psum_scatter": 1,
    "axis_index": 0, "axis_size": 0,
    # async start halves (lax's own, and jax_compat's ppermute shim that
    # pipeline.py's hop goes through — their `_done` twins take a ticket,
    # not an axis, and are covered by the collective-discipline pairing
    # probe instead)
    "psum_start": 1, "all_gather_start": 1, "ppermute_start": 1,
}

_COLLECTIVE_MODULES = ("jax.lax.", "theanompi_tpu.jax_compat.")


def collective_name(resolved: Optional[str]) -> Optional[str]:
    """The simple collective name of a resolved dotted path, or None."""
    if not resolved:
        return None
    for mod in _COLLECTIVE_MODULES:
        if resolved.startswith(mod):
            simple = resolved[len(mod):]
            if simple in COLLECTIVES:
                return simple
    return None


# ---------------------------------------------------------------------------
# compile-surface vocabulary (docs/design.md §26)
# ---------------------------------------------------------------------------

#: Trace-shaping consumer slots that must be STATIC at trace time — a
#: host value landing here changes the traced program's shape (scan
#: lengths, schedule tables, iota/zeros shapes, PartitionSpecs, jit
#: donation/static signatures).  ``"all"`` marks every argument;
#: otherwise a tuple of positional indices and keyword names.
TRACE_SHAPE_SLOTS = {
    "jax.lax.scan": ("length",),
    "jax.numpy.arange": "all",
    "jax.numpy.zeros": "all",
    "jax.numpy.ones": "all",
    "jax.numpy.full": (0, "shape"),
    "jax.numpy.eye": "all",
    "jax.numpy.reshape": (1, "newshape", "shape"),
    "numpy.arange": "all",
    "numpy.zeros": "all",
    "numpy.ones": "all",
    "numpy.full": (0, "shape"),
    "jax.sharding.PartitionSpec": "all",
    "theanompi_tpu.jax_compat.P": "all",
    # repo-local schedule/plan builders: their scalar arguments bake
    # host-side tables into the traced program (docs/design.md §26)
    "theanompi_tpu.parallel.pipeline.build_schedule": "all",
    "theanompi_tpu.parallel.update_sharding.plan_tree": "all",
}

#: Method names whose arguments are shape slots on any receiver.
TRACE_SHAPE_METHODS = {"reshape", "broadcast_to"}

#: ``jax.jit`` keywords whose values shape the compiled signature.
TRACE_JIT_KWARGS = {"static_argnums", "static_argnames",
                    "donate_argnums", "donate_argnames"}

#: Attribute reads that are aval-static on a tracer — ``x.shape[0]`` in
#: a reshape is shape arithmetic over the ALREADY-compiled signature,
#: not a host value, so their bases never count as shaping uses.
AVAL_ATTRS = {"shape", "ndim", "size", "dtype"}

#: Dtypes the dtype-flow pass treats as low-precision wire formats.
LOW_PRECISION_DTYPES = {"bfloat16", "float16", "float8_e4m3fn",
                        "float8_e5m2"}

_DTYPE_MODULES = ("jax.numpy.", "numpy.", "jax.dtypes.")


def shaping_slot_exprs(call: ast.Call, resolver: ImportResolver):
    """``(expr, slot description)`` for every argument of ``call``
    occupying a trace-shaping slot."""
    resolved = resolver.resolve(call.func)
    out = []

    def take(slots, label):
        if slots == "all":
            for a in call.args:
                out.append((a, label))
            for kw in call.keywords:
                if kw.arg is not None:
                    out.append((kw.value, label))
            return
        for s in slots:
            if isinstance(s, int):
                if s < len(call.args):
                    out.append((call.args[s], label))
            else:
                for kw in call.keywords:
                    if kw.arg == s:
                        out.append((kw.value, label))

    if resolved in TRACE_SHAPE_SLOTS:
        take(TRACE_SHAPE_SLOTS[resolved], f"`{resolved.rsplit('.', 1)[-1]}`")
    elif resolved == "jax.jit":
        for kw in call.keywords:
            if kw.arg in TRACE_JIT_KWARGS:
                out.append((kw.value, f"`jax.jit({kw.arg}=…)`"))
    elif resolved is None and isinstance(call.func, ast.Attribute) and \
            call.func.attr in TRACE_SHAPE_METHODS:
        for a in call.args:
            out.append((a, f"`.{call.func.attr}()`"))
    return out


def bare_names(expr: ast.AST) -> List[ast.Name]:
    """Name loads in ``expr``, excluding bases of aval-attribute chains
    (``x.shape[0]`` is static per-aval, not a host value)."""
    out: List[ast.Name] = []

    def visit(n: ast.AST) -> None:
        if isinstance(n, ast.Attribute) and n.attr in AVAL_ATTRS:
            return
        if isinstance(n, ast.Name):
            out.append(n)
        for c in ast.iter_child_nodes(n):
            visit(c)

    visit(expr)
    return out


def static_dtype(node: ast.AST, resolver: ImportResolver
                 ) -> Optional[str]:
    """The simple dtype name of a statically-resolved dtype expression
    (``jnp.bfloat16``, ``np.float16``, ``"bfloat16"``), else None —
    dynamic wire dtypes (``self.wire_dtype``) resolve to nothing and are
    deliberately not guessed."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    resolved = resolver.resolve(node)
    if resolved:
        for mod in _DTYPE_MODULES:
            if resolved.startswith(mod):
                return resolved[len(mod):]
    return None


# ---------------------------------------------------------------------------
# records and summaries
# ---------------------------------------------------------------------------

@dataclass
class FuncRecord:
    """One function/method definition anywhere in scope."""

    sf: SourceFile
    node: ast.AST                      # FunctionDef/AsyncFunctionDef/Lambda
    qualname: str                      # module.Class.method / module.func
    class_name: Optional[str] = None   # simple name of the enclosing class
    class_key: Optional[Tuple[str, str]] = None   # (module, ClassName)

    @property
    def name(self) -> str:
        return getattr(self.node, "name", "<lambda>")

    def params(self) -> List[str]:
        a = self.node.args
        out = [p.arg for p in list(a.posonlyargs) + list(a.args)]
        return [p for p in out if p not in ("self", "cls")]


@dataclass
class FuncSummary:
    """Direct (non-transitive) facts about one function body.  Every
    field is a monotone set/flag so transitive summaries are unions."""

    host_calls: List[Tuple[ast.AST, str]] = field(default_factory=list)
    key_params: Set[int] = field(default_factory=set)
    collectives: List[Tuple[ast.AST, str, Tuple]] = field(
        default_factory=list)        # (call node, name, axis values or ())
    donates: bool = False

    @property
    def reads_host_state(self) -> bool:
        return bool(self.host_calls)

    @property
    def consumes_key(self) -> bool:
        return bool(self.key_params)

    @property
    def issues_collective(self) -> bool:
        return bool(self.collectives)


@dataclass
class TransitiveSummary:
    reads_host_state: bool = False
    consumes_key: bool = False
    issues_collective: bool = False
    donates: bool = False
    collective_names: FrozenSet[str] = frozenset()


# ---------------------------------------------------------------------------
# thread roles (host-concurrency pass, docs/design.md §16)
# ---------------------------------------------------------------------------

#: The implicit role every function belongs to unless it is ONLY reachable
#: through a spawn edge (a ``Thread(target=…)`` reference, a signal
#: handler registration, …).
MAIN_ROLE = "main"

# spawn-construct vocabulary: resolved callable -> (kind, how to find the
# entry expression).  ``signal.signal(sig, h)``'s handler is positional 1;
# ``atexit.register(f)``'s is positional 0; Thread/Timer take keyword
# ``target``/``function`` (or the documented positional slot).
_SPAWN_CTORS = {
    "threading.Thread": ("thread", 1, ("target",)),
    "threading.Timer": ("timer", 1, ("function",)),
}
_SPAWN_REGISTRARS = {
    "signal.signal": ("signal", 1, ("handler",)),
    "atexit.register": ("atexit", 0, ()),
}
# receiver methods that hand a callable to another thread
_SPAWN_METHODS = {"submit": ("executor", 0),
                  "add_done_callback": ("executor", 0)}
_NON_HANDLERS = {"signal.SIG_DFL", "signal.SIG_IGN", "signal.default_int_handler"}

#: Method names so common on stdlib objects (threads, locks, sockets,
#: files, processes) that the unique-family fallback must not claim them
#: during ROLE closure: `t.join()` on a Thread resolving to the one
#: in-scope class that happens to define `join` would teleport that
#: class's methods into the spawning role.  Precise resolution paths
#: (self., ctor-typed receivers, imports) are unaffected.
GENERIC_METHOD_NAMES = {
    "join", "start", "stop", "run", "close", "wait", "get", "put",
    "set", "clear", "pop", "read", "write", "flush", "send", "recv",
    "sendall", "accept", "connect", "acquire", "release", "poll",
    "kill", "terminate", "shutdown", "submit", "result", "cancel",
    "items", "keys", "values", "update", "copy", "append", "add",
    "remove", "beat",
}

#: Base classes whose subclasses' ``run`` (Thread) / ``handle``
#: (socketserver) methods execute on their own thread.
THREAD_BASES = ("threading.Thread", "threading.Timer")
HANDLER_BASES = ("socketserver.BaseRequestHandler",
                 "socketserver.StreamRequestHandler",
                 "socketserver.DatagramRequestHandler")


@dataclass
class SpawnSite:
    """One place a concurrency entry point is introduced: a
    ``Thread``/``Timer`` construction, a handler registration, a
    thread-subclass / request-handler class definition."""

    sf: SourceFile
    node: ast.AST                 # the Call or ClassDef
    kind: str                     # thread|timer|signal|atexit|executor|
    #                               thread-subclass|handler
    target_desc: str              # source text of the entry expression
    entries: List[FuncRecord] = field(default_factory=list)

    @property
    def path(self) -> str:
        return self.sf.path

    @property
    def line(self) -> int:
        return getattr(self.node, "lineno", 1)


@dataclass
class ThreadRole:
    """One inferred thread role: a name, its kind, every spawn site that
    introduces it, and its entry records (members come from
    :meth:`ProgramIndex.role_members`)."""

    name: str
    kind: str
    sites: List[SpawnSite] = field(default_factory=list)
    entries: List[FuncRecord] = field(default_factory=list)


# ---------------------------------------------------------------------------
# per-file scope index
# ---------------------------------------------------------------------------

class FileIndex:
    """Scoping structure of one file: defs by enclosing function scope,
    methods by class, classes with their (resolved) base names, and the
    enclosing function of every node."""

    def __init__(self, sf: SourceFile):
        self.sf = sf
        # id(scope func node or None) -> {name: [def nodes]}
        self.by_scope: Dict[Optional[int], Dict[str, List[ast.AST]]] = {}
        # method simple name -> [def nodes] across every class in the file
        self.methods: Dict[str, List[ast.AST]] = {}
        # def-node id -> enclosing function node
        self.parent_func: Dict[int, Optional[ast.AST]] = {}
        # any-node id -> enclosing function node (call-site scope lookup)
        self.enclosing: Dict[int, Optional[ast.AST]] = {}
        # ClassDef nodes by simple name; def-node id -> owning ClassDef
        self.classes: Dict[str, ast.ClassDef] = {}
        self.class_of: Dict[int, ast.ClassDef] = {}
        self._walk(sf.tree, None, None)
        self._record_enclosing(sf.tree, None)

    def _walk(self, node, func, cls) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FuncDef):
                scope = self.by_scope.setdefault(
                    id(func) if func else None, {})
                scope.setdefault(child.name, []).append(child)
                if cls is not None and isinstance(node, ast.ClassDef):
                    self.methods.setdefault(child.name, []).append(child)
                    self.class_of[id(child)] = cls
                self.parent_func[id(child)] = func
                self._walk(child, child, None)
            elif isinstance(child, ast.ClassDef):
                self.classes.setdefault(child.name, child)
                self._walk(child, func, child)
            elif isinstance(child, ast.Lambda):
                self.parent_func[id(child)] = func
                self._walk(child, child, None)
            else:
                self._walk(child, func, cls)

    def _record_enclosing(self, node, func) -> None:
        self.enclosing[id(node)] = func
        for child in ast.iter_child_nodes(node):
            self._record_enclosing(
                child, child if isinstance(child, _FuncLike) else func)

    def lookup(self, name: str, from_func: Optional[ast.AST]
               ) -> List[ast.AST]:
        """Defs named ``name`` visible from ``from_func``: its locals,
        then enclosing functions', then module level."""
        f = from_func
        while True:
            scope = self.by_scope.get(id(f) if f else None, {})
            if name in scope:
                return list(scope[name])
            if f is None:
                return []
            f = self.parent_func.get(id(f))


# ---------------------------------------------------------------------------
# the whole-program index
# ---------------------------------------------------------------------------

class ProgramIndex:
    """Repo-wide call graph + summaries over a list of parsed files."""

    def __init__(self, files: Sequence[SourceFile]):
        self.files = list(files)
        self.by_path: Dict[str, SourceFile] = {sf.path: sf for sf in files}
        self.file_index: Dict[str, FileIndex] = {
            sf.path: FileIndex(sf) for sf in files}
        # absolute dotted name -> [FuncRecord] (module funcs AND methods
        # under module.Class.method)
        self.by_qualname: Dict[str, List[FuncRecord]] = {}
        # method simple name -> [FuncRecord] repo-wide
        self.methods: Dict[str, List[FuncRecord]] = {}
        self.records: Dict[int, FuncRecord] = {}      # id(node) -> record
        # (module, ClassName) -> [absolute dotted base names]
        self.class_bases: Dict[Tuple[str, str], List[str]] = {}
        # absolute dotted class name -> (module, ClassName)
        self._class_keys: Dict[str, Tuple[str, str]] = {}
        self._module_constants: Dict[str, object] = {}
        for sf in files:
            self._index_file(sf)
        self._subclasses = self._compute_subclasses()
        self._callees_cache: Dict[int, List[FuncRecord]] = {}
        self._summary_cache: Dict[int, FuncSummary] = {}
        self._key_params_cache: Optional[Dict[int, Set[int]]] = None
        self._shaping_params_cache: Optional[Dict[int, Set[int]]] = None
        self._transitive_cache: Dict[int, TransitiveSummary] = {}

    # -- construction ------------------------------------------------------

    def _index_file(self, sf: SourceFile) -> None:
        module = sf.resolver.module
        idx = self.file_index[sf.path]
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Lambda):
                # unnamed, unresolvable by name — indexed so a lambda
                # seed (a scan body) still closes over its callees
                rec = FuncRecord(sf, node,
                                 f"{module}.<lambda>:{node.lineno}")
                self.records[id(node)] = rec
                continue
            if not isinstance(node, _FuncDef):
                continue
            cls = idx.class_of.get(id(node))
            if cls is not None:
                qual = f"{module}.{cls.name}.{node.name}"
                rec = FuncRecord(sf, node, qual, cls.name,
                                 (module, cls.name))
                self.methods.setdefault(node.name, []).append(rec)
            elif idx.parent_func.get(id(node)) is None:
                qual = f"{module}.{node.name}"
                rec = FuncRecord(sf, node, qual)
            else:
                qual = f"{module}.<locals>.{node.name}"
                rec = FuncRecord(sf, node, qual)
            self.records[id(node)] = rec
            self.by_qualname.setdefault(rec.qualname, []).append(rec)
        for name, cls in idx.classes.items():
            key = (module, name)
            self._class_keys[f"{module}.{name}"] = key
            bases = []
            for b in cls.bases:
                resolved = sf.resolver.resolve(b)
                if resolved is None and isinstance(b, ast.Name):
                    # same-file base class
                    if b.id in idx.classes:
                        resolved = f"{module}.{b.id}"
                if resolved:
                    bases.append(resolved)
            self.class_bases[key] = bases
        # module-level constants: strings (mesh axis names and the like)
        # and tuples of constants (event vocabularies — MEMBERSHIP_EVENTS,
        # STATUSZ_OPS, RULE_ACTIONS — the protocol checkers read these
        # statically).  Consumers filter by type, so adding tuples here
        # cannot change the axis-name evaluation (isinstance(v, str)).
        for st in sf.tree.body:
            if not isinstance(st, ast.Assign):
                continue
            value = None
            if isinstance(st.value, ast.Constant):
                value = st.value.value
            elif isinstance(st.value, (ast.Tuple, ast.List)) and \
                    all(isinstance(e, ast.Constant) for e in st.value.elts):
                value = tuple(e.value for e in st.value.elts)
            if value is None:
                continue
            for t in st.targets:
                if isinstance(t, ast.Name):
                    self._module_constants[f"{module}.{t.id}"] = value

    def _compute_subclasses(self) -> Dict[Tuple[str, str],
                                          Set[Tuple[str, str]]]:
        """Transitive subclass sets, keyed by (module, ClassName)."""
        direct: Dict[Tuple[str, str], Set[Tuple[str, str]]] = {}
        for key, bases in self.class_bases.items():
            for b in bases:
                bkey = self._class_keys.get(b)
                if bkey is not None:
                    direct.setdefault(bkey, set()).add(key)
        out: Dict[Tuple[str, str], Set[Tuple[str, str]]] = {}

        def close(key):
            if key in out:
                return out[key]
            out[key] = set()
            for sub in direct.get(key, ()):
                out[key].add(sub)
            # iterate to fixpoint below instead of recursing (cycles)
            return out[key]

        for key in list(self.class_bases):
            close(key)
        changed = True
        while changed:
            changed = False
            for key, subs in out.items():
                grown = set(subs)
                for s in subs:
                    grown |= out.get(s, set())
                if grown != subs:
                    out[key] = grown
                    changed = True
        return out

    # -- class hierarchy queries ------------------------------------------

    def module_constant(self, dotted: str):
        """The literal value of a module-level constant, or None."""
        return self._module_constants.get(dotted)

    def subclasses_of(self, dotted_class: str) -> List[Tuple[str, str]]:
        key = self._class_keys.get(dotted_class)
        if key is None:
            return []
        return sorted(self._subclasses.get(key, set()) | {key})

    def hierarchy_root(self, key: Tuple[str, str]) -> Tuple[str, str]:
        """Topmost in-scope ancestor of a class (first-base chain)."""
        seen = set()
        while key not in seen:
            seen.add(key)
            bases = self.class_bases.get(key, [])
            parent = None
            for b in bases:
                bkey = self._class_keys.get(b)
                if bkey is not None:
                    parent = bkey
                    break
            if parent is None:
                return key
            key = parent
        return key

    def method_records(self, class_key: Tuple[str, str], name: str,
                       include_subclasses: bool = True) -> List[FuncRecord]:
        """Records for ``name`` defined on the class, its in-scope
        ancestors, and (optionally) every subclass override."""
        keys = {class_key}
        # ancestors (first-base chains, all bases)
        frontier = [class_key]
        while frontier:
            k = frontier.pop()
            for b in self.class_bases.get(k, []):
                bk = self._class_keys.get(b)
                if bk is not None and bk not in keys:
                    keys.add(bk)
                    frontier.append(bk)
        if include_subclasses:
            keys |= self._subclasses.get(class_key, set())
            # overrides live on subclasses of ANCESTORS too (siblings are
            # deliberately excluded: a sibling's override is unreachable
            # through this receiver)
        out = []
        for k in keys:
            out.extend(self.by_qualname.get(f"{k[0]}.{k[1]}.{name}", []))
        return out

    # -- call resolution ---------------------------------------------------

    def _unique_family(self, name: str) -> List[FuncRecord]:
        """All methods named ``name`` when they belong to ONE class
        hierarchy (same root) — the ``exchange_body`` rule.  Ambiguous
        names (``update``, ``init``) resolve to nothing."""
        recs = self.methods.get(name, [])
        if not recs:
            return []
        roots = {self.hierarchy_root(r.class_key) for r in recs
                 if r.class_key is not None}
        if len(roots) != 1:
            return []
        return list(recs)

    def _local_ctor_types(self, rec: FuncRecord) -> Dict[str, Tuple[str,
                                                                    str]]:
        """Names assigned from a visible constructor call in this
        function's body: ``exch = BSP_Exchanger(cfg)`` -> class key."""
        out: Dict[str, Tuple[str, str]] = {}
        for sub in body_walk(rec.node):
            if not isinstance(sub, ast.Assign) or \
                    not isinstance(sub.value, ast.Call):
                continue
            fn = sub.value.func
            cls_key = None
            if isinstance(fn, ast.Name):
                idx = self.file_index[rec.sf.path]
                if fn.id in idx.classes:
                    cls_key = (rec.sf.resolver.module, fn.id)
                else:
                    resolved = rec.sf.resolver.resolve(fn)
                    cls_key = self._class_keys.get(resolved or "")
            else:
                resolved = rec.sf.resolver.resolve(fn)
                cls_key = self._class_keys.get(resolved or "")
            if cls_key is None:
                continue
            for t in sub.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = cls_key
        return out

    def resolve_call(self, sf: SourceFile, func_expr: ast.AST,
                     enclosing: Optional[ast.AST],
                     ctor_types: Optional[Dict[str, Tuple[str, str]]] = None,
                     skip_generic_unique: bool = False
                     ) -> List[FuncRecord]:
        """Possible targets of a call through ``func_expr``, or [].
        ``skip_generic_unique`` (role closures) withholds the
        unique-family fallback for :data:`GENERIC_METHOD_NAMES`."""
        idx = self.file_index[sf.path]
        if isinstance(func_expr, ast.Name):
            local = idx.lookup(func_expr.id, enclosing)
            if local:
                return [self.records[id(n)] for n in local
                        if id(n) in self.records]
            resolved = sf.resolver.resolve(func_expr)
            if resolved:
                return list(self.by_qualname.get(resolved, []))
            return []
        if isinstance(func_expr, ast.Attribute):
            base = func_expr.value
            # self.m / cls.m: the enclosing class hierarchy + overrides
            if isinstance(base, ast.Name) and base.id in ("self", "cls"):
                cls = None
                f = enclosing
                while f is not None:
                    cls = idx.class_of.get(id(f))
                    if cls is not None:
                        break
                    f = idx.parent_func.get(id(f))
                if cls is not None:
                    recs = self.method_records(
                        (sf.resolver.module, cls.name), func_expr.attr)
                    if recs:
                        return recs
                # fixtures sometimes call self.m outside an indexed class;
                # fall back to same-file methods by name
                return [self.records[id(n)]
                        for n in idx.methods.get(func_expr.attr, [])
                        if id(n) in self.records]
            # module.func through the import resolver
            resolved = sf.resolver.resolve(func_expr)
            if resolved and resolved in self.by_qualname:
                return list(self.by_qualname[resolved])
            # receiver with a locally-visible constructor type
            if isinstance(base, ast.Name) and ctor_types and \
                    base.id in ctor_types:
                return self.method_records(ctor_types[base.id],
                                           func_expr.attr)
            # self.<attr>.<m>() where the attr was assigned from a
            # visible constructor anywhere in the enclosing class
            if isinstance(base, ast.Attribute) and \
                    isinstance(base.value, ast.Name) and \
                    base.value.id in ("self", "cls"):
                cls = None
                f = enclosing
                while f is not None:
                    cls = idx.class_of.get(id(f))
                    if cls is not None:
                        break
                    f = idx.parent_func.get(id(f))
                if cls is not None:
                    ctor = self.class_attr_ctors(
                        (sf.resolver.module, cls.name)).get(base.attr)
                    key = self._class_keys.get(ctor or "")
                    if key is not None:
                        return self.method_records(key, func_expr.attr)
            # unique-family method name (the exchange_body rule)
            if skip_generic_unique and \
                    func_expr.attr in GENERIC_METHOD_NAMES:
                return []
            return self._unique_family(func_expr.attr)
        return []

    def callees(self, rec: FuncRecord) -> List[FuncRecord]:
        """Direct call/reference targets of one function body (not
        descending into nested defs — they are reachable when called,
        and local calls resolve through the scope chain)."""
        cached = self._callees_cache.get(id(rec.node))
        if cached is not None:
            return cached
        idx = self.file_index[rec.sf.path]
        ctor_types = self._local_ctor_types(rec)
        out: List[FuncRecord] = []
        seen: Set[int] = set()

        def add(targets: Iterable[FuncRecord]) -> None:
            for t in targets:
                if id(t.node) not in seen and t.node is not rec.node:
                    seen.add(id(t.node))
                    out.append(t)

        for sub in body_walk(rec.node):
            if isinstance(sub, ast.Call):
                enc = idx.enclosing.get(id(sub.func), rec.node)
                add(self.resolve_call(rec.sf, sub.func, enc, ctor_types))
                # callables passed as arguments are references too
                for arg in list(sub.args) + [kw.value for kw in
                                             sub.keywords]:
                    if isinstance(arg, (ast.Name, ast.Attribute)):
                        enc = idx.enclosing.get(id(arg), rec.node)
                        add(self.resolve_call(rec.sf, arg, enc,
                                              ctor_types))
        self._callees_cache[id(rec.node)] = out
        return out

    def reachable(self, seeds: Iterable[FuncRecord]) -> List[FuncRecord]:
        """Transitive closure of :meth:`callees` over the seed set
        (seeds included)."""
        out: List[FuncRecord] = []
        seen: Set[int] = set()
        frontier = list(seeds)
        while frontier:
            rec = frontier.pop()
            if id(rec.node) in seen:
                continue
            seen.add(id(rec.node))
            out.append(rec)
            frontier.extend(self.callees(rec))
        return out

    def record_for(self, node: ast.AST) -> Optional[FuncRecord]:
        return self.records.get(id(node))

    # -- summaries ---------------------------------------------------------

    def summary(self, rec: FuncRecord) -> FuncSummary:
        """Direct facts about one function body (cached)."""
        cached = self._summary_cache.get(id(rec.node))
        if cached is not None:
            return cached
        s = FuncSummary()
        resolver = rec.sf.resolver
        params = [p for p in rec.params()]
        for sub in body_walk(rec.node):
            if not isinstance(sub, ast.Call):
                continue
            resolved = resolver.resolve(sub.func)
            if resolved in HOST_CLOCKS:
                s.host_calls.append((sub, f"host clock `{resolved}()`"))
            elif resolved and resolved.startswith("numpy.random."):
                s.host_calls.append((sub, f"host RNG `{resolved}()`"))
            elif resolved in SYNC_CALLS:
                s.host_calls.append((sub, f"`{resolved}()`"))
            cname = collective_name(resolved)
            if cname is not None:
                s.collectives.append(
                    (sub, cname, axis_values(sub, cname, resolver, self)))
            if resolved == "jax.jit" and any(
                    kw.arg in ("donate_argnums", "donate_argnames")
                    for kw in sub.keywords):
                s.donates = True
            # direct key consumption of a parameter
            kn = consumed_key_name(sub, resolver)
            if kn is not None and kn in params:
                s.key_params.add(params.index(kn))
        self._summary_cache[id(rec.node)] = s
        return s

    def key_params(self, rec: FuncRecord) -> Set[int]:
        """Parameter positions this function consumes as jax.random keys
        — directly, or by passing them to a consuming callee (fixpoint
        across the whole graph)."""
        if self._key_params_cache is None:
            self._key_params_cache = self._compute_key_params()
        return self._key_params_cache.get(id(rec.node), set())

    def _compute_key_params(self) -> Dict[int, Set[int]]:
        out: Dict[int, Set[int]] = {}
        for rec in self.records.values():
            direct = self.summary(rec).key_params
            if direct:
                out[id(rec.node)] = set(direct)
        changed = True
        while changed:
            changed = False
            for rec in self.records.values():
                params = rec.params()
                idx = self.file_index[rec.sf.path]
                ctor_types = None
                for sub in body_walk(rec.node):
                    if not isinstance(sub, ast.Call):
                        continue
                    enc = idx.enclosing.get(id(sub.func), rec.node)
                    if ctor_types is None:
                        ctor_types = self._local_ctor_types(rec)
                    for tgt in self.resolve_call(rec.sf, sub.func, enc,
                                                 ctor_types):
                        tgt_kp = out.get(id(tgt.node))
                        if not tgt_kp:
                            continue
                        tparams = tgt.params()
                        for i in tgt_kp:
                            arg = None
                            if i < len(sub.args):
                                arg = sub.args[i]
                            for kw in sub.keywords:
                                if i < len(tparams) and \
                                        kw.arg == tparams[i]:
                                    arg = kw.value
                            if isinstance(arg, ast.Name) and \
                                    arg.id in params:
                                j = params.index(arg.id)
                                cur = out.setdefault(id(rec.node), set())
                                if j not in cur:
                                    cur.add(j)
                                    changed = True
        return out

    def shaping_params(self, rec: FuncRecord) -> Set[int]:
        """Parameter positions this function spends in trace-shaping
        slots — directly, or by passing them into a callee that does
        (fixpoint, like :meth:`key_params`)."""
        if self._shaping_params_cache is None:
            self._shaping_params_cache = self._compute_shaping_params()
        return self._shaping_params_cache.get(id(rec.node), set())

    def _compute_shaping_params(self) -> Dict[int, Set[int]]:
        out: Dict[int, Set[int]] = {}
        for rec in self.records.values():
            params = rec.params()
            if not params:
                continue
            direct: Set[int] = set()
            for sub in body_walk(rec.node):
                if not isinstance(sub, ast.Call):
                    continue
                for expr, _why in shaping_slot_exprs(sub, rec.sf.resolver):
                    for nm in bare_names(expr):
                        if nm.id in params:
                            direct.add(params.index(nm.id))
            if direct:
                out[id(rec.node)] = direct
        changed = True
        while changed:
            changed = False
            for rec in self.records.values():
                params = rec.params()
                if not params:
                    continue
                idx = self.file_index[rec.sf.path]
                ctor_types = None
                for sub in body_walk(rec.node):
                    if not isinstance(sub, ast.Call):
                        continue
                    enc = idx.enclosing.get(id(sub.func), rec.node)
                    if ctor_types is None:
                        ctor_types = self._local_ctor_types(rec)
                    for tgt in self.resolve_call(rec.sf, sub.func, enc,
                                                 ctor_types):
                        tgt_sp = out.get(id(tgt.node))
                        if not tgt_sp:
                            continue
                        tparams = tgt.params()
                        for i in tgt_sp:
                            arg = sub.args[i] if i < len(sub.args) else None
                            for kw in sub.keywords:
                                if i < len(tparams) and \
                                        kw.arg == tparams[i]:
                                    arg = kw.value
                            if arg is None:
                                continue
                            for nm in bare_names(arg):
                                if nm.id in params:
                                    j = params.index(nm.id)
                                    cur = out.setdefault(id(rec.node),
                                                         set())
                                    if j not in cur:
                                        cur.add(j)
                                        changed = True
        return out

    def shaping_use_sites(self, rec: FuncRecord, deep: bool = False):
        """``(expr, why)`` for every expression in ``rec``'s body that
        occupies a trace-shaping slot — the direct consumer slots plus
        arguments feeding a callee parameter the callee spends in one.
        ``deep=True`` walks nested defs too (closure-variable flows:
        a knob read at build level consumed inside the traced inner
        function)."""
        idx = self.file_index[rec.sf.path]
        resolver = rec.sf.resolver
        ctor_types = None
        out = []
        walk = ast.walk(rec.node) if deep else body_walk(rec.node)
        for sub in walk:
            if not isinstance(sub, ast.Call):
                continue
            out.extend(shaping_slot_exprs(sub, resolver))
            enc = idx.enclosing.get(id(sub.func), rec.node)
            if ctor_types is None:
                ctor_types = self._local_ctor_types(rec)
            for tgt in self.resolve_call(rec.sf, sub.func, enc,
                                         ctor_types):
                sp = self.shaping_params(tgt)
                if not sp:
                    continue
                tparams = tgt.params()
                for i in sp:
                    arg = sub.args[i] if i < len(sub.args) else None
                    for kw in sub.keywords:
                        if i < len(tparams) and kw.arg == tparams[i]:
                            arg = kw.value
                    if arg is not None:
                        out.append(
                            (arg, f"`{tgt.name}({tparams[i]}=…)`"))
        return out

    def transitive_summary(self, rec: FuncRecord) -> TransitiveSummary:
        """Union of :meth:`summary` over everything reachable from
        ``rec`` (cached)."""
        cached = self._transitive_cache.get(id(rec.node))
        if cached is not None:
            return cached
        t = TransitiveSummary()
        names: Set[str] = set()
        for r in self.reachable([rec]):
            s = self.summary(r)
            t.reads_host_state = t.reads_host_state or s.reads_host_state
            t.consumes_key = t.consumes_key or s.consumes_key
            t.issues_collective = t.issues_collective or \
                s.issues_collective
            t.donates = t.donates or s.donates
            names.update(n for _, n, _ in s.collectives)
        t.collective_names = frozenset(names)
        self._transitive_cache[id(rec.node)] = t
        return t

    # -- thread roles (host-concurrency pass) -------------------------------

    def resolve_callable(self, sf: SourceFile, expr: ast.AST,
                         enclosing: Optional[ast.AST],
                         ctor_types=None,
                         _seen_names: Optional[Set[str]] = None
                         ) -> List[FuncRecord]:
        """Targets of a callable-valued expression — :meth:`resolve_call`
        plus the spawn-site idioms: an inline ``lambda``, and a local
        Name bound from an assignment or a ``for``-loop over a literal
        tuple of method references (the ChaosProxy pump-pair shape).
        ``_seen_names`` guards cyclic local rebinds (``fn = fn``,
        ``a = b; b = a``) — a cycle degrades to unresolved instead of
        recursing unboundedly."""
        if isinstance(expr, ast.Lambda):
            rec = self.records.get(id(expr))
            return [rec] if rec is not None else []
        out = self.resolve_call(sf, expr, enclosing, ctor_types)
        if out or not isinstance(expr, ast.Name) or enclosing is None:
            return out
        seen_names = set(_seen_names or ())
        if expr.id in seen_names:
            return []
        seen_names.add(expr.id)
        found: List[FuncRecord] = []
        for sub in body_walk(enclosing):
            exprs: List[ast.AST] = []
            if isinstance(sub, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == expr.id
                    for t in sub.targets):
                exprs = [sub.value]
            elif isinstance(sub, ast.For) and \
                    isinstance(sub.target, ast.Name) and \
                    sub.target.id == expr.id and \
                    isinstance(sub.iter, (ast.Tuple, ast.List)):
                exprs = list(sub.iter.elts)
            for e in exprs:
                if isinstance(e, (ast.Tuple, ast.List)):
                    exprs.extend(e.elts)
                    continue
                found.extend(self.resolve_callable(sf, e, enclosing,
                                                   ctor_types,
                                                   _seen_names=seen_names))
        seen: Set[int] = set()
        return [r for r in found
                if id(r.node) not in seen and not seen.add(id(r.node))]

    def _spawn_entry_expr(self, call: ast.Call, pos: int,
                          kwnames) -> Optional[ast.AST]:
        expr = call.args[pos] if len(call.args) > pos else None
        for kw in call.keywords:
            if kw.arg in kwnames:
                expr = kw.value
        return expr

    def is_thread_subclass(self, class_key: Tuple[str, str]) -> bool:
        return self._inherits(class_key, THREAD_BASES)

    def _inherits(self, class_key, dotted_bases) -> bool:
        seen = set()
        frontier = [class_key]
        while frontier:
            k = frontier.pop()
            if k in seen:
                continue
            seen.add(k)
            for b in self.class_bases.get(k, []):
                if b in dotted_bases:
                    return True
                bk = self._class_keys.get(b)
                if bk is not None:
                    frontier.append(bk)
        return False

    def spawn_sites(self) -> List[SpawnSite]:
        """Every concurrency entry point in scope (cached)."""
        if getattr(self, "_spawn_sites", None) is not None:
            return self._spawn_sites
        sites: List[SpawnSite] = []
        arg_ids: Set[int] = set()     # entry-expr node ids (spawn edges)
        for sf in self.files:
            idx = self.file_index[sf.path]
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.ClassDef):
                    key = (sf.resolver.module, node.name)
                    if self.is_thread_subclass(key):
                        recs = self.method_records(key, "run",
                                                   include_subclasses=False)
                        recs = [r for r in recs if r.class_key == key]
                        if recs:
                            sites.append(SpawnSite(
                                sf, node, "thread-subclass",
                                f"{node.name}.run", recs))
                    elif self._inherits(key, HANDLER_BASES):
                        recs = [r for n in ("handle", "setup", "finish")
                                for r in self.method_records(
                                    key, n, include_subclasses=False)
                                if r.class_key == key]
                        if recs:
                            sites.append(SpawnSite(
                                sf, node, "handler",
                                f"{node.name}.handle", recs))
                    continue
                if not isinstance(node, ast.Call):
                    continue
                resolved = sf.resolver.resolve(node.func)
                kind = pos = kwnames = None
                if resolved in _SPAWN_CTORS:
                    kind, pos, kwnames = _SPAWN_CTORS[resolved]
                elif resolved in _SPAWN_REGISTRARS:
                    kind, pos, kwnames = _SPAWN_REGISTRARS[resolved]
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr in _SPAWN_METHODS:
                    kind, pos = _SPAWN_METHODS[node.func.attr]
                    kwnames = ("fn",)
                if kind is None:
                    continue
                expr = self._spawn_entry_expr(node, pos, kwnames)
                if expr is None:
                    continue
                hresolved = sf.resolver.resolve(expr)
                if kind == "signal" and hresolved in _NON_HANDLERS:
                    continue
                enc = idx.enclosing.get(id(expr))
                enc_rec = self.records.get(id(enc)) if enc is not None \
                    else None
                ctor_types = self._local_ctor_types(enc_rec) \
                    if enc_rec is not None else None
                entries = self.resolve_callable(sf, expr, enc, ctor_types)
                desc = ImportResolver.dotted(expr) or \
                    ("<lambda>" if isinstance(expr, ast.Lambda)
                     else ast.dump(expr)[:40])
                sites.append(SpawnSite(sf, node, kind, desc, entries))
                arg_ids.add(id(expr))
        self._spawn_arg_ids = arg_ids
        self._spawn_sites = sites
        return sites

    def _role_callees(self, rec: FuncRecord) -> List[FuncRecord]:
        """:meth:`callees` with the SPAWN EDGES cut: a callable handed to
        ``Thread(target=…)``/``signal.signal``/``submit`` runs on the new
        thread, not the spawning one, so it is not a same-role callee."""
        cache = getattr(self, "_role_callees_cache", None)
        if cache is None:
            cache = self._role_callees_cache = {}
        cached = cache.get(id(rec.node))
        if cached is not None:
            return cached
        self.spawn_sites()            # ensures _spawn_arg_ids
        skip = self._spawn_arg_ids
        idx = self.file_index[rec.sf.path]
        ctor_types = self._local_ctor_types(rec)
        out: List[FuncRecord] = []
        seen: Set[int] = set()

        def add(targets) -> None:
            for t in targets:
                if id(t.node) not in seen and t.node is not rec.node:
                    seen.add(id(t.node))
                    out.append(t)

        for sub in body_walk(rec.node):
            if isinstance(sub, ast.Call):
                enc = idx.enclosing.get(id(sub.func), rec.node)
                add(self.resolve_call(rec.sf, sub.func, enc, ctor_types,
                                      skip_generic_unique=True))
                for arg in list(sub.args) + [kw.value for kw in
                                             sub.keywords]:
                    if id(arg) in skip:
                        continue
                    if isinstance(arg, (ast.Name, ast.Attribute)):
                        enc = idx.enclosing.get(id(arg), rec.node)
                        add(self.resolve_call(rec.sf, arg, enc,
                                              ctor_types,
                                              skip_generic_unique=True))
        cache[id(rec.node)] = out
        return out

    def _role_closure(self, seeds: Iterable[FuncRecord]) -> List[FuncRecord]:
        out: List[FuncRecord] = []
        seen: Set[int] = set()
        frontier = list(seeds)
        while frontier:
            rec = frontier.pop()
            if id(rec.node) in seen:
                continue
            seen.add(id(rec.node))
            out.append(rec)
            frontier.extend(self._role_callees(rec))
        return out

    def thread_roles(self) -> List[ThreadRole]:
        """Concurrent roles (one per distinct entry set), cached.  Role
        names are ``<kind>:<entry qualname>`` — stable across runs."""
        if getattr(self, "_thread_roles", None) is not None:
            return self._thread_roles
        by_name: Dict[str, ThreadRole] = {}
        for site in self.spawn_sites():
            if site.entries:
                name = f"{site.kind}:{site.entries[0].qualname}"
            else:
                name = f"{site.kind}:{site.sf.path}:{site.line}"
            role = by_name.get(name)
            if role is None:
                role = by_name[name] = ThreadRole(name, site.kind)
            role.sites.append(site)
            known = {id(e.node) for e in role.entries}
            role.entries.extend(e for e in site.entries
                                if id(e.node) not in known)
        self._thread_roles = [by_name[n] for n in sorted(by_name)]
        return self._thread_roles

    def role_members(self, role: ThreadRole) -> List[FuncRecord]:
        return self._role_closure(role.entries)

    def role_map(self) -> Dict[int, Set[str]]:
        """func-node id -> the set of role names the function can run
        under.

        The ``main`` role's seeds are the records with NO incoming
        call-graph reference (the public surface: CLI mains, class
        methods called through duck-typed receivers, constructors) minus
        the concurrent entries; its members are their closure.  A helper
        referenced ONLY by a thread entry's closure therefore stays out
        of ``main`` — attributing it to the spawning thread too would
        make every thread-private helper read as cross-thread.  The
        approximation is deliberately biased toward fewer false
        conflicts: an unresolvable duck-typed call from main into a
        role-private helper is missed, never invented."""
        cached = getattr(self, "_role_map", None)
        if cached is not None:
            return cached
        roles = self.thread_roles()
        out: Dict[int, Set[str]] = {}
        entry_ids: Set[int] = set()
        for role in roles:
            for rec in self.role_members(role):
                out.setdefault(id(rec.node), set()).add(role.name)
            entry_ids.update(id(e.node) for e in role.entries)
        referenced: Set[int] = set()
        for rec in self.records.values():
            for callee in self._role_callees(rec):
                referenced.add(id(callee.node))
        main_seeds = []
        for rec in self.records.values():
            if isinstance(rec.node, ast.Lambda):
                continue
            if id(rec.node) in entry_ids or id(rec.node) in referenced:
                continue
            fidx = self.file_index[rec.sf.path]
            if fidx.parent_func.get(id(rec.node)) is not None:
                continue              # nested defs: reachable via parent
            main_seeds.append(rec)
        for rec in self._role_closure(main_seeds):
            out.setdefault(id(rec.node), set()).add(MAIN_ROLE)
        for rec in self.records.values():
            out.setdefault(id(rec.node), {MAIN_ROLE})
        self._role_map = out
        return out

    def roles_of(self, rec: FuncRecord) -> Set[str]:
        return self.role_map().get(id(rec.node), {MAIN_ROLE})

    # -- class attribute construction map (lock/sync-object identity) ------

    def class_attr_ctors(self, class_key: Tuple[str, str]) -> Dict[str, str]:
        """``self.X = <Call>`` assignments anywhere in the class (its own
        methods): attr -> the resolved constructor's dotted path (or the
        in-scope class path).  The concurrency checkers use it to know a
        ``_lock`` is a ``threading.Lock`` vs ``RLock``, a ``_q`` is a
        ``queue.Queue``, and which class ``self.center`` is."""
        cache = getattr(self, "_attr_ctor_cache", None)
        if cache is None:
            cache = self._attr_ctor_cache = {}
        cached = cache.get(class_key)
        if cached is not None:
            return cached
        out: Dict[str, str] = {}
        module, _cls_name = class_key
        for name, recs in self.methods.items():
            for rec in recs:
                if rec.class_key != class_key:
                    continue
                for sub in body_walk(rec.node):
                    if not isinstance(sub, (ast.Assign, ast.AnnAssign)):
                        continue
                    value = sub.value
                    if not isinstance(value, ast.Call):
                        continue
                    resolved = rec.sf.resolver.resolve(value.func)
                    if resolved is None and \
                            isinstance(value.func, ast.Name):
                        fidx = self.file_index[rec.sf.path]
                        if value.func.id in fidx.classes:
                            resolved = f"{module}.{value.func.id}"
                    if resolved is None:
                        continue
                    targets = sub.targets if isinstance(sub, ast.Assign) \
                        else [sub.target]
                    for t in targets:
                        if isinstance(t, ast.Attribute) and \
                                isinstance(t.value, ast.Name) and \
                                t.value.id == "self":
                            out.setdefault(t.attr, resolved)
        cache[class_key] = out
        return out


# ---------------------------------------------------------------------------
# small shared AST helpers
# ---------------------------------------------------------------------------

def body_walk(fn: ast.AST):
    """Walk a function's body, NOT descending into nested FunctionDefs
    (reachable separately when called) but following inline lambdas
    (they run at trace time via tree.map etc.)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, _FuncDef):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def consumed_key_name(call: ast.Call, resolver: ImportResolver
                      ) -> Optional[str]:
    """The Name consumed as the key of a ``jax.random.<sampler>`` call,
    or None."""
    resolved = resolver.resolve(call.func)
    if not resolved or not resolved.startswith("jax.random."):
        return None
    if resolved.rsplit(".", 1)[-1] not in KEY_CONSUMERS:
        return None
    key_arg = call.args[0] if call.args else None
    for kw in call.keywords:
        if kw.arg == "key":
            key_arg = kw.value
    if isinstance(key_arg, ast.Name):
        return key_arg.id
    return None


def axis_values(call: ast.Call, cname: str, resolver: ImportResolver,
                index: Optional[ProgramIndex] = None,
                local_consts: Optional[Dict[str, object]] = None
                ) -> Tuple:
    """Statically-known axis names of one collective call: a tuple of
    strings for every axis entry that resolves to a literal, or () when
    the axis argument is not statically evaluable (parameters, computed
    tuples) — unknown axes are SKIPPED, never guessed."""
    pos = COLLECTIVES[cname]
    arg = call.args[pos] if len(call.args) > pos else None
    for kw in call.keywords:
        if kw.arg in ("axis_name", "axis_names"):
            arg = kw.value
    if arg is None:
        return ()
    vals = _eval_axis(arg, resolver, index, local_consts)
    return tuple(vals) if vals is not None else ()


def _eval_axis(node: ast.AST, resolver: ImportResolver,
               index: Optional[ProgramIndex],
               local_consts: Optional[Dict[str, object]]
               ) -> Optional[List[str]]:
    if isinstance(node, ast.Constant):
        return [node.value] if isinstance(node.value, str) else None
    if isinstance(node, (ast.Tuple, ast.List)):
        out: List[str] = []
        for e in node.elts:
            sub = _eval_axis(e, resolver, index, local_consts)
            if sub is None:
                return None          # partially-unknown tuple: skip all
            out.extend(sub)
        return out
    if isinstance(node, ast.Name) and local_consts is not None and \
            node.id in local_consts:
        v = local_consts[node.id]
        return [v] if isinstance(v, str) else None
    if isinstance(node, (ast.Name, ast.Attribute)):
        resolved = resolver.resolve(node)
        if resolved and index is not None:
            v = index.module_constant(resolved)
            if isinstance(v, str):
                return [v]
        return None
    return None

"""Host-concurrency pass: shared-state races, lock-order cycles, signal
safety, daemon discipline (docs/design.md §16).

Every concurrency bug in this repo so far was caught by hand review: the
signal-mid-event registry deadlock (PR 4, fixed by making the registry
lock reentrant), the unlocked ``stats_snapshot`` iteration race and the
``threading.Thread._stop`` attribute collision (PRs 8–9).  The runtime
keeps growing threads — PrefetchLoader window producers, the watchdog
monitor, membership heartbeats, center-server handler threads,
ChaosMonkey/ChaosProxy daemons — so this module machine-checks the
class of bug, on top of the engine's thread-role inference
(:meth:`~..engine.ProgramIndex.role_map`):

* **shared-state-race** — an instance attribute (or module global)
  written from ≥2 thread roles, or a container mutated in one role
  while another iterates/copies it (the ``stats_snapshot`` shape), with
  no COMMON lock dominating the conflicting accesses.  Lock dominance
  is interprocedural: an access is guarded by the ``with <lock>:``
  blocks lexically around it PLUS the locks provably held at every
  resolvable call site of its function (the ``request`` →
  ``_request_locked`` → ``_note_fail`` shape).  Whitelists: attributes
  constructed as synchronization/atomic objects (``queue.Queue``,
  ``threading.Event``/locks/threads, ``collections.deque``, executors)
  and writes inside ``__init__``/``__new__`` (construction
  happens-before ``start()``).
* **lock-ordering** — the global lock acquisition graph (nested
  ``with`` blocks, plus calls made while holding a lock into functions
  that transitively acquire).  A cycle between distinct locks is a
  potential deadlock; re-acquiring a known non-reentrant
  ``threading.Lock`` while it is already held is a self-deadlock.
* **signal-safety** — functions reachable from ``signal.signal``
  handlers may not acquire non-reentrant locks (the PR-4
  generalization), block (sleeps, socket connects, queue/thread/event
  waits), spawn threads, or record telemetry (a registry call does
  buffered-file I/O; a signal landing mid-``write`` on the same thread
  raises ``RuntimeError: reentrant call`` inside the BufferedWriter —
  only ``utils/telemetry.py``'s own TERMINAL fatal-signal hook, which
  re-raises with ``SIG_DFL``, is sanctioned).
* **daemon-discipline** — non-daemon threads never joined block
  interpreter exit; a thread object that ESCAPES (stored on ``self``
  or appended to an attribute container) and is started but never
  joined can outlive its owner's ``stop()``; a ``threading.Thread``
  subclass must be daemonic or join itself, and must not shadow Thread
  internals (``self._stop`` — the PR-8 collision).

Scope: findings are reported for runtime code only (``theanompi_tpu/``,
``scripts/``).  ``tests/`` spawn threads to *provoke*
races; their spawn sites neither seed roles nor produce findings.
Resolution follows the engine's static-only contract — a duck-typed
call the call graph cannot resolve contributes nothing, so the pass
under-approximates rather than guesses.
"""

from __future__ import annotations

import ast
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

from ..core import Checker, Finding, ImportResolver, SourceFile, register
from ..engine import MAIN_ROLE, FuncRecord, ProgramIndex, body_walk

_FuncDef = (ast.FunctionDef, ast.AsyncFunctionDef)

# -- vocabulary ---------------------------------------------------------------

#: lock constructors -> reentrancy class
LOCK_CTORS = {
    "threading.Lock": "lock",            # NON-reentrant
    "threading.RLock": "rlock",
    "threading.Condition": "condition",
    "threading.Semaphore": "semaphore",
    "threading.BoundedSemaphore": "semaphore",
}

#: attributes constructed as one of these are synchronization / atomic
#: objects — their own methods synchronize, so they are not race state
SYNC_CTORS = set(LOCK_CTORS) | {
    "threading.Event", "threading.Thread", "threading.Timer",
    "threading.local", "threading.Barrier",
    "queue.Queue", "queue.SimpleQueue", "queue.LifoQueue",
    "queue.PriorityQueue",
    "collections.deque",                 # append/popleft are atomic
    "concurrent.futures.ThreadPoolExecutor",
    "concurrent.futures.ProcessPoolExecutor",
}

#: container-mutating method names (a call on ``self.X`` counts as a write)
MUTATORS = {"append", "appendleft", "add", "remove", "discard", "pop",
            "popitem", "popleft", "clear", "update", "setdefault",
            "extend", "insert"}

#: reads that traverse the whole container (the iteration-race shape)
COPY_METHODS = {"items", "values", "keys", "copy"}
ITER_WRAPPERS = {"list", "dict", "set", "frozenset", "sorted", "tuple",
                 "sum", "max", "min", "any", "all"}

#: calls a signal handler must not make (module-level, resolver-resolved)
BLOCKING_RESOLVED = {
    "time.sleep", "select.select", "socket.create_connection",
    "subprocess.run", "subprocess.call", "subprocess.check_call",
    "subprocess.check_output",
}
#: blocking methods on ctor-typed receivers: ctor -> method names
BLOCKING_METHODS = {
    "queue.Queue": {"get", "put", "join"},
    "queue.LifoQueue": {"get", "put", "join"},
    "queue.PriorityQueue": {"get", "put", "join"},
    "queue.SimpleQueue": {"get", "put"},
    "threading.Thread": {"join"},
    "threading.Timer": {"join"},
    "threading.Event": {"wait"},
    "threading.Condition": {"wait", "wait_for", "acquire"},
    "threading.Lock": {"acquire"},
    "threading.Semaphore": {"acquire"},
    "subprocess.Popen": {"wait", "communicate"},
}
THREAD_CTORS = {"threading.Thread", "threading.Timer"}

#: telemetry recording surface (mirrors telemetry_hot_path.RECORDING +
#: the accessor-adjacent calls that do registry/file I/O)
TM_RECORDING = {"counter", "gauge", "observe", "phase", "event",
                "system_snapshot", "dump_flight", "summary", "close"}
TELEMETRY_MODULE = "theanompi_tpu.utils.telemetry"
TM_HANDLE_SOURCES = {TELEMETRY_MODULE + ".active", TELEMETRY_MODULE + ".init"}
#: the one module whose handler may record: its own fatal-signal hook is
#: terminal (dump + re-raise with SIG_DFL), per docs/design.md §11/§16
TM_SANCTIONED_PATH = "theanompi_tpu/utils/telemetry.py"

#: ``threading.Thread`` internals a subclass must not shadow (the PR-8
#: ``_stop`` collision: Thread.join() calls self._stop() internally)
THREAD_INTERNALS = {"_started", "_stop", "_target", "_args", "_kwargs",
                    "_name", "_daemonic", "_ident", "_native_id",
                    "_tstate_lock", "_invoke_excepthook", "_stderr",
                    "_initialized"}

_WRITE_KINDS = ("write", "augwrite", "mutwrite")


def _runtime_path(path: str) -> bool:
    return not path.startswith("tests/")


# -- access / scan records ----------------------------------------------------

class Access:
    __slots__ = ("key", "kind", "node", "rec", "held")

    def __init__(self, key, kind, node, rec, held):
        self.key = key                # (owner_id, attr) — owner_id is
        #                               'module.Class' or 'module' (global)
        self.kind = kind              # write|augwrite|mutwrite|iterread
        self.node = node
        self.rec = rec
        self.held = held              # frozenset of syntactically-held locks


class FuncScan:
    __slots__ = ("accesses", "acquires", "calls", "tm_calls", "blocking",
                 "spawns")

    def __init__(self):
        self.accesses: List[Access] = []
        # (lock_id, reentrancy|None, node, held-before frozenset)
        self.acquires: List[Tuple[str, Optional[str], ast.AST,
                                  FrozenSet[str]]] = []
        # (node, tuple of target node ids, held frozenset)
        self.calls: List[Tuple[ast.AST, Tuple[int, ...],
                               FrozenSet[str]]] = []
        self.tm_calls: List[Tuple[ast.AST, str]] = []   # (node, rendered)
        self.blocking: List[Tuple[ast.AST, str]] = []
        self.spawns: List[ast.AST] = []


# -- the shared analysis context ---------------------------------------------

class ConcurrencyContext:
    """One pass over the runtime records, shared by the four checkers
    (cached on the ProgramIndex)."""

    @classmethod
    def get(cls, index: ProgramIndex) -> "ConcurrencyContext":
        ctx = getattr(index, "_host_concurrency_ctx", None)
        if ctx is None:
            ctx = index._host_concurrency_ctx = cls(index)
        return ctx

    def __init__(self, index: ProgramIndex):
        self.index = index
        self.roles = {r.name: r for r in index.thread_roles()}
        #: roles introduced by at least one non-test spawn site — the
        #: only ones that count toward conflicts (tests provoke races
        #: on purpose)
        self.runtime_roles = {
            name for name, r in self.roles.items()
            if any(_runtime_path(s.path) for s in r.sites)}
        self.recs = [r for r in index.records.values()
                     if _runtime_path(r.sf.path)]
        self._module_ctors: Dict[str, Dict[str, str]] = {}
        self._handles: Dict[str, Set[str]] = {}
        self._owner_keys: Dict[str, Tuple[str, str]] = {}
        self._shares_cache: Dict[Tuple[str, str], bool] = {}
        self.scans: Dict[int, FuncScan] = {}
        for rec in self.recs:
            self.scans[id(rec.node)] = self._scan(rec)
        self._held_entry = self._compute_held_at_entry()
        self._trans_acquires = self._compute_transitive_acquires()

    # -- role helpers -------------------------------------------------------

    def roles_of(self, rec: FuncRecord) -> Set[str]:
        roles = {r for r in self.index.roles_of(rec)
                 if r == MAIN_ROLE or r in self.runtime_roles}
        return roles or {MAIN_ROLE}

    def multi_instance(self, role_name: str) -> bool:
        """Roles that run MANY threads at once (one socketserver handler
        per connection, one executor worker per pool slot) — two
        executions of the SAME role race with each other."""
        role = self.roles.get(role_name)
        return role is not None and role.kind in ("handler", "executor")

    def role_shares_owner(self, role_name: str, owner_id: str) -> bool:
        """Does this role provably share INSTANCES of the attribute's
        owner class with other roles?  True when the role's entry is a
        method of that class, a spawn site sits inside one of its
        methods (``Thread(target=self._producer)`` hands ``self`` to
        the new thread), or the role is multi-instance (handlers /
        executor workers share their closures).  ``main`` never shares
        by itself — a conflict needs a concurrent role anchored to the
        class, which is what keeps per-island private models (each
        thread constructs its OWN ModelBase) out of the findings."""
        if role_name == MAIN_ROLE:
            return False
        if self.multi_instance(role_name):
            return True
        role = self.roles.get(role_name)
        if role is None:
            return False
        owner_key = self._owner_keys.get(owner_id)
        if owner_key is None:
            return True                 # module global: trivially shared
        cache = self._shares_cache
        hit = cache.get((role_name, owner_id))
        if hit is not None:
            return hit
        out = any(e.class_key == owner_key for e in role.entries)
        if not out:
            for site in role.sites:
                idx = self.index.file_index[site.sf.path]
                f = idx.enclosing.get(id(site.node))
                while f is not None and not out:
                    cls = idx.class_of.get(id(f))
                    if cls is not None:
                        out = (site.sf.resolver.module,
                               cls.name) == owner_key
                        break
                    f = idx.parent_func.get(id(f))
        cache[(role_name, owner_id)] = out
        return out

    def conflicting_pair(self, owner_id: str, a: "Access", b: "Access"
                         ) -> Optional[Tuple[str, str]]:
        """The first (role_a, role_b) witness that accesses ``a`` and
        ``b`` can touch the SAME object from two live threads, or
        None."""
        for r1 in sorted(self.roles_of(a.rec)):
            for r2 in sorted(self.roles_of(b.rec)):
                if r1 == r2 and not self.multi_instance(r1):
                    continue
                if self.role_shares_owner(r1, owner_id) or \
                        self.role_shares_owner(r2, owner_id):
                    return (r1, r2)
        return None

    # -- identity helpers ---------------------------------------------------

    def module_ctors(self, sf: SourceFile) -> Dict[str, str]:
        """Module-level ``NAME = <ctor>()`` assignments of one file."""
        cached = self._module_ctors.get(sf.path)
        if cached is not None:
            return cached
        out: Dict[str, str] = {}
        for st in sf.tree.body:
            if isinstance(st, ast.Assign) and isinstance(st.value, ast.Call):
                resolved = sf.resolver.resolve(st.value.func)
                if resolved:
                    for t in st.targets:
                        if isinstance(t, ast.Name):
                            out.setdefault(t.id, resolved)
        self._module_ctors[sf.path] = out
        return out

    def _class_id(self, key: Tuple[str, str]) -> str:
        return f"{key[0]}.{key[1]}"

    def attr_ctor(self, class_key, attr) -> Optional[str]:
        return self.index.class_attr_ctors(class_key).get(attr)

    def _attr_key(self, rec: FuncRecord, expr: ast.AST
                  ) -> Optional[Tuple[Tuple[str, str], Optional[str]]]:
        """``(key, ctor)`` for a shared-state expression:
        ``self.X`` → the enclosing class's attr; ``self.A.B`` → ``B`` on
        ``A``'s constructor class (when known); a bare Name that some
        function in the module writes through ``global`` → module
        global.  None for everything else (locals, parameters)."""
        if isinstance(expr, ast.Attribute):
            base = expr.value
            if isinstance(base, ast.Name) and base.id == "self":
                if rec.class_key is None:
                    return None
                owner = self._class_id(rec.class_key)
                self._owner_keys.setdefault(owner, rec.class_key)
                return (owner, expr.attr), \
                    self.attr_ctor(rec.class_key, expr.attr)
            if isinstance(base, ast.Attribute) and \
                    isinstance(base.value, ast.Name) and \
                    base.value.id == "self" and rec.class_key is not None:
                ctor = self.attr_ctor(rec.class_key, base.attr)
                ckey = self.index._class_keys.get(ctor or "")
                if ckey is not None:
                    owner = self._class_id(ckey)
                    self._owner_keys.setdefault(owner, ckey)
                    return (owner, expr.attr), self.attr_ctor(ckey,
                                                              expr.attr)
            return None
        if isinstance(expr, ast.Name):
            module = rec.sf.resolver.module
            if expr.id in self._global_writes(rec.sf):
                return (module, expr.id), \
                    self.module_ctors(rec.sf).get(expr.id)
        return None

    def _global_writes(self, sf: SourceFile) -> Set[str]:
        """Names some function in the module declares ``global`` —
        the module-global shared-state candidates."""
        cached = getattr(sf, "_tpulint_global_names", None)
        if cached is None:
            cached = set()
            for node in ast.walk(sf.tree):
                if isinstance(node, ast.Global):
                    cached.update(node.names)
            sf._tpulint_global_names = cached
        return cached

    def lock_id(self, rec: FuncRecord, expr: ast.AST
                ) -> Optional[Tuple[str, Optional[str]]]:
        """``(canonical id, reentrancy class|None)`` when ``expr`` looks
        like a lock being entered, else None.  Identity is the owning
        class + attribute (so ``self._lock`` in two methods — or
        ``self.center._lock`` and ``ElasticCenter``'s own ``self._lock``
        — unify); unresolvable lock-named expressions fall back to a
        per-file textual id (consistent within the file, documented
        approximation)."""
        dotted = ImportResolver.dotted(expr)
        if dotted is None:
            return None
        keyed = self._attr_key(rec, expr)
        if keyed is not None:
            (owner, attr), ctor = keyed
            kind = LOCK_CTORS.get(ctor or "")
            if kind is not None:
                return f"{owner}.{attr}", kind
            if "lock" in attr.lower():
                return f"{owner}.{attr}", None
            return None
        if isinstance(expr, ast.Name):
            ctor = self.module_ctors(rec.sf).get(expr.id)
            kind = LOCK_CTORS.get(ctor or "")
            if kind is not None or "lock" in expr.id.lower():
                return f"{rec.sf.resolver.module}.{expr.id}", kind
            return None
        terminal = dotted.rsplit(".", 1)[-1]
        if "lock" in terminal.lower():
            return f"{rec.sf.path}:{dotted}", None
        return None

    def telemetry_handles(self, sf: SourceFile) -> Set[str]:
        """Dotted names bound to a telemetry registry in one file
        (the telemetry-hot-path discovery, shared here for the
        signal-safety recording rule)."""
        cached = self._handles.get(sf.path)
        if cached is not None:
            return cached
        handles: Set[str] = {"self.telemetry"}
        changed = True
        while changed:
            changed = False
            for node in ast.walk(sf.tree):
                if not isinstance(node, ast.Assign):
                    continue

                def is_src(v) -> bool:
                    if isinstance(v, ast.Call):
                        return sf.resolver.resolve(v.func) in \
                            TM_HANDLE_SOURCES
                    if isinstance(v, (ast.Name, ast.Attribute)):
                        return ImportResolver.dotted(v) in handles
                    if isinstance(v, ast.IfExp):
                        # `tm = init(...) if record_dir else active()`
                        return is_src(v.body) or is_src(v.orelse)
                    return False

                if not is_src(node.value):
                    continue
                for t in node.targets:
                    name = ImportResolver.dotted(t)
                    if name and name not in handles:
                        handles.add(name)
                        changed = True
        self._handles[sf.path] = handles
        return handles

    # -- the per-function walk ----------------------------------------------

    def _scan(self, rec: FuncRecord) -> FuncScan:
        scan = FuncScan()
        idx = self.index.file_index[rec.sf.path]
        ctor_types = self.index._local_ctor_types(rec)
        handles = self.telemetry_handles(rec.sf)

        def attr_access(expr, kind, node, held):
            keyed = self._attr_key(rec, expr)
            if keyed is None:
                return
            key, ctor = keyed
            if ctor in SYNC_CTORS:
                return                      # synchronization object
            scan.accesses.append(Access(key, kind, node, rec,
                                        frozenset(held)))

        def classify_call(node, held):
            func = node.func
            resolved = rec.sf.resolver.resolve(func)
            if resolved in BLOCKING_RESOLVED:
                scan.blocking.append((node, f"`{resolved}()`"))
            if resolved in THREAD_CTORS:
                scan.spawns.append(node)
            if isinstance(func, ast.Name) and func.id in ITER_WRAPPERS \
                    and len(node.args) == 1 and not node.keywords:
                attr_access(node.args[0], "iterread", node, held)
            if isinstance(func, ast.Attribute):
                recv = func.value
                if func.attr in MUTATORS:
                    attr_access(recv, "mutwrite", node, held)
                elif func.attr in COPY_METHODS:
                    attr_access(recv, "iterread", node, held)
                # blocking method on a ctor-typed receiver (self attr or
                # module-level name; locals stay out of scope — no guess)
                keyed = self._attr_key(rec, recv)
                if keyed is not None:
                    ctor = keyed[1]
                elif isinstance(recv, ast.Name):
                    ctor = self.module_ctors(rec.sf).get(recv.id)
                else:
                    ctor = None
                if ctor in BLOCKING_METHODS and \
                        func.attr in BLOCKING_METHODS[ctor]:
                    base = ImportResolver.dotted(recv) or "<recv>"
                    scan.blocking.append(
                        (node, f"`{base}.{func.attr}()` "
                               f"({ctor.rsplit('.', 1)[-1]})"))
                # telemetry recording
                if func.attr in TM_RECORDING:
                    base = ImportResolver.dotted(recv)
                    rbase = rec.sf.resolver.resolve(recv)
                    if (base in handles) or (rbase == TELEMETRY_MODULE):
                        scan.tm_calls.append(
                            (node, f"{base}.{func.attr}(...)"))
            # call-graph edge — generic names must not fall through to
            # the unique-family fallback here either: a `t.join()` on a
            # Thread resolving to an unrelated in-scope `join` would
            # inject bogus lock-free call sites into the held-at-entry
            # intersection and bogus acquires into transitive_acquires
            enc = idx.enclosing.get(id(func), rec.node)
            targets = self.index.resolve_call(rec.sf, func, enc, ctor_types,
                                              skip_generic_unique=True)
            if targets:
                scan.calls.append(
                    (node, tuple(id(t.node) for t in targets),
                     frozenset(held)))

        def walk(node, held):
            if isinstance(node, _FuncDef):
                return                      # separate record, fresh locks
            if isinstance(node, (ast.With, ast.AsyncWith)):
                inner = set(held)
                for item in node.items:
                    walk(item.context_expr, held)
                    lid = self.lock_id(rec, item.context_expr)
                    if lid is not None:
                        scan.acquires.append((lid[0], lid[1],
                                              item.context_expr,
                                              frozenset(held)))
                        inner.add(lid[0])
                for st in node.body:
                    walk(st, inner)
                return
            if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) \
                    else [node.target]
                if isinstance(node, ast.AnnAssign) and node.value is None:
                    targets = []        # bare annotation — not a write
                kind = "augwrite" if isinstance(node, ast.AugAssign) \
                    else "write"
                for t in targets:
                    for el in (t.elts if isinstance(t, (ast.Tuple,
                                                        ast.List))
                               else [t]):
                        if isinstance(el, ast.Subscript):
                            attr_access(el.value, "mutwrite", node, held)
                        else:
                            attr_access(el, kind, node, held)
            elif isinstance(node, ast.Call):
                classify_call(node, held)
            elif isinstance(node, ast.For):
                attr_access(node.iter, "iterread", node, held)
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                for gen in node.generators:
                    attr_access(gen.iter, "iterread", node, held)
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    if isinstance(t, ast.Subscript):
                        attr_access(t.value, "mutwrite", node, held)
            for child in ast.iter_child_nodes(node):
                walk(child, held)

        for child in ast.iter_child_nodes(rec.node):
            walk(child, set())
        return scan

    # -- interprocedural lock context ---------------------------------------

    def _compute_held_at_entry(self) -> Dict[int, FrozenSet[str]]:
        """locks held at EVERY resolvable call site of each function
        (decreasing fixpoint; thread entries and functions with no call
        sites run lock-free)."""
        sites: Dict[int, List[Tuple[int, FrozenSet[str]]]] = {}
        in_scope = {id(r.node) for r in self.recs}
        for rec in self.recs:
            scan = self.scans[id(rec.node)]
            for node, targets, held in scan.calls:
                for t in targets:
                    if t in in_scope:
                        sites.setdefault(t, []).append((id(rec.node), held))
        entry_ids = set()
        for role in self.roles.values():
            entry_ids.update(id(e.node) for e in role.entries)
        TOP = None                      # the full-universe sentinel
        held: Dict[int, object] = {}
        for rec in self.recs:
            nid = id(rec.node)
            if nid in entry_ids or nid not in sites:
                held[nid] = frozenset()
            else:
                held[nid] = TOP
        for _ in range(len(self.recs) + 1):
            changed = False
            for nid, calls in sites.items():
                if nid in entry_ids:
                    continue            # entries run lock-free, period
                # H[n] = ⋂ over call sites (site_held ∪ H[caller]);
                # TOP is ⋂'s identity.  H[caller] only ever shrinks, so
                # full recomputation converges decreasingly.
                acc = TOP
                for caller, site_held in calls:
                    ch = held.get(caller, frozenset())
                    if ch is TOP:
                        continue        # TOP contributes the identity
                    eff = site_held | ch
                    acc = eff if acc is TOP else (acc & eff)
                if acc is not TOP and acc != held.get(nid):
                    held[nid] = acc
                    changed = True
            if not changed:
                break
        return {nid: (v if v is not TOP else frozenset())
                for nid, v in held.items()}

    def held_at_entry(self, rec: FuncRecord) -> FrozenSet[str]:
        return self._held_entry.get(id(rec.node), frozenset())

    def effective_locks(self, access: Access) -> FrozenSet[str]:
        return access.held | self.held_at_entry(access.rec)

    def _compute_transitive_acquires(self) -> Dict[int, Set[str]]:
        out: Dict[int, Set[str]] = {}
        for rec in self.recs:
            direct = {lid for lid, _, _, _ in self.scans[id(rec.node)]
                      .acquires}
            if direct:
                out[id(rec.node)] = set(direct)
        changed = True
        while changed:
            changed = False
            for rec in self.recs:
                scan = self.scans[id(rec.node)]
                cur = out.setdefault(id(rec.node), set())
                for _, targets, _ in scan.calls:
                    for t in targets:
                        extra = out.get(t)
                        if extra and not extra <= cur:
                            cur |= extra
                            changed = True
        return out

    def transitive_acquires(self, rec: FuncRecord) -> Set[str]:
        return self._trans_acquires.get(id(rec.node), set())

    def lock_kind(self, lock_id: str) -> Optional[str]:
        """Reentrancy class of a canonical lock id, when its constructor
        is known."""
        cached = getattr(self, "_lock_kinds", None)
        if cached is None:
            cached = self._lock_kinds = {}
            for rec in self.recs:
                for lid, kind, _, _ in self.scans[id(rec.node)].acquires:
                    if kind is not None:
                        cached.setdefault(lid, kind)
        return cached.get(lock_id)


def _fmt_roles(roles: Sequence[str]) -> str:
    return ", ".join(sorted(roles))


# ---------------------------------------------------------------------------
# shared-state-race
# ---------------------------------------------------------------------------

@register
class SharedStateRaceChecker(Checker):
    name = "shared-state-race"
    description = ("instance attributes / module globals written from "
                   "multiple thread roles (or mutated under another "
                   "role's iteration) without a common lock")
    needs_engine = True

    def check_program(self, index: ProgramIndex):
        ctx = ConcurrencyContext.get(index)
        by_key: Dict[Tuple[str, str], List[Access]] = {}
        for rec in ctx.recs:
            fname = rec.name
            for a in ctx.scans[id(rec.node)].accesses:
                if fname in ("__init__", "__new__") and \
                        a.kind in _WRITE_KINDS:
                    continue            # construction happens-before start
                by_key.setdefault(a.key, []).append(a)
        findings: List[Finding] = []
        for key in sorted(by_key, key=lambda k: (k[0], k[1])):
            accesses = sorted(by_key[key],
                              key=lambda a: (a.rec.sf.path,
                                             a.node.lineno,
                                             a.node.col_offset))
            findings.extend(self._check_attr(ctx, key, accesses))
        findings.sort(key=Finding.sort_key)
        return findings

    def _check_attr(self, ctx: ConcurrencyContext, key, accesses
                    ) -> List[Finding]:
        owner, attr = key
        out: List[Finding] = []
        writes = [a for a in accesses if a.kind in _WRITE_KINDS]
        # (a) a PAIR of writes that can land from two live threads on the
        # same object (distinct roles or one multi-instance role, with
        # instance-sharing evidence) and holds no common lock
        for i, w1 in enumerate(writes):
            for w2 in writes[i:]:
                pair = ctx.conflicting_pair(owner, w1, w2)
                if pair is None:
                    continue
                if ctx.effective_locks(w1) & ctx.effective_locks(w2):
                    continue
                anchor = w1 if not ctx.effective_locks(w1) else w2
                out.append(Finding(
                    self.name, anchor.rec.sf.path, anchor.node.lineno,
                    anchor.node.col_offset,
                    f"`{attr}` on `{owner}` is written from thread "
                    f"roles {_fmt_roles(set(pair))} that can run "
                    f"concurrently on one instance, with no common "
                    f"lock — guard every write with the same "
                    f"`with <lock>:` or confine writes to one role"))
                return out              # one finding per attribute
        # (b) container mutated in one role while another iterates/copies
        mut_writes = [a for a in writes if a.kind == "mutwrite"]
        iter_reads = [a for a in accesses if a.kind == "iterread"]
        for r in iter_reads:
            r_locks = ctx.effective_locks(r)
            for w in mut_writes:
                pair = ctx.conflicting_pair(owner, w, r)
                if pair is None:
                    continue
                if r_locks & ctx.effective_locks(w):
                    continue
                out.append(Finding(
                    self.name, r.rec.sf.path, r.node.lineno,
                    r.node.col_offset,
                    f"unlocked iteration/copy of `{attr}` on `{owner}` "
                    f"while role(s) {_fmt_roles(ctx.roles_of(w.rec))} "
                    f"mutate it (write at {w.rec.sf.path}:"
                    f"{w.node.lineno}) — the stats_snapshot race class; "
                    f"take the same lock around both sides"))
                break                   # one finding per read site
        return out


# ---------------------------------------------------------------------------
# lock-ordering
# ---------------------------------------------------------------------------

@register
class LockOrderingChecker(Checker):
    name = "lock-ordering"
    description = ("cycles in the lock acquisition graph (nested `with` "
                   "blocks + calls made while holding a lock) and "
                   "non-reentrant self-acquisition")
    needs_engine = True

    def check_program(self, index: ProgramIndex):
        ctx = ConcurrencyContext.get(index)
        # edges[a][b] = (sf, node) witness for a held -> b acquired
        edges: Dict[str, Dict[str, Tuple]] = {}
        findings: List[Finding] = []
        for rec in ctx.recs:
            scan = ctx.scans[id(rec.node)]
            entry_held = ctx.held_at_entry(rec)
            for lid, kind, node, held in scan.acquires:
                for a in sorted(held | entry_held):
                    if a == lid:
                        if ctx.lock_kind(lid) == "lock":
                            findings.append(Finding(
                                self.name, rec.sf.path, node.lineno,
                                node.col_offset,
                                f"non-reentrant lock `{lid}` re-acquired "
                                f"while already held — self-deadlock "
                                f"(use RLock or release first)"))
                        continue
                    edges.setdefault(a, {}).setdefault(
                        lid, (rec.sf, node))
            for node, targets, held in scan.calls:
                if not (held or entry_held):
                    continue
                acquired: Set[str] = set()
                for t in targets:
                    trec = index.records.get(t)
                    if trec is not None:
                        acquired |= ctx.transitive_acquires(trec)
                for a in sorted(held | entry_held):
                    for b in sorted(acquired):
                        if a == b:
                            if ctx.lock_kind(a) == "lock":
                                findings.append(Finding(
                                    self.name, rec.sf.path, node.lineno,
                                    node.col_offset,
                                    f"call while holding non-reentrant "
                                    f"lock `{a}` reaches a function that "
                                    f"acquires it again — self-deadlock"))
                            continue
                        edges.setdefault(a, {}).setdefault(
                            b, (rec.sf, node))
        findings.extend(self._cycles(edges))
        findings.sort(key=Finding.sort_key)
        return findings

    def _cycles(self, edges) -> List[Finding]:
        out: List[Finding] = []
        reported: Set[FrozenSet[str]] = set()
        for start in sorted(edges):
            stack = [(start, [start])]
            while stack:
                node, path = stack.pop()
                for nxt in sorted(edges.get(node, ())):
                    if nxt == start:
                        cyc = frozenset(path)
                        if cyc in reported or len(path) < 2:
                            continue
                        reported.add(cyc)
                        sf, wnode = edges[path[-1]][start]
                        chain = " -> ".join(path + [start])
                        out.append(Finding(
                            self.name, sf.path, wnode.lineno,
                            wnode.col_offset,
                            f"lock-order cycle: {chain} — two threads "
                            f"taking these locks in different orders can "
                            f"deadlock; impose one global order"))
                    elif nxt not in path and len(path) < 8:
                        stack.append((nxt, path + [nxt]))
        return out


# ---------------------------------------------------------------------------
# signal-safety
# ---------------------------------------------------------------------------

@register
class SignalSafetyChecker(Checker):
    name = "signal-safety"
    description = ("functions reachable from signal handlers must not "
                   "acquire non-reentrant locks, block, spawn threads, "
                   "or record telemetry (reentrant-BufferedWriter "
                   "hazard; the terminal fatal-signal hook in "
                   "utils/telemetry.py is the one sanctioned recorder)")
    needs_engine = True

    def check_program(self, index: ProgramIndex):
        ctx = ConcurrencyContext.get(index)
        findings: List[Finding] = []
        seen_members: Set[int] = set()
        for role in index.thread_roles():
            if role.kind != "signal" or role.name not in ctx.runtime_roles:
                continue
            for rec in index.role_members(role):
                if id(rec.node) in seen_members:
                    continue
                seen_members.add(id(rec.node))
                if not _runtime_path(rec.sf.path):
                    continue
                findings.extend(self._check_member(ctx, index, rec))
        findings.sort(key=Finding.sort_key)
        return findings

    def _check_member(self, ctx, index, rec: FuncRecord) -> List[Finding]:
        out: List[Finding] = []
        scan = ctx.scans.get(id(rec.node))
        if scan is None:
            return out
        where = f"signal-handler-reachable `{rec.qualname}`"
        for lid, kind, node, _held in scan.acquires:
            if kind == "lock":
                out.append(Finding(
                    self.name, rec.sf.path, node.lineno, node.col_offset,
                    f"{where} acquires NON-reentrant lock `{lid}` — a "
                    f"signal landing while the interrupted thread holds "
                    f"it deadlocks the process (the PR-4 class; use "
                    f"RLock or keep handlers lock-free)"))
        for node, targets, _held in scan.calls:
            reached = set()
            for t in targets:
                trec = index.records.get(t)
                if trec is not None:
                    reached |= {lid for lid in ctx.transitive_acquires(trec)
                                if ctx.lock_kind(lid) == "lock"}
            for lid in sorted(reached):
                out.append(Finding(
                    self.name, rec.sf.path, node.lineno, node.col_offset,
                    f"{where} calls into code acquiring NON-reentrant "
                    f"lock `{lid}` — deadlock if the signal interrupts "
                    f"a holder"))
        for node, desc in scan.blocking:
            out.append(Finding(
                self.name, rec.sf.path, node.lineno, node.col_offset,
                f"{where} blocks on {desc} — a signal handler must "
                f"return promptly (it runs on the main thread mid-"
                f"bytecode); set a flag/Event and handle it in the loop"))
        for node in scan.spawns:
            out.append(Finding(
                self.name, rec.sf.path, node.lineno, node.col_offset,
                f"{where} spawns a thread — thread bootstrap takes "
                f"interpreter-internal locks the interrupted thread may "
                f"hold; defer the spawn to the main loop"))
        if rec.sf.path != TM_SANCTIONED_PATH:
            for node, rendered in scan.tm_calls:
                out.append(Finding(
                    self.name, rec.sf.path, node.lineno, node.col_offset,
                    f"{where} records telemetry (`{rendered}`) — the "
                    f"registry does buffered-file I/O, and a signal "
                    f"landing mid-write on the same thread raises "
                    f"`RuntimeError: reentrant call` inside the "
                    f"BufferedWriter; only the terminal fatal-signal "
                    f"hook in utils/telemetry.py (dump + re-raise with "
                    f"SIG_DFL) is sanctioned (docs/design.md §16)"))
        return out


# ---------------------------------------------------------------------------
# daemon-discipline
# ---------------------------------------------------------------------------

@register
class DaemonDisciplineChecker(Checker):
    name = "daemon-discipline"
    description = ("non-daemon threads never joined; escaping started "
                   "threads without a bounded join; Thread subclasses "
                   "shadowing threading internals")
    needs_engine = True

    def check_program(self, index: ProgramIndex):
        ctx = ConcurrencyContext.get(index)
        findings: List[Finding] = []
        for site in index.spawn_sites():
            if not _runtime_path(site.path):
                continue
            if site.kind in ("thread", "timer"):
                findings.extend(self._check_ctor_site(index, site))
            elif site.kind == "thread-subclass":
                findings.extend(self._check_subclass(index, site))
        findings.sort(key=Finding.sort_key)
        return findings

    # -- helpers ------------------------------------------------------------

    @staticmethod
    def _kw_true(call: ast.Call, name: str) -> bool:
        for kw in call.keywords:
            if kw.arg == name and isinstance(kw.value, ast.Constant):
                return bool(kw.value.value)
        return False

    @staticmethod
    def _join_targets(tree: ast.AST) -> Tuple[Set[str], Set[str]]:
        """(dotted receivers of ``.join(`` calls, container attrs whose
        loop variable is joined) within ``tree``."""
        joined: Set[str] = set()
        containers: Set[str] = set()
        loop_vars: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.For) and \
                    isinstance(node.target, ast.Name):
                it = ImportResolver.dotted(node.iter)
                if it:
                    loop_vars[node.target.id] = it
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "join":
                recv = ImportResolver.dotted(node.func.value)
                if recv:
                    joined.add(recv)
                    if recv in loop_vars:
                        containers.add(loop_vars[recv])
        return joined, containers

    def _scope_tree(self, index, site) -> ast.AST:
        """The join-discipline search scope: the enclosing class body if
        the spawn happens in a method, else the whole module."""
        idx = index.file_index[site.sf.path]
        enc = idx.enclosing.get(id(site.node))
        f = enc
        while f is not None:
            cls = idx.class_of.get(id(f))
            if cls is not None:
                return cls
            f = idx.parent_func.get(id(f))
        return site.sf.tree

    def _check_ctor_site(self, index, site) -> List[Finding]:
        call = site.node
        idx = index.file_index[site.sf.path]
        enc = idx.enclosing.get(id(call))
        parent_src = enc if enc is not None else site.sf.tree
        # binding: the statement the constructor appears in
        stored_attr = local_name = None
        for sub in ast.walk(parent_src):
            if isinstance(sub, ast.Assign) and sub.value is call:
                t = sub.targets[0]
                if isinstance(t, ast.Attribute):
                    stored_attr = ImportResolver.dotted(t)
                elif isinstance(t, ast.Name):
                    local_name = t.id
                break
        daemon = self._kw_true(call, "daemon")
        started = False
        appended_to = None
        binding = local_name or stored_attr
        if not daemon and binding and enc is not None:
            # post-construction daemonization: `t.daemon = True` AND the
            # stored-attr shape `self._t.daemon = True`
            for sub in body_walk(enc):
                if isinstance(sub, ast.Assign) and \
                        ImportResolver.dotted(sub.targets[0] if
                                              sub.targets else None) == \
                        f"{binding}.daemon" and \
                        isinstance(sub.value, ast.Constant) and \
                        sub.value.value:
                    daemon = True
        if enc is not None and (local_name or stored_attr):
            for sub in body_walk(enc):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Attribute):
                    recv = ImportResolver.dotted(sub.func.value)
                    if recv in (local_name, stored_attr) and \
                            sub.func.attr == "start":
                        started = True
                    if sub.func.attr == "append" and sub.args and \
                            local_name is not None and \
                            isinstance(sub.args[0], ast.Name) and \
                            sub.args[0].id == local_name:
                        appended_to = ImportResolver.dotted(sub.func.value)
        # chained Thread(...).start()
        chained = False
        for sub in ast.walk(parent_src):
            if isinstance(sub, ast.Call) and \
                    isinstance(sub.func, ast.Attribute) and \
                    sub.func.attr == "start" and sub.func.value is call:
                chained = started = True
        scope = self._scope_tree(index, site)
        joined, join_containers = self._join_targets(scope)
        out: List[Finding] = []
        kind = "Timer" if site.kind == "timer" else "Thread"
        if stored_attr is not None:
            escapes_as = stored_attr
            is_joined = stored_attr in joined
        elif appended_to is not None:
            escapes_as = appended_to
            is_joined = appended_to in join_containers
        else:
            escapes_as = None
            is_joined = (local_name in joined) if local_name else False
        if not daemon and not is_joined:
            out.append(Finding(
                self.name, site.path, site.line, call.col_offset,
                f"non-daemon {kind} (target `{site.target_desc}`) with "
                f"no join() in scope — it blocks interpreter exit and "
                f"outlives its owner; pass daemon=True or join it on "
                f"every shutdown path"))
        elif escapes_as is not None and started and not is_joined:
            out.append(Finding(
                self.name, site.path, site.line, call.col_offset,
                f"{kind} stored on `{escapes_as}` is start()ed but "
                f"never joined — it can outlive stop(); add a bounded "
                f"join (join(timeout=...)) on the shutdown path"))
        if chained and not daemon:
            pass                        # already covered by the first arm
        return out

    def _check_subclass(self, index, site) -> List[Finding]:
        cls = site.node                 # the ClassDef
        out: List[Finding] = []
        # internals shadowing: any method assigning self.<internal>
        for sub in ast.walk(cls):
            if not isinstance(sub, ast.Assign):
                continue
            for t in sub.targets:
                if isinstance(t, ast.Attribute) and \
                        isinstance(t.value, ast.Name) and \
                        t.value.id == "self" and \
                        t.attr in THREAD_INTERNALS:
                    out.append(Finding(
                        self.name, site.path, sub.lineno, sub.col_offset,
                        f"Thread subclass `{cls.name}` assigns "
                        f"`self.{t.attr}`, shadowing a threading.Thread "
                        f"internal — the PR-8 `_stop` collision class; "
                        f"rename the attribute"))
        # daemon / join discipline of the subclass itself
        daemonic = False
        for sub in ast.walk(cls):
            if isinstance(sub, ast.Call) and self._kw_true(sub, "daemon"):
                daemonic = True
            if isinstance(sub, ast.Assign):
                for t in sub.targets:
                    if isinstance(t, ast.Attribute) and \
                            ImportResolver.dotted(t) == "self.daemon" and \
                            isinstance(sub.value, ast.Constant) and \
                            sub.value.value:
                        daemonic = True
        joined, _ = self._join_targets(cls)
        self_joins = any(j == "self" or j.startswith("self.")
                         for j in joined) or \
            any(isinstance(n, ast.Call) and
                isinstance(n.func, ast.Attribute) and
                n.func.attr == "join" and
                isinstance(n.func.value, ast.Name) and
                n.func.value.id == "self"
                for n in ast.walk(cls))
        if not daemonic and not self_joins:
            out.append(Finding(
                self.name, site.path, site.line, cls.col_offset,
                f"Thread subclass `{cls.name}` is non-daemon and never "
                f"joins itself — instances outlive their owners and "
                f"block interpreter exit; pass daemon=True to "
                f"super().__init__ or join in a stop() method"))
        return out


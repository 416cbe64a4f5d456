"""tpulint framework: findings, per-file AST walk, shared import
resolver, inline suppression, baseline bookkeeping.

A checker is a small class registered via :func:`register`; the runner
(:func:`run_lint`) parses every file in scope ONCE into a
:class:`SourceFile` (source text + AST + :class:`ImportResolver` +
suppression map) and hands it to each applicable checker, so N checkers
cost one parse.  Project-level checkers (schema-drift's live probe)
implement :meth:`Checker.check_project` instead and run once per
invocation.

Baseline contract (``tpulint_baseline.json``): entries match findings by
``(check, path, message)`` — NOT by line, so unrelated edits above a
grandfathered finding don't churn the file — as a multiset (two
identical findings need two entries).  ``--update-baseline`` writes the
file deterministically: entries sorted by (check, path, message), paths
repo-relative POSIX, existing justifications preserved.
"""

from __future__ import annotations

import ast
import json
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# Files/dirs the repo-wide walk visits by default (repo-relative).
DEFAULT_PATHS = ("theanompi_tpu", "scripts", "tests")

BASELINE_NAME = "tpulint_baseline.json"

_SUPPRESS_RE = re.compile(r"#\s*tpulint:\s*disable=([A-Za-z0-9_\-, ]+)")


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Finding:
    """One checker hit.  ``fingerprint`` (check, path, message) is the
    baseline-matching identity; ``line``/``col`` are for humans."""

    check: str
    path: str          # repo-relative, POSIX separators
    line: int
    col: int
    message: str

    @property
    def fingerprint(self) -> Tuple[str, str, str]:
        return (self.check, self.path, self.message)

    @property
    def stable_id(self) -> str:
        """Line-insensitive hex id for machine consumers (--format json,
        docs/design.md §12): sha1 over ``check|path|message``, 12 hex
        chars — stable across unrelated edits exactly like the baseline
        identity it hashes."""
        import hashlib
        return hashlib.sha1(
            "|".join(self.fingerprint).encode()).hexdigest()[:12]

    def sort_key(self):
        return (self.path, self.line, self.col, self.check, self.message)

    def to_dict(self) -> dict:
        return {"check": self.check, "path": self.path, "line": self.line,
                "col": self.col, "message": self.message}

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.check}] " \
               f"{self.message}"


# ---------------------------------------------------------------------------
# import resolver (shared by every AST checker)
# ---------------------------------------------------------------------------

class ImportResolver:
    """Maps names/attribute chains in one module to absolute dotted paths.

    ``import jax.numpy as jnp`` → ``jnp`` = ``jax.numpy``;
    ``from jax import lax`` → ``lax`` = ``jax.lax``;
    ``from ..jax_compat import shard_map`` (in
    ``theanompi_tpu/parallel/steps.py``) → ``shard_map`` =
    ``theanompi_tpu.jax_compat.shard_map``.  :meth:`resolve` then turns a
    ``Name``/``Attribute`` node into its absolute dotted path (``None``
    when the base is not an import — locals, ``self``, call results)."""

    def __init__(self, relpath: str, tree: ast.AST):
        self.module = relpath[:-3].replace("/", ".") \
            if relpath.endswith(".py") else relpath.replace("/", ".")
        # package the module lives in, for relative-import resolution
        self.package = self.module.rpartition(".")[0]
        self.aliases: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.aliases[a.asname or a.name.split(".")[0]] = \
                        a.name if a.asname else a.name.split(".")[0]
            elif isinstance(node, ast.ImportFrom):
                base = self.resolve_from_module(node)
                if base is None:
                    continue
                for a in node.names:
                    if a.name == "*":
                        continue
                    self.aliases[a.asname or a.name] = f"{base}.{a.name}"

    def resolve_from_module(self, node: ast.ImportFrom) -> Optional[str]:
        """Absolute module path of a ``from X import ...`` statement."""
        if node.level == 0:
            return node.module
        parts = self.package.split(".") if self.package else []
        up = node.level - 1
        if up > len(parts):
            return None
        base_parts = parts[:len(parts) - up] if up else parts
        if node.module:
            base_parts = base_parts + node.module.split(".")
        return ".".join(base_parts) if base_parts else None

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Absolute dotted path of a Name/Attribute chain, or None."""
        if isinstance(node, ast.Name):
            return self.aliases.get(node.id)
        if isinstance(node, ast.Attribute):
            base = self.resolve(node.value)
            return f"{base}.{node.attr}" if base else None
        return None

    @staticmethod
    def dotted(node: ast.AST) -> Optional[str]:
        """Literal dotted text of a Name/Attribute chain (``self.model.x``),
        resolver-independent — identity for dataflow-ish checks."""
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            base = ImportResolver.dotted(node.value)
            return f"{base}.{node.attr}" if base else None
        return None


# ---------------------------------------------------------------------------
# source files + suppression
# ---------------------------------------------------------------------------

class SourceFile:
    """One parsed module: text, AST, resolver, suppression map."""

    def __init__(self, root: str, relpath: str, text: Optional[str] = None):
        self.root = root
        self.path = relpath.replace(os.sep, "/")
        if text is None:
            with open(os.path.join(root, relpath), encoding="utf-8") as f:
                text = f.read()
        self.text = text
        self.lines = text.splitlines()
        self.tree = ast.parse(text, filename=self.path)
        self.resolver = ImportResolver(self.path, self.tree)
        self._suppress = self._parse_suppressions(self.lines)

    @staticmethod
    def _parse_suppressions(lines: Sequence[str]) -> Dict[int, set]:
        """``# tpulint: disable=a,b`` inline suppresses checks a,b on that
        line; on a comment-only line it suppresses them on the NEXT line."""
        out: Dict[int, set] = {}
        for i, line in enumerate(lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if not m:
                continue
            checks = {c.strip() for c in m.group(1).split(",") if c.strip()}
            target = i + 1 if line.split("#", 1)[0].strip() == "" else i
            out.setdefault(target, set()).update(checks)
        return out

    def suppressed(self, line: int, check: str) -> bool:
        s = self._suppress.get(line)
        return bool(s) and (check in s or "all" in s)


# ---------------------------------------------------------------------------
# checker registry
# ---------------------------------------------------------------------------

CHECKERS: Dict[str, "Checker"] = {}


class Checker:
    """Base checker.  Subclasses set ``name``/``description`` and override
    :meth:`check_file` (per-file AST walk), :meth:`check_program` (the
    whole-program pass — receives the shared
    :class:`~.engine.ProgramIndex`, built once per invocation), and/or
    :meth:`check_project` (one run per invocation — live-object probes).
    A project-only checker sets ``reads_files = False`` so a run
    restricted to it (``--only schema-drift``, the shim's mode) skips
    the repo-wide parse — and its parse-error findings — entirely.
    ``needs_engine = True`` asks the runner for the shared call-graph
    index.  ``disk_scoped`` lists repo-relative paths (or glob patterns)
    the checker reads beyond the lint selection — the runner folds them
    into partial runs (``--diff``, explicit paths) and into the result
    cache's content hash so a disk-scoped checker can neither miss its
    context nor serve stale cached verdicts."""

    name = "checker"
    description = ""
    reads_files = True
    needs_engine = False
    disk_scoped: Sequence[str] = ()

    def applies_to(self, path: str) -> bool:
        return True

    def check_file(self, sf: SourceFile) -> Iterable[Finding]:
        return ()

    def check_program(self, index) -> Iterable[Finding]:
        """Whole-program pass over the shared ProgramIndex."""
        return ()

    def check_project(self, files: List[SourceFile]) -> Iterable[Finding]:
        return ()


def register(cls):
    """Class decorator: instantiate and add to the registry."""
    inst = cls()
    assert inst.name not in CHECKERS, f"duplicate checker {inst.name!r}"
    CHECKERS[inst.name] = inst
    return cls


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def iter_py_paths(root: str, paths: Optional[Sequence[str]] = None
                  ) -> List[str]:
    """Repo-relative paths of every ``.py`` under ``paths`` (files or
    dirs), sorted within each root for deterministic output."""
    out: List[str] = []
    for p in (paths or DEFAULT_PATHS):
        full = os.path.join(root, p)
        if os.path.isfile(full):
            out.append(os.path.relpath(full, root))
            continue
        for dirpath, dirnames, filenames in os.walk(full):
            dirnames[:] = sorted(d for d in dirnames
                                 if d != "__pycache__"
                                 and not d.startswith("."))
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.relpath(os.path.join(dirpath, fn),
                                               root))
    return out


def collect_files(root: str, paths: Optional[Sequence[str]] = None
                  ) -> List[SourceFile]:
    """Parse every ``.py`` under ``paths``; raises on a syntax error (use
    :func:`run_lint` for the finding-producing wrapper)."""
    return [SourceFile(root, rel) for rel in iter_py_paths(root, paths)]


def run_lint(root: str, paths: Optional[Sequence[str]] = None,
             only: Optional[Sequence[str]] = None,
             disable: Optional[Sequence[str]] = None,
             file_cache: Optional[Dict[str, List["Finding"]]] = None
             ) -> List[Finding]:
    """Run the registered checkers over the file set; returns findings
    sorted by (path, line).  Suppressed findings are dropped here, so
    checkers never need to know about the comment syntax.

    ``file_cache`` (the ``scripts/lint.py`` result cache): per-path
    findings of the FILE-scoped checkers from a previous run over
    byte-identical content — those paths skip :meth:`Checker.check_file`
    and splice the cached findings in (already suppression-filtered,
    since suppression is a function of the unchanged file content).
    Program/project checkers always run live."""
    selected = {n: c for n, c in CHECKERS.items()
                if (only is None or n in only)
                and (disable is None or n not in disable)}
    unknown = [n for n in (list(only or []) + list(disable or []))
               if n not in CHECKERS]
    if unknown:
        raise KeyError(f"unknown checker(s) {unknown}; have "
                       f"{sorted(CHECKERS)}")

    files: List[SourceFile] = []
    findings: List[Finding] = []
    if any(c.reads_files for c in selected.values()):
        for rel in iter_py_paths(root, paths):
            try:
                files.append(SourceFile(root, rel))
            except SyntaxError as e:
                findings.append(Finding(
                    "parse-error", rel.replace(os.sep, "/"),
                    int(e.lineno or 1), 0, f"cannot parse: {e.msg}"))

    index = None
    if files and any(c.needs_engine for c in selected.values()):
        from .engine import ProgramIndex
        index = ProgramIndex(files)

    by_path = {sf.path: sf for sf in files}
    cached_paths = set(file_cache or ())
    for name in sorted(selected):
        checker = selected[name]
        for sf in files:
            if not checker.applies_to(sf.path):
                continue
            if sf.path in cached_paths:
                continue      # spliced in below, once per path
            for f in checker.check_file(sf):
                if not sf.suppressed(f.line, f.check):
                    findings.append(f)
        if index is not None and checker.needs_engine:
            for f in checker.check_program(index):
                sf = by_path.get(f.path)
                if sf is None or not sf.suppressed(f.line, f.check):
                    findings.append(f)
        for f in checker.check_project(files):
            # project-level findings honor the same inline suppression
            # when they anchor to a file the run parsed
            sf = by_path.get(f.path)
            if sf is None or not sf.suppressed(f.line, f.check):
                findings.append(f)
    for path in cached_paths & set(by_path):
        findings.extend(f for f in file_cache[path]
                        if f.check in selected or f.check == "parse-error")
    findings.sort(key=Finding.sort_key)
    return findings


def file_scoped_checkers(selected: Optional[Dict[str, "Checker"]] = None
                         ) -> List[str]:
    """Names of checkers whose findings are a pure function of ONE file
    (overridden :meth:`Checker.check_file`) — the set the per-file
    result cache may memoize."""
    pool = selected if selected is not None else CHECKERS
    return sorted(n for n, c in pool.items()
                  if type(c).check_file is not Checker.check_file)


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

def load_baseline(path: str) -> List[dict]:
    if not os.path.exists(path):
        return []
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return list(data.get("entries", []))


def save_baseline(path: str, findings: Sequence[Finding],
                  old_entries: Sequence[dict] = ()) -> List[dict]:
    """Write the baseline deterministically (sorted, path-relative),
    carrying justifications over from matching old entries."""
    just = {}
    for e in old_entries:
        key = (e.get("check"), e.get("path"), e.get("message"))
        just.setdefault(key, []).append(
            e.get("justification", "TODO: justify"))
    entries = []
    for f in sorted(findings, key=lambda f: (f.check, f.path, f.message,
                                             f.line)):
        pool = just.get(f.fingerprint)
        entries.append({
            "check": f.check, "path": f.path, "line": f.line,
            "message": f.message,
            "justification": pool.pop(0) if pool else "TODO: justify",
        })
    with open(path, "w", encoding="utf-8") as fh:
        json.dump({"version": 1, "entries": entries}, fh, indent=2,
                  sort_keys=True)
        fh.write("\n")
    return entries


def compare_baseline(findings: Sequence[Finding], entries: Sequence[dict]
                     ) -> Tuple[List[Finding], List[Finding], List[dict]]:
    """Multiset match on (check, path, message).  Returns
    ``(new, baselined, stale)``: findings not in the baseline, findings
    covered by it, and baseline entries matching nothing (stale)."""
    pool: Dict[Tuple, List[dict]] = {}
    for e in entries:
        key = (e.get("check"), e.get("path"), e.get("message"))
        pool.setdefault(key, []).append(e)
    new: List[Finding] = []
    matched: List[Finding] = []
    for f in findings:
        bucket = pool.get(f.fingerprint)
        if bucket:
            bucket.pop()
            matched.append(f)
        else:
            new.append(f)
    stale = [e for bucket in pool.values() for e in bucket]
    return new, matched, stale

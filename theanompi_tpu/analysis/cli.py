"""tpulint CLI — the one analysis entry point ``scripts/lint.py`` execs.

Modes:

* default: run the suite, print findings; non-baselined findings fail
  (exit 1), stale baseline entries only warn.
* ``--check-baseline`` (the tier-1 gate): ALSO fail on stale entries —
  the committed baseline must be exact (no drift in either direction).
* ``--update-baseline``: regenerate ``tpulint_baseline.json``
  deterministically (sorted, path-relative), preserving justifications
  of retained entries; new entries get ``TODO: justify``.
* ``--format json`` (``--json`` kept as an alias): machine-readable
  findings + baseline delta; every finding carries a stable
  ``fingerprint`` (schema in docs/design.md §12).
* ``--format sarif``: SARIF 2.1.0 log of the NEW findings (stable
  fingerprints → ``partialFingerprints``) for CI diff annotation;
  ``precommit_lint.sh`` writes one when ``TPULINT_SARIF`` is set.
* ``--only`` / ``--disable``: comma-separated checker names;
  ``--list-checks`` prints the registry.
* ``--diff <ref>``: lint only the ``.py`` files changed vs a git ref
  (``CACHED`` = the staged index vs HEAD — the precommit hook's mode),
  filtered to the repo lint scope.  Partial-run semantics (stale
  baseline entries are not judged, ``--update-baseline`` refuses) and
  the per-file result cache apply, so CI and precommit runs on big
  trees stay sub-second.  Untracked files are invisible to a git diff
  — a full run still covers them.
* ``--no-cache``: bypass the ``.tpulint_cache/`` result cache (on by
  default; keyed on content hashes + the analysis-source fingerprint,
  so it can only ever hit on a byte-identical configuration —
  ``analysis/cache.py``).
* ``--verbose``: list every TODO-justified baseline entry instead of
  the one-line summary.

Exit codes: 0 clean, 1 findings/drift, 2 bad invocation.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

from . import cache as cache_mod
from . import checkers as _checkers  # noqa: F401  (registers the suite)
from .core import (BASELINE_NAME, CHECKERS, DEFAULT_PATHS, Finding,
                   compare_baseline, file_scoped_checkers, iter_py_paths,
                   load_baseline, run_lint, save_baseline)


def _repo_root() -> str:
    # core.py lives at <root>/theanompi_tpu/analysis/cli.py
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _split(value: Optional[str]) -> Optional[List[str]]:
    """Comma-split + group-alias expansion (``--only concurrency`` →
    the four host-concurrency checkers), deduplicated in order."""
    if value is None:
        return None
    from .checkers import CHECK_GROUPS
    out: List[str] = []
    for v in value.split(","):
        v = v.strip()
        if not v:
            continue
        for name in CHECK_GROUPS.get(v, (v,)):
            if name not in out:
                out.append(name)
    return out


def _lint_scope(path: str) -> bool:
    """Is a repo-relative path inside the default lint scope?"""
    for d in DEFAULT_PATHS:
        if path == d or path.startswith(d.rstrip("/") + "/"):
            return True
    return False


def _git_changed(root: str, ref: str):
    """Repo-relative ``.py`` paths changed vs ``ref`` (``CACHED`` = the
    staged index vs HEAD), deletions excluded.  Git runs in ``root``
    when it is a repository, else in the cwd — the precommit hook lints
    a temp checkout of the index (no ``.git``) from the repo root, so
    the diff is computed against the real repository either way.
    Returns ``(paths, None)`` or ``(None, error message)``."""
    import subprocess
    # .git is a DIRECTORY in a primary checkout but a FILE in worktrees
    # and submodules — exists() covers all three; a non-repo root (the
    # precommit hook's temp index checkout) falls back to the cwd
    git_root = root if os.path.exists(os.path.join(root, ".git")) \
        else os.getcwd()
    cmd = ["git", "-C", git_root, "diff", "--name-only",
           "--diff-filter=d"]
    cmd.append("--cached" if ref == "CACHED" else ref)
    cmd += ["--", "*.py"]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=60)
    except (OSError, subprocess.TimeoutExpired) as e:
        return None, f"git unavailable ({e!r})"
    if out.returncode != 0:
        return None, (out.stderr.strip().splitlines() or
                      [f"git diff exited {out.returncode}"])[-1]
    return [ln.strip().replace(os.sep, "/")
            for ln in out.stdout.splitlines() if ln.strip()], None


def _cached_run(root, paths, only, disable, cache_dir=None):
    """Run the suite through the result cache.  Returns
    ``(findings, status)`` with status in hit/miss/off (off = the cache
    store is unusable)."""
    unknown = [n for n in (list(only or []) + list(disable or []))
               if n not in CHECKERS]
    if unknown:
        raise KeyError(f"unknown checker(s) {unknown}; have "
                       f"{sorted(CHECKERS)}")
    selected = sorted(n for n in CHECKERS
                      if (only is None or n in only)
                      and (disable is None or n not in disable))
    rels = iter_py_paths(root, paths)
    lint_rels = {r.replace(os.sep, "/") for r in rels}
    # EVERY file a disk-scoped checker loads beyond the lint selection
    # (live-probe targets, ops/ kernels) must
    # key the cache even on partial runs whose path set does not cover
    # it — but they are NOT part of the linted set then, so no per-file
    # entry may be stored for them (it would read as "no findings" to a
    # later full run).  Omitting one would let a stale tree hit mask a
    # drift the checker exists to catch.  Checkers declare the set via
    # ``Checker.disk_scoped`` (paths or glob patterns).
    disk_extra: List[str] = []
    for name in selected:
        for pat in CHECKERS[name].disk_scoped:
            if any(ch in pat for ch in "*?["):
                import glob as _glob
                probes = sorted(
                    os.path.relpath(m, root).replace(os.sep, "/")
                    for m in _glob.glob(os.path.join(root, pat))
                    if m.endswith(".py"))
            else:
                probes = [pat]
            for probe in probes:
                if probe not in lint_rels and probe not in disk_extra \
                        and os.path.exists(os.path.join(root, probe)):
                    disk_extra.append(probe)
    if disk_extra:
        rels = list(rels) + disk_extra
    hashes = cache_mod.file_hashes(root, rels)
    afp = cache_mod.analysis_fingerprint()
    store = cache_mod.LintCache(root, cache_dir)
    tkey = cache_mod.tree_key(afp, selected, list(paths or []), hashes)
    cached = store.load_tree(tkey)
    if cached is not None:
        return cached, "hit"

    # tree miss: splice per-file hits for the file-scoped checkers and
    # run everything else live
    fsc = [n for n in file_scoped_checkers() if n in selected]
    fkeys = {rel: cache_mod.file_key(afp, fsc, sha)
             for rel, sha in hashes}
    file_cache: Dict[str, List[Finding]] = {}
    for rel, key in fkeys.items():
        hit = store.load_file(key)
        if hit is not None:
            file_cache[rel] = hit
    findings = run_lint(root, paths=paths, only=only, disable=disable,
                        file_cache=file_cache or None)
    by_path: Dict[str, List[Finding]] = {}
    for f in findings:
        if f.check in fsc:
            by_path.setdefault(f.path, []).append(f)
    for rel, key in fkeys.items():
        if rel not in file_cache and rel in lint_rels:
            store.store_file(key, by_path.get(rel, []))
    store.store_tree(tkey, findings)
    return findings, "miss"


def _sarif_log(new: List[Finding]) -> dict:
    """Minimal SARIF 2.1.0 log over the NEW findings (the baseline is
    tpulint's own suppression layer — CI annotates what would fail the
    gate).  ``ruleId`` is the checker name; ``partialFingerprints``
    carries each finding's stable id so SARIF consumers track a finding
    across runs the way the baseline does."""
    rule_ids = sorted({f.check for f in new})
    rules = [{
        "id": rid,
        "shortDescription": {
            "text": CHECKERS[rid].description if rid in CHECKERS
            else rid},
    } for rid in rule_ids]
    results = [{
        "ruleId": f.check,
        "level": "error",
        "message": {"text": f.message},
        "locations": [{
            "physicalLocation": {
                "artifactLocation": {"uri": f.path},
                "region": {"startLine": max(f.line, 1),
                           "startColumn": max(f.col + 1, 1)},
            },
        }],
        "partialFingerprints": {"tpulintFingerprint/v1": f.stable_id},
    } for f in new]
    return {
        "$schema": "https://json.schemastore.org/sarif-2.1.0.json",
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "tpulint",
                "informationUri": "docs/design.md",
                "rules": rules,
            }},
            "results": results,
        }],
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="lint.py",
        description="tpulint — whole-program invariant checkers "
                    "(docs/design.md §12)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the repo set)")
    ap.add_argument("--root", default=None,
                    help="repo root (default: inferred from this file)")
    ap.add_argument("--format", default=None, dest="fmt",
                    choices=("human", "json", "sarif"),
                    help="output format (default: human; sarif emits "
                         "a SARIF 2.1.0 log of the NEW findings for "
                         "CI diff annotation)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="alias for --format json")
    ap.add_argument("--only", default=None,
                    help="comma-separated checker names (or the "
                         "'concurrency' group) to run")
    ap.add_argument("--disable", default=None,
                    help="comma-separated checker names (or group) "
                         "to skip")
    ap.add_argument("--diff", default=None, metavar="REF",
                    help="lint only .py files changed vs the git ref "
                         "(CACHED = staged index vs HEAD); partial-run "
                         "semantics")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: <root>/{BASELINE_NAME})")
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--check-baseline", action="store_true",
                    help="fail on stale baseline entries too (tier-1 mode)")
    ap.add_argument("--no-cache", action="store_true",
                    help="bypass the .tpulint_cache/ result cache")
    ap.add_argument("--cache-dir", default=None,
                    help="cache directory (default: <root>/"
                         ".tpulint_cache; the precommit hook points "
                         "this at the repo while rooting at a temp "
                         "index checkout)")
    ap.add_argument("--verbose", action="store_true",
                    help="list every TODO-justified baseline entry")
    ap.add_argument("--list-checks", action="store_true")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 2 if e.code not in (0, None) else 0

    if args.list_checks:
        for name in sorted(CHECKERS):
            print(f"{name}: {CHECKERS[name].description}")
        return 0

    as_json = args.as_json or args.fmt == "json"
    root = os.path.abspath(args.root or _repo_root())
    baseline_path = args.baseline or os.path.join(root, BASELINE_NAME)
    if args.diff:
        if args.paths:
            print("lint: --diff and explicit paths are mutually "
                  "exclusive", file=sys.stderr)
            return 2
        if args.update_baseline:
            # refused HERE, not only at the shared partial-run check
            # below: an empty changeset's early exit 0 must not read as
            # "baseline updated" to automation
            print("lint: --update-baseline requires a full run (no "
                  "paths/--diff/--only/--disable)", file=sys.stderr)
            return 2
        changed, err = _git_changed(root, args.diff)
        if changed is None:
            print(f"lint: --diff {args.diff}: {err}", file=sys.stderr)
            return 2
        # scope-filter, and drop paths absent from THIS root (a
        # restricted precommit checkout holds only the staged blobs)
        args.paths = sorted({
            p for p in changed
            if p.endswith(".py") and _lint_scope(p)
            and os.path.exists(os.path.join(root, p))})
        if not args.paths:
            print(f"lint: no changed python files in lint scope vs "
                  f"{args.diff}")
            return 0
    # a typo'd explicit path must not read as "linted clean" — the
    # default set is allowed to have absent members (bare roots), an
    # explicitly named one is not
    missing = [p for p in args.paths
               if not os.path.exists(os.path.join(root, p))]
    if missing:
        print(f"lint: no such path(s): {', '.join(missing)}",
              file=sys.stderr)
        return 2
    try:
        if args.no_cache:
            findings = run_lint(root, paths=args.paths or None,
                                only=_split(args.only),
                                disable=_split(args.disable))
            cache_status = "off"
        else:
            findings, cache_status = _cached_run(
                root, args.paths or None, _split(args.only),
                _split(args.disable), cache_dir=args.cache_dir)
    except KeyError as e:
        print(f"lint: {e.args[0]}", file=sys.stderr)
        return 2

    entries = load_baseline(baseline_path)
    # a partial run (explicit paths / --only) must not call untouched
    # baseline entries stale — staleness is only meaningful repo-wide
    partial = bool(args.paths or args.only or args.disable)
    new, matched, stale = compare_baseline(findings, entries)
    if partial:
        stale = []

    if args.update_baseline:
        if partial:
            # a partial run only sees a slice of the findings — writing
            # it out would silently drop every entry outside the slice
            print("lint: --update-baseline requires a full run (no "
                  "paths/--diff/--only/--disable)", file=sys.stderr)
            return 2
        saved = save_baseline(baseline_path, findings, entries)
        print(f"tpulint: baseline written to "
              f"{os.path.relpath(baseline_path, root)} "
              f"({len(saved)} entries)")
        todo = sum(1 for e in saved
                   if e["justification"].startswith("TODO"))
        if todo:
            print(f"tpulint: {todo} entries need a justification "
                  "(edit the file)", file=sys.stderr)
        return 0

    # the documented baseline contract: entries carry a real one-line
    # justification; TODO placeholders nag on EVERY run, not just the
    # --update-baseline that wrote them — but as ONE summary line, not
    # a per-entry flood (--verbose restores the full list)
    todo = [e for e in entries
            if str(e.get("justification", "")).startswith("TODO")]
    if todo:
        # stderr, so json stdout stays machine-readable
        if args.verbose:
            for e in todo:
                print(f"baseline entry needs a justification: "
                      f"{e.get('check')}: {e.get('path')}: "
                      f"{e.get('message')}", file=sys.stderr)
        else:
            n = len(todo)
            print(f"tpulint: {n} baseline entr{'y' if n == 1 else 'ies'} "
                  "with a TODO placeholder — each needs a justification "
                  "(--verbose lists them)", file=sys.stderr)

    if args.fmt == "sarif":
        print(json.dumps(_sarif_log(new), indent=2, sort_keys=True))
    elif as_json:
        def enrich(f: Finding) -> dict:
            d = f.to_dict()
            d["fingerprint"] = f.stable_id
            return d

        print(json.dumps({
            "version": 2,
            "findings": [enrich(f) for f in findings],
            "new": [enrich(f) for f in new],
            "baselined": len(matched),
            "stale_baseline": stale,
            "cache": cache_status,
        }, indent=2, sort_keys=True))
    else:
        for f in new:
            print(f.render())
        for e in stale:
            print(f"stale baseline entry: {e.get('check')}: "
                  f"{e.get('path')}: {e.get('message')}", file=sys.stderr)
        status = (f"tpulint: {len(findings)} finding(s) — {len(new)} new, "
                  f"{len(matched)} baselined, {len(stale)} stale baseline "
                  f"entr(ies) [cache {cache_status}]")
        print(status)

    if new:
        return 1
    if stale and args.check_baseline:
        return 1
    return 0

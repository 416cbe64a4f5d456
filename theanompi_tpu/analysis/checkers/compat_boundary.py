"""compat-boundary: shard_map/pvary/pcast go through ``jax_compat`` only.

The invariant (docs/design.md §12): jax has moved ``shard_map`` and the
vma markers (``lax.pvary`` → ``lax.pcast``) between releases, so every
call site routes through ``theanompi_tpu/jax_compat.py`` (``shard_map``,
``vary``; ``steps._vary`` is the latter's alias) and the next move is
absorbed in one file.  A direct ``jax.shard_map`` / ``lax.pvary`` /
``lax.pcast`` reference anywhere else is flagged.

Flagged: attribute references resolving to the banned dotted names, and
imports from ``jax.experimental.shard_map`` (the legacy location).  Name
USES of a banned imported alias are not re-flagged; the import line
carries the finding.
"""

from __future__ import annotations

import ast
from typing import List

from ..core import Checker, Finding, SourceFile, register

BANNED = {
    "jax.shard_map",
    "jax.lax.pvary",
    "jax.lax.pcast",
}

LEGACY_MODULE = "jax.experimental.shard_map"

SHIM_PATH = "theanompi_tpu/jax_compat.py"


@register
class CompatBoundaryChecker(Checker):
    name = "compat-boundary"
    description = ("direct jax.shard_map/lax.pvary/lax.pcast references "
                   "outside jax_compat.py")

    def applies_to(self, path: str) -> bool:
        # the shim itself is the one sanctioned home of these names
        return not path.endswith("jax_compat.py")

    def check_file(self, sf: SourceFile):
        findings: List[Finding] = []
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.ImportFrom):
                base = sf.resolver.resolve_from_module(node)
                if base == LEGACY_MODULE or (
                        base and base.startswith(LEGACY_MODULE + ".")):
                    findings.append(Finding(
                        self.name, sf.path, node.lineno, node.col_offset,
                        f"import from `{LEGACY_MODULE}` outside "
                        "jax_compat.py — route through the shim "
                        "(theanompi_tpu.jax_compat.shard_map)"))
                    continue
                # `from jax import shard_map` / `from jax.lax import
                # pvary` bind the banned name without any Attribute node
                for a in (node.names if base else ()):
                    full = f"{base}.{a.name}"
                    if full in BANNED:
                        findings.append(Finding(
                            self.name, sf.path, node.lineno,
                            node.col_offset,
                            f"import of `{full}` outside jax_compat.py "
                            "— use theanompi_tpu.jax_compat (shard_map, "
                            "vary)"))
                continue
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == LEGACY_MODULE or \
                            a.name.startswith(LEGACY_MODULE + "."):
                        findings.append(Finding(
                            self.name, sf.path, node.lineno,
                            node.col_offset,
                            f"import of `{a.name}` outside jax_compat.py "
                            "— route through the shim"))
                continue
            if isinstance(node, ast.Attribute):
                resolved = sf.resolver.resolve(node)
                if resolved in BANNED:
                    findings.append(Finding(
                        self.name, sf.path, node.lineno, node.col_offset,
                        f"direct `{resolved}` reference outside "
                        "jax_compat.py — use theanompi_tpu.jax_compat "
                        "(shard_map, vary)"))
        return findings

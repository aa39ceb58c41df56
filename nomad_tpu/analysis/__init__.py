"""Invariant analysis plane: AST lint for the repo's own disciplines.

``python -m nomad_tpu.analysis --check`` runs four rule families over
the whole non-vendor tree (the ``nomad_tpu`` package plus the root
``__graft_entry__.py`` driver; tests are exempt — they
deliberately arm knobs and hold locks in shapes production code must
not):

- **lock-discipline** (``lockrules``) — reconstructs ``with <lock>:``
  regions per module, flags blocking operations held under them
  (fsync, socket send/recv, ``jax.device_get``/``block_until_ready``,
  subprocess, ``time.sleep`` — the exact PR 9 fsync-under-lock and
  PR 10 drain-under-lock bug classes) and builds the static lock-order
  graph, failing on cycles;
- **jax-discipline** (``jaxrules``) — donated-buffer reuse after a
  ``donate_argnums`` call site, host-sync calls in the hot-path
  modules (``ops/``, ``parallel/``), and jitted entry points in
  modules that never register with ``kernels.note_signature``
  (compile-audit escapes);
- **guard-coverage** (``guardrules``) — every native twin, columnar
  mirror, and resident device mirror must be paired with a registered
  differential guard, a breaker feed, and an env kill-switch, checked
  structurally against ``ops/guards.py``;
- **knob-registry** (``knobrules``) — every ``NOMAD_TPU_*`` read goes
  through ``utils/knobs.py``; ad-hoc ``os.environ`` reads, undeclared
  knob names, and README-table drift all fail.

Suppression is by **justified allowlist** (``allowlist.txt`` next to
this file): one line per violation key with a written reason; stale
entries (matching nothing) fail the pass so the file cannot rot.
Violation keys are stable across line-number drift:
``rule path::qualname::detail``.
"""
from __future__ import annotations

import ast
import fnmatch
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

__all__ = [
    "Violation", "SourceFile", "Allowlist", "repo_root",
    "iter_source_files", "load_tree", "run_checks", "RULE_FAMILIES",
    "expr_text",
]

RULE_FAMILIES = ("lock-discipline", "jax-discipline",
                 "guard-coverage", "knob-registry")


def expr_text(node: ast.expr) -> Optional[str]:
    """Dotted text of a Name/Attribute chain (``self._lock``,
    ``jax.device_get``) or None for anything dynamic — the shared
    resolver every rule family names expressions with."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = expr_text(node.value)
        return f"{base}.{node.attr}" if base else None
    return None

_HERE = os.path.dirname(os.path.abspath(__file__))


def repo_root() -> str:
    # nomad_tpu/analysis/ -> nomad_tpu/ -> repo root
    return os.path.dirname(os.path.dirname(_HERE))


@dataclass
class Violation:
    rule: str
    path: str          # repo-relative
    line: int
    detail: str        # stable discriminator within (rule, path)
    message: str
    qualname: str = ""

    @property
    def key(self) -> str:
        q = self.qualname or "<module>"
        return f"{self.rule} {self.path}::{q}::{self.detail}"

    def render(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}] {self.message}"
                f"\n    key: {self.key}")


@dataclass
class SourceFile:
    path: str           # repo-relative, forward slashes
    abspath: str
    source: str
    tree: ast.Module
    lines: List[str] = field(default_factory=list)

    def __post_init__(self) -> None:
        if not self.lines:
            self.lines = self.source.splitlines()


class Allowlist:
    """``allowlist.txt``: ``<key-pattern>  # <reason>`` lines.  The key
    pattern is fnmatch-matched against violation keys; every entry must
    carry a reason and must match at least one violation (stale entries
    are themselves violations, so suppressions cannot outlive the code
    they excuse)."""

    def __init__(self, path: str):
        self.path = path
        self.entries: List[Tuple[str, str, int]] = []  # pattern, reason, line
        self.used: Dict[int, int] = {}
        self.malformed: List[Tuple[int, str]] = []
        if os.path.exists(path):
            with open(path, "r", encoding="utf-8") as fh:
                for lineno, raw in enumerate(fh, 1):
                    line = raw.rstrip("\n")
                    if not line.strip() or line.lstrip().startswith("#"):
                        continue
                    if "#" not in line:
                        self.malformed.append(
                            (lineno, "entry has no '# reason' part"))
                        continue
                    pattern, reason = line.split("#", 1)
                    pattern = pattern.strip()
                    reason = reason.strip()
                    if not pattern or not reason:
                        self.malformed.append(
                            (lineno, "empty pattern or empty reason"))
                        continue
                    self.entries.append((pattern, reason, lineno))

    def suppresses(self, violation: Violation) -> bool:
        hit = False
        for i, (pattern, _reason, _ln) in enumerate(self.entries):
            if (violation.key == pattern
                    or fnmatch.fnmatchcase(violation.key, pattern)):
                self.used[i] = self.used.get(i, 0) + 1
                hit = True
        return hit

    def stale_entries(self) -> List[Tuple[str, int]]:
        return [(pattern, ln)
                for i, (pattern, _r, ln) in enumerate(self.entries)
                if i not in self.used]


DEFAULT_ALLOWLIST = os.path.join(_HERE, "allowlist.txt")

EXCLUDE_DIRS = {"__pycache__", ".git", "tests", ".claude"}


def iter_source_files(root: Optional[str] = None) -> List[str]:
    """Repo-relative paths of every non-vendor, non-test Python source."""
    root = root or repo_root()
    out: List[str] = []
    pkg = os.path.join(root, "nomad_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in sorted(dirnames)
                       if d not in EXCLUDE_DIRS]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                out.append(os.path.relpath(
                    os.path.join(dirpath, fn), root).replace(os.sep, "/"))
    if os.path.exists(os.path.join(root, "__graft_entry__.py")):
        out.append("__graft_entry__.py")
    return out


def load_tree(root: Optional[str] = None,
              paths: Optional[List[str]] = None) -> List[SourceFile]:
    root = root or repo_root()
    files: List[SourceFile] = []
    for rel in (paths if paths is not None else iter_source_files(root)):
        abspath = os.path.join(root, rel)
        with open(abspath, "r", encoding="utf-8") as fh:
            source = fh.read()
        files.append(SourceFile(
            path=rel, abspath=abspath, source=source,
            tree=ast.parse(source, filename=rel)))
    return files


def run_checks(root: Optional[str] = None,
               allowlist_path: Optional[str] = None,
               rules: Optional[List[str]] = None,
               ) -> Tuple[List[Violation], List[Violation]]:
    """Run every rule family; returns ``(active, suppressed)``.
    Malformed/stale allowlist entries surface as active ``allowlist``
    violations."""
    from . import guardrules, jaxrules, knobrules, lockrules

    root = root or repo_root()
    if rules:
        unknown = sorted(set(rules) - set(RULE_FAMILIES))
        if unknown:
            # An unknown family name must not run zero rules and report
            # a vacuous "clean".
            raise ValueError(
                f"unknown rule family {unknown} — choose from "
                f"{list(RULE_FAMILIES)}")
    files = load_tree(root)
    all_violations: List[Violation] = []
    families = {
        "lock-discipline": lockrules.check,
        "jax-discipline": jaxrules.check,
        "guard-coverage": guardrules.check,
        "knob-registry": knobrules.check,
    }
    for name, fn in families.items():
        if rules and name not in rules:
            continue
        all_violations.extend(fn(root, files))

    allow = Allowlist(allowlist_path or DEFAULT_ALLOWLIST)
    active: List[Violation] = []
    suppressed: List[Violation] = []
    for v in all_violations:
        (suppressed if allow.suppresses(v) else active).append(v)
    rel_allow = os.path.relpath(allow.path, root).replace(os.sep, "/")
    for lineno, why in allow.malformed:
        active.append(Violation(
            rule="allowlist", path=rel_allow, line=lineno,
            detail=f"malformed:{lineno}",
            message=f"malformed allowlist entry: {why}"))
    if rules is None:  # stale detection only meaningful on a full run
        for pattern, lineno in allow.stale_entries():
            active.append(Violation(
                rule="allowlist", path=rel_allow, line=lineno,
                detail=f"stale:{pattern}",
                message=f"stale allowlist entry matches nothing: "
                        f"{pattern!r} — delete it or fix the pattern"))
    active.sort(key=lambda v: (v.path, v.line, v.rule))
    suppressed.sort(key=lambda v: (v.path, v.line, v.rule))
    return active, suppressed

"""Rule family 4: the env-knob registry.

Every ``NOMAD_TPU_*`` environment variable is declared once in
``utils/knobs.py`` and read only through its accessors.  Three checks:

- **knob-env-read** — an ``os.environ.get`` / ``os.environ[...]`` /
  ``os.getenv`` *read* of a ``NOMAD_TPU_*`` name anywhere outside
  ``utils/knobs.py`` (writes — arming a drill, spawning a child with a
  knob set — are fine; interpreting a knob's value ad hoc is not).
  Names are resolved through module-level string constants
  (``TRACE_ENV = "NOMAD_TPU_TRACE"``) so indirection cannot
  launder a read.
- **knob-unregistered** — any ``NOMAD_TPU_*`` token appearing in a
  Python source (string, comment, knobs accessor argument) that is not
  declared in the registry.  Wildcard doc mentions
  (``NOMAD_TPU_BREAKER_*``, ``NOMAD_TPU_RAFT_{...}_S``) pass via a
  prefix rule: a token that is a strict prefix of registered knobs is
  documentation, not a knob.
- **knob-readme-drift** — the README env-knob table between the
  ``knob-table`` markers must equal ``knobs.render_readme_table()``
  byte-for-byte (regenerate with ``--write-knob-table``).
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional

from . import SourceFile, Violation, expr_text
from .guardrules import _load_by_path, registry_missing

RULE_READ = "knob-env-read"
RULE_UNREG = "knob-unregistered"
RULE_DRIFT = "knob-readme-drift"

KNOBS_PATH = "nomad_tpu/utils/knobs.py"
KNOB_RE = re.compile(r"NOMAD_TPU_[A-Z0-9_]+")

_ACCESSORS = {"get_bool", "get_int", "get_float", "get_str", "raw",
              "lookup"}


def _module_str_constants(tree: ast.Module) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Constant) and isinstance(
                node.value.value, str):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out[tgt.id] = node.value.value
    return out


def _resolve_key(node: ast.expr,
                 consts: Dict[str, str]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return consts.get(node.id)
    return None


def _check_env_reads(sf: SourceFile, consts: Dict[str, str],
                     violations: List[Violation]) -> None:
    for fn_node in ast.walk(sf.tree):
        if not isinstance(fn_node, ast.Call):
            continue
        text = expr_text(fn_node.func) or ""
        key_node = None
        if text in ("os.environ.get", "environ.get", "os.getenv",
                    "getenv"):
            if fn_node.args:
                key_node = fn_node.args[0]
        if key_node is None:
            continue
        key = _resolve_key(key_node, consts)
        if key is None or not key.startswith("NOMAD_TPU_"):
            continue
        qual = _enclosing_name(sf.tree, fn_node)
        violations.append(Violation(
            rule=RULE_READ, path=sf.path, line=fn_node.lineno,
            qualname=qual, detail=key,
            message=f"ad-hoc env read of {key} — go through "
                    f"utils/knobs.py (get_bool/get_int/get_float/"
                    f"get_str, or raw() for save/restore)"))
    # Subscript loads in Load context (os.environ[...] as a read).
    for node in ast.walk(sf.tree):
        if (isinstance(node, ast.Subscript)
                and isinstance(node.ctx, ast.Load)
                and expr_text(node.value) in ("os.environ", "environ")):
            key = _resolve_key(node.slice, consts)
            if key and key.startswith("NOMAD_TPU_"):
                violations.append(Violation(
                    rule=RULE_READ, path=sf.path, line=node.lineno,
                    qualname=_enclosing_name(sf.tree, node),
                    detail=f"subscript:{key}",
                    message=f"ad-hoc env read of {key} via "
                            f"os.environ[...] — go through "
                            f"utils/knobs.py"))


def _enclosing_name(tree: ast.Module, target: ast.AST) -> str:
    best = ""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if (node.lineno <= target.lineno
                    <= (node.end_lineno or node.lineno)):
                best = node.name
    return best


def _prefix_of_registered(token: str, registered) -> bool:
    stripped = token.rstrip("_")
    for name in registered:
        if name != token and (name.startswith(token)
                              or name.startswith(stripped + "_")
                              or name == stripped):
            return True
    return False


def check(root: str, files: List[SourceFile]) -> List[Violation]:
    violations: List[Violation] = []
    missing = registry_missing(root, KNOBS_PATH, RULE_READ)
    if missing is not None:
        return [missing]
    knobs = _load_by_path(root, KNOBS_PATH, "_analysis_knobs2")
    registered = {k.name for k in knobs.registered()}

    for sf in files:
        consts = _module_str_constants(sf.tree)
        if sf.path != KNOBS_PATH:
            _check_env_reads(sf, consts, violations)
        # Unregistered tokens anywhere in the source (incl. comments).
        seen = set()
        for lineno, line in enumerate(sf.lines, 1):
            for match in KNOB_RE.finditer(line):
                token = match.group(0).rstrip("_")
                if token in registered or token in seen:
                    continue
                if _prefix_of_registered(match.group(0), registered):
                    continue
                seen.add(token)
                violations.append(Violation(
                    rule=RULE_UNREG, path=sf.path, line=lineno,
                    detail=token,
                    message=f"{token} is not declared in "
                            f"utils/knobs.py — register it (name, "
                            f"type, default, doc) before use"))

    # README drift.
    readme = os.path.join(root, "README.md")
    expected = knobs.render_readme_table()
    drift = None
    if not os.path.exists(readme):
        drift = "README.md missing"
    else:
        with open(readme, "r", encoding="utf-8") as fh:
            text = fh.read()
        begin, end = knobs.TABLE_BEGIN, knobs.TABLE_END
        if begin not in text or end not in text:
            drift = ("README.md has no knob-table markers — run "
                     "python -m nomad_tpu.analysis --write-knob-table")
        else:
            start = text.index(begin)
            stop = text.index(end) + len(end)
            if text[start:stop] != expected:
                drift = ("README knob table out of sync with "
                         "utils/knobs.py — regenerate with "
                         "python -m nomad_tpu.analysis "
                         "--write-knob-table")
    if drift is not None:
        violations.append(Violation(
            rule=RULE_DRIFT, path="README.md", line=1,
            detail="knob-table", message=drift))
    return violations

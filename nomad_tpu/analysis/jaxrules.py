"""Rule family 2: JAX device discipline (hot-path modules).

Three invariants over ``ops/`` and ``parallel/``:

- **jax-donated-reuse** — after calling a jitted function created with
  ``donate_argnums``, the buffer passed at a donated position is dead
  (XLA aliased it into the output); reading the old variable again in
  the same function is a use-after-donation.  Detected in-module: jit
  objects built with ``jax.jit(..., donate_argnums=...)`` (including
  ``functools.partial(jax.jit, donate_argnums=...)`` decorators), call
  sites passing plain names at donated positions, and any later load
  of that name without an intervening rebind.
- **jax-host-sync** — ``jax.device_get`` / ``.block_until_ready()``
  force a device→host sync; in the hot-path modules every such call
  must be one of the sanctioned single-fetch sites (allowlisted with
  a reason) — anything else is a stealth second fetch, the exact
  regression class the one-dispatch/one-fetch contract guards.
- **jax-note-signature** — every module that builds a jit program must
  register invocation signatures with ``kernels.note_signature`` (the
  compile-audit seam); a jit call site in a module that never calls
  ``note_signature`` is a compile-audit escape: new program shapes
  would not show up in the ``batch.compiles`` gauge or the
  ``--check`` compile-budget ceiling.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from . import SourceFile, Violation, expr_text

RULE_DONATED = "jax-donated-reuse"
RULE_HOSTSYNC = "jax-host-sync"
RULE_NOTESIG = "jax-note-signature"

HOT_PREFIXES = ("nomad_tpu/ops/", "nomad_tpu/parallel/")


def _is_jit_call(node: ast.Call) -> bool:
    """``jax.jit(...)`` or ``functools.partial(jax.jit, ...)``."""
    text = expr_text(node.func)
    if text in ("jax.jit", "jit"):
        return True
    if text in ("functools.partial", "partial") and node.args:
        return expr_text(node.args[0]) in ("jax.jit", "jit")
    return False


def _donate_argnums(node: ast.Call) -> Optional[Tuple[int, ...]]:
    for kw in node.keywords:
        if kw.arg == "donate_argnums":
            try:
                val = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                return ()
            if isinstance(val, int):
                return (val,)
            return tuple(int(v) for v in val)
    return None


class _DonatedCallables(ast.NodeVisitor):
    """Names in a module bound to donated jit programs: assignments
    ``f = jax.jit(g, donate_argnums=...)`` and functions decorated with
    ``functools.partial(jax.jit, donate_argnums=...)``."""

    def __init__(self) -> None:
        self.donated: Dict[str, Tuple[int, ...]] = {}

    def visit_Assign(self, node: ast.Assign) -> None:
        if isinstance(node.value, ast.Call) and _is_jit_call(node.value):
            nums = _donate_argnums(node.value)
            if nums:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        self.donated[tgt.id] = nums
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        for dec in node.decorator_list:
            if isinstance(dec, ast.Call) and _is_jit_call(dec):
                nums = _donate_argnums(dec)
                if nums:
                    self.donated[node.name] = nums
        self.generic_visit(node)


def _check_donated_reuse(sf: SourceFile,
                         violations: List[Violation]) -> None:
    finder = _DonatedCallables()
    finder.visit(sf.tree)
    # Local ``f = jax.jit(..., donate_argnums=...)`` inside functions
    # are caught by the same visitor (it walks the whole module).
    if not finder.donated:
        return
    for fn in ast.walk(sf.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # Events ordered by (line, kind rank): a donation lands at the
        # call's END line and precedes a same-line rebind (evaluation
        # order of ``buf = _apply(buf, ...)``); the call's own argument
        # loads are skipped by node identity.
        events: List[Tuple[int, int, str, str]] = []
        arg_nodes = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                callee = expr_text(node.func)
                nums = finder.donated.get(callee or "")
                if nums:
                    for idx in nums:
                        if idx < len(node.args) and isinstance(
                                node.args[idx], ast.Name):
                            arg_nodes.add(id(node.args[idx]))
                            events.append((node.end_lineno or
                                           node.lineno, 0, "donate",
                                           node.args[idx].id))
        for node in ast.walk(fn):
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Store):
                    events.append((node.lineno, 1, "bind", node.id))
                elif (isinstance(node.ctx, ast.Load)
                        and id(node) not in arg_nodes):
                    events.append((node.lineno, 2, "load", node.id))
        events.sort(key=lambda e: (e[0], e[1]))
        dead: Dict[str, int] = {}
        for line, _rank, kind, name in events:
            if kind == "donate":
                dead[name] = line
            elif kind == "bind":
                dead.pop(name, None)
            elif kind == "load" and name in dead \
                    and line > dead[name]:
                violations.append(Violation(
                    rule=RULE_DONATED, path=sf.path, line=line,
                    qualname=fn.name,
                    detail=f"{name}:donated-at:{dead[name] - fn.lineno}",
                    message=f"{name!r} was passed at a donated "
                            f"position on line {dead[name]} and read "
                            f"again here — the buffer is dead after "
                            f"donation (use the aliased result, or "
                            f"rebind before reuse)"))
                dead.pop(name)  # one report per donation


def _check_host_sync(sf: SourceFile,
                     violations: List[Violation]) -> None:
    for fn in ast.walk(sf.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            text = expr_text(node.func) or ""
            attr = text.rsplit(".", 1)[-1]
            if text == "jax.device_get" or attr == "block_until_ready":
                violations.append(Violation(
                    rule=RULE_HOSTSYNC, path=sf.path, line=node.lineno,
                    qualname=fn.name,
                    detail=f"{attr}",
                    message=f"host-sync call {attr} in hot-path "
                            f"module — every device→host sync must be "
                            f"a sanctioned single-fetch site "
                            f"(allowlist with a reason)"))


def _enclosing_func(tree: ast.Module, target: ast.AST) -> str:
    best = ""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if (node.lineno <= target.lineno
                    <= (node.end_lineno or node.lineno)):
                best = node.name
    return best


def _check_note_signature(sf: SourceFile,
                          violations: List[Violation]) -> None:
    has_note = False
    jit_sites: List[Tuple[int, str]] = []
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Call):
            text = expr_text(node.func) or ""
            if text.rsplit(".", 1)[-1] in ("note_signature",
                                           "program_call"):
                has_note = True
            elif _is_jit_call(node):
                qual = _enclosing_func(sf.tree, node)
                jit_sites.append((node.lineno, qual))
    if jit_sites and not has_note:
        seen = set()
        for line, qual in jit_sites:
            # Keyed by enclosing function, not line number — allowlist
            # keys must survive line drift (one key per function, not
            # per call site).
            detail = f"jit-in:{qual or '<module>'}"
            if detail in seen:
                continue
            seen.add(detail)
            violations.append(Violation(
                rule=RULE_NOTESIG, path=sf.path, line=line,
                qualname=qual, detail=detail,
                message="module builds a jit program but never calls "
                        "kernels.note_signature — compile-audit "
                        "escape: new program shapes will not show in "
                        "batch.compiles or the --check compile "
                        "budget"))


def check(root: str, files: List[SourceFile]) -> List[Violation]:
    violations: List[Violation] = []
    for sf in files:
        if not sf.path.startswith(HOT_PREFIXES):
            continue
        _check_donated_reuse(sf, violations)
        _check_host_sync(sf, violations)
        _check_note_signature(sf, violations)
    return violations

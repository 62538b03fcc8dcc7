"""Exception-hygiene rule.

The serving stack's fault-tolerance contract (ISSUE 2): no failure is
silently swallowed — a request either completes or its waiter gets an
explicit error. Bare ``except:`` is banned everywhere in ``dllama_tpu/``;
broad handlers in ``runtime/``/``serve/`` must re-raise, surface to a
waiter (``.error`` assignment, ``done.set``/``_fail_*``/``_on_crash``/
``os._exit``), or justify themselves with ``# noqa: BLE001 — reason``.
"""

from __future__ import annotations

import ast

from .core import Finding, Project, rule

PKG = "dllama_tpu"
STRICT_DIRS = (f"{PKG}/runtime", f"{PKG}/serve")
_SURFACING_CALLS = {"_fail_all", "_fail_request", "_on_crash", "_exit"}


def _is_broad(node: ast.ExceptHandler) -> bool:
    def broad_name(t: ast.expr) -> bool:
        return isinstance(t, ast.Name) and t.id in ("Exception",
                                                    "BaseException")

    t = node.type
    if t is None:
        return False
    if broad_name(t):
        return True
    return isinstance(t, ast.Tuple) and any(broad_name(e) for e in t.elts)


def _walk_same_scope(stmts):
    """Walk without descending into nested defs — a ``raise`` inside a
    callback defined in the handler does not surface THIS failure."""
    todo = list(stmts)
    while todo:
        node = todo.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            todo.append(child)


def _handler_ok(node: ast.ExceptHandler, src_lines: list[str]) -> bool:
    line = src_lines[node.lineno - 1]
    if "noqa: BLE001" in line:
        return True
    for sub in _walk_same_scope(node.body):
        if isinstance(sub, ast.Raise):
            return True
        if isinstance(sub, ast.Assign):
            for tgt in sub.targets:
                if isinstance(tgt, ast.Attribute) and tgt.attr == "error":
                    return True
        if isinstance(sub, ast.Call):
            f = sub.func
            name = f.attr if isinstance(f, ast.Attribute) else (
                f.id if isinstance(f, ast.Name) else "")
            if name in _SURFACING_CALLS:
                return True
            if (name == "set" and isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Attribute)
                    and f.value.attr == "done"):
                return True
    return False


def check(project: Project) -> tuple[list[Finding], str]:
    findings: list[Finding] = []
    n_handlers = 0
    files = project.walk(PKG)
    findings += project.parse_failures(files, "exception-hygiene")
    for sf in files:
        if sf.tree is None:
            continue
        strict = any(sf.rel.startswith(d + "/") for d in STRICT_DIRS)
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                findings.append(Finding(
                    "exception-hygiene", sf.rel, node.lineno,
                    "bare `except:` (catches KeyboardInterrupt/"
                    "SystemExit; name the exception)"))
                continue
            if strict and _is_broad(node):
                n_handlers += 1
                if not _handler_ok(node, sf.lines):
                    findings.append(Finding(
                        "exception-hygiene", sf.rel, node.lineno,
                        "`except Exception` must set a request .error, "
                        "re-raise, surface via done.set/_fail_*, or "
                        "carry `# noqa: BLE001 — <reason>` on the "
                        "except line"))
    return findings, (f"no bare excepts; {n_handlers} broad handlers in "
                      f"runtime/+serve/ all surface their failures")


rule("exception-hygiene",
     "no bare excepts; broad handlers in runtime//serve/ surface their "
     "failures to a waiter")(check)

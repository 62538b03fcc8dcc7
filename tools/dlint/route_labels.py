"""Route-label rule.

``serve/api.py`` folds unknown paths into the ``other`` route label; that
only works if every route a handler matches is in ``_ROUTES``, and the
``GET /debug`` index (``_DEBUG_INDEX``) is closed-world against the
``/debug/*`` routes, both directions.
"""

from __future__ import annotations

import ast

from .core import Finding, Project, rule

API = "dllama_tpu/serve/api.py"


def _mentions_path(node: ast.expr) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in ("path", "_route"):
            return True
        if isinstance(sub, ast.Name) and sub.id == "path":
            return True
    return False


def _route_literals(node: ast.expr) -> list[str]:
    return [sub.value for sub in ast.walk(node)
            if isinstance(sub, ast.Constant) and isinstance(sub.value, str)
            and sub.value.startswith("/")]


def check(project: Project, api_rel: str = API) -> tuple[list[Finding], str]:
    findings: list[Finding] = []

    def f(msg, lineno=0):
        findings.append(Finding("route-labels", api_rel, lineno, msg))

    sf = project.file(api_rel)
    if sf is None or sf.tree is None:
        f(f"{api_rel} missing or unparseable")
        return findings, ""

    routes: set[str] | None = None
    debug_index: dict | None = None
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Assign):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name) and tgt.id == "_ROUTES":
                    routes = set(ast.literal_eval(node.value))
                elif isinstance(tgt, ast.Name) and tgt.id == "_DEBUG_INDEX":
                    debug_index = ast.literal_eval(node.value)
    if routes is None:
        f("no _ROUTES assignment found")
        return findings, ""
    if debug_index is None:
        f("no _DEBUG_INDEX assignment found (the GET /debug index)")
        return findings, ""

    compared: set[str] = set()
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Compare):
            continue
        sides = [node.left, *node.comparators]
        if not any(_mentions_path(s) for s in sides):
            continue
        for s in sides:
            if _mentions_path(s):
                continue
            for lit in _route_literals(s):
                compared.add(lit)
                if lit not in routes:
                    f(f"handler matches {lit!r} but it is not in "
                      f"_ROUTES — its traffic would be folded into the "
                      f"'other' label", node.lineno)

    debug_routes = {r for r in routes if r.startswith("/debug/")}
    for r in sorted(debug_routes - set(debug_index)):
        f(f"/debug route {r!r} has no _DEBUG_INDEX description — the "
          f"GET /debug index would silently omit it")
    for r in sorted(set(debug_index) - debug_routes):
        f(f"_DEBUG_INDEX entry {r!r} is not a registered /debug route "
          f"in _ROUTES")
    for r, desc in sorted(debug_index.items()):
        if not isinstance(desc, str) or not desc.strip():
            f(f"_DEBUG_INDEX[{r!r}] has an empty description")
    if "/debug" not in routes:
        f("the '/debug' index route itself is missing from _ROUTES")

    return findings, (f"route labels closed-world: {len(compared)} "
                      f"handler-matched routes all listed in _ROUTES "
                      f"({len(routes)} registered); GET /debug index "
                      f"covers all {len(debug_routes)} /debug routes")


rule("route-labels",
     "every handler-matched route is in serve/api.py _ROUTES; the "
     "/debug index is closed-world")(check)

"""Failpoint-site rule.

The chaos suite can only drive failure paths whose injection sites exist
and are named what the docs say. Closed-world both directions: every
``failpoints.fire("<name>")`` call site uses a name documented in the
Site registry of ``runtime/failpoints.py``'s module docstring, and every
documented site fires somewhere.
"""

from __future__ import annotations

import ast
import re

from .core import Finding, Project, rule

PKG = "dllama_tpu"
FAILPOINTS = f"{PKG}/runtime/failpoints.py"
_REGISTRY_RE = re.compile(r"^\* ``([a-z_]+)``", re.MULTILINE)


def check(project: Project,
          failpoints_rel: str = FAILPOINTS) -> tuple[list[Finding], str]:
    findings: list[Finding] = []

    fsf = project.file(failpoints_rel)
    if fsf is None or fsf.tree is None:
        findings.append(Finding("failpoint-sites", failpoints_rel, 0,
                                "missing or unparseable"))
        return findings, ""
    doc = ast.get_docstring(fsf.tree) or ""
    documented = set(_REGISTRY_RE.findall(doc))

    fired: dict[str, list[tuple[str, int]]] = {}
    for sf in project.walk(PKG):
        if sf.rel == failpoints_rel or sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "fire"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "failpoints"):
                continue
            if not (node.args and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                findings.append(Finding(
                    "failpoint-sites", sf.rel, node.lineno,
                    "failpoints.fire() with a non-literal site name — "
                    "the closed world can't see it"))
                continue
            fired.setdefault(node.args[0].value, []).append(
                (sf.rel, node.lineno))

    if not documented:
        findings.append(Finding(
            "failpoint-sites", failpoints_rel, 0,
            "no Site registry entries found in the module docstring "
            "(expected '* ``name`` — ...' lines)"))
    for name, sites in sorted(fired.items()):
        if name not in documented:
            findings.append(Finding(
                "failpoint-sites", sites[0][0], sites[0][1],
                f"site {name!r} is fired here but not documented in the "
                f"failpoints.py Site registry"))
    for name in sorted(documented - set(fired)):
        findings.append(Finding(
            "failpoint-sites", failpoints_rel, 0,
            f"site {name!r} is documented in the Site registry but "
            f"never fired anywhere in {PKG}/ — dead chaos surface"))

    n_sites = sum(len(v) for v in fired.values())
    return findings, (f"failpoint sites closed-world: {len(fired)} names "
                      f"over {n_sites} call sites, all documented (and "
                      f"vice versa)")


rule("failpoint-sites",
     "every failpoints.fire() site is documented in the registry and "
     "every documented site fires")(check)

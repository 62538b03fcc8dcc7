"""Trace-safety analyzer — the invariants every jitted program leans on.

Three rule families over ``dllama_tpu/`` (see LINTS.md for the catalog):

* ``jit-entry`` / ``shard-map-shim`` — the **closed-world jit entry**:
  every jit of a model-layer function goes through
  ``parallel.api.plan_scoped_jit`` (the per-engine trace-cache scope +
  compile-ledger hook), and every manual-SPMD entry goes through the
  ``parallel.api.shard_map`` version-compat shim. A raw spelling outside
  ``parallel/api.py`` is an error. ``ops/`` kernels are exempt from
  ``jit-entry`` by design: they are plan-independent (no ``constrain``
  in their bodies), so the plan-scoped cache argument does not apply.

* ``tracer-host-sync`` / ``tracer-ambient`` / ``tracer-branch`` —
  **tracer hazards inside traced function bodies**. Traced functions are
  found by reachability: every function handed to
  ``plan_scoped_jit``/``jax.jit``/``shard_map`` anywhere in the package
  is a root; a name-based call graph over ``models/``, ``ops/`` and
  ``parallel/`` closes the set. Inside a traced body:

  - host syncs — ``.item()``, ``float()/int()/bool()`` casts or
    ``np.asarray``/``np.array`` on a *traced* value — block the dispatch
    pipeline (or crash on non-concrete tracers);
  - ambient host state — ``time.*``, ``np.random.*``, ``random.*``,
    ``datetime.*`` — silently bakes one trace-time value into the
    compiled program;
  - Python branching (``if``/``while``/``assert``/ternary) on a traced
    value raises ``TracerBoolConversionError`` at trace time — on
    whichever backend first traces that path, which for multihost/TPU
    branches may be the one machine CI never runs.

  Traced-vs-static telling: the repo's STATIC-trace-config convention —
  ``cfg``-style config objects, mesh plans, ``n_*`` counts, shape/axis/
  impl-string parameters are trace-time constants (static_argnums);
  everything else flowing in is a tracer. Metadata reads
  (``.shape``/``.ndim``/``.dtype``, ``len()``) and ``is None`` checks on
  tracers are static and stay allowed.

* ``guarded-twin`` — **tripwire completeness** (the PR5 contract): every
  decode program in the ``*_step``/``*_steps`` family (``models/llama.py``)
  and every ``replicated_*`` program (``parallel/multihost.py``) takes
  ``poison`` and ends in ``_guarded``. There is ONE program a kind and the
  tripwire is in it (a caller that wants no injection passes poison 0.0): a
  decode program without it is a dispatch the non-finite tripwire cannot
  ride, exactly where an engine could dispatch.
"""

from __future__ import annotations

import ast
import re

from .core import Finding, Project, SourceFile, rule

PKG = "dllama_tpu"
TRACED_DIRS = (f"{PKG}/models", f"{PKG}/ops", f"{PKG}/parallel")
SHIM = f"{PKG}/parallel/api.py"


# -- helpers ------------------------------------------------------------------

def dotted(node: ast.expr) -> str | None:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_dlint_path(rel: str) -> bool:
    return rel.replace("\\", "/").startswith("tools/dlint/")


# -- rule: jit-entry ----------------------------------------------------------

# model-layer dirs where a jit can bake a mesh plan into its trace
_JIT_SCOPE = (f"{PKG}/models", f"{PKG}/runtime", f"{PKG}/serve",
              f"{PKG}/parallel", f"{PKG}/tokenizer", f"{PKG}/convert",
              f"{PKG}/formats")
_RAW_JIT = {"jax.jit", "jax.pjit", "pjit", "jax.experimental.pjit.pjit"}


@rule("jit-entry",
      "model-layer jit goes through parallel.api.plan_scoped_jit "
      "(closed-world per-engine trace cache + compile ledger)")
def check_jit_entry(project: Project):
    findings: list[Finding] = []
    files = [sf for sf in project.walk(*_JIT_SCOPE) if sf.rel != SHIM]
    findings += project.parse_failures(files, "jit-entry")
    n = 0
    for sf in files:
        if sf.tree is None:
            continue
        n += 1
        for node in ast.walk(sf.tree):
            name = None
            if isinstance(node, (ast.Attribute, ast.Name)):
                name = dotted(node)
            if name in _RAW_JIT:
                findings.append(Finding(
                    "jit-entry", sf.rel, node.lineno,
                    f"raw {name!r} — jit model-layer functions through "
                    f"parallel.api.plan_scoped_jit (per-engine trace "
                    f"cache, compile-ledger hook); see LINTS.md"))
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                mod = getattr(node, "module", "") or ""
                for alias in node.names:
                    if (alias.name == "pjit" or "pjit" in mod):
                        findings.append(Finding(
                            "jit-entry", sf.rel, node.lineno,
                            f"import of pjit ({mod or alias.name}) — "
                            f"route jit through parallel.api"))
    return findings, (f"{n} model-layer files: every jit goes through "
                      f"plan_scoped_jit")


# -- rule: shard-map-shim ----------------------------------------------------

_RAW_SHARD_RE = re.compile(
    r"(jax\.shard_map"
    r"|jax\.experimental\.shard_map"
    r"|from\s+jax\.experimental\.shard_map\s+import"
    r"|from\s+jax\.experimental\s+import\s+shard_map)")


@rule("shard-map-shim",
      "every shard_map call site goes through parallel.api's "
      "version-compat shim")
def check_shard_map_shim(project: Project):
    """The top-level ``jax.shard_map`` does not exist on 0.4.x jax and
    ``jax.experimental.shard_map`` is gone on >= 0.5 — a raw call site
    can never trace on one of the two (the root cause of the 13 seed
    qcollectives failures; CHANGES.md PR2)."""
    findings: list[Finding] = []
    n = 0
    for sf in project.walk(PKG, "tests", "tools"):
        if sf.rel == SHIM or _is_dlint_path(sf.rel):
            continue
        n += 1
        for lineno, line in sf.code_lines():
            m = _RAW_SHARD_RE.search(line)
            if m:
                findings.append(Finding(
                    "shard-map-shim", sf.rel, lineno,
                    f"raw {m.group(0)!r} — route manual SPMD through "
                    f"dllama_tpu.parallel.api.shard_map (the version-"
                    f"compat shim); a raw call cannot trace on every "
                    f"supported jax"))
    return findings, (f"{n} files: every shard_map call site goes through "
                      f"parallel.api's version-compat shim")


# -- traced-function discovery ------------------------------------------------

_JIT_WRAPPERS = {"plan_scoped_jit", "jit", "shard_map"}
# static reads on traced values: array metadata, plus shape-derived
# properties and pytree AUX fields this repo declares static under jit
# (QuantizedWeight.out_features is codes.shape-derived)
_METADATA_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval",
                   "itemsize", "out_features"}
_STATIC_NAMES = {"cfg", "config", "plan", "mesh", "self", "impl", "axis",
                 "axis_name", "axis_names", "interpret", "fast", "bn", "bk",
                 "block_size", "unroll", "site", "sites", "program", "scope",
                 "k"}
_STATIC_PREFIXES = ("n_", "is_", "use_", "num_")
_STATIC_SUFFIXES = ("_shape", "_size", "_axis", "_name", "_impl", "_dtype",
                    "_logical", "_axes", "_specs", "_spec", "_steps",
                    "_type")
_STATIC_ANNOT = ("Config", "int", "str", "bool", "Mesh", "MeshPlan",
                 "Plan")
_AMBIENT_PREFIXES = ("time.", "random.", "np.random.", "numpy.random.",
                     "datetime.")
_HOST_CASTS = {"float", "int", "bool", "complex"}
_NP_SYNC = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
            "np.copy", "numpy.copy"}
_SAFE_CALLS = {"len", "isinstance", "hasattr", "getattr", "type", "range",
               "print", "repr", "str", "tuple", "min", "max",
               "jax.ShapeDtypeStruct"}

# `# dlint: static-fn` on a def line declares a host gate whose return
# value is a trace-time constant (shape/dtype/env decisions only) — its
# call results stay untainted. The rule harvests these from the traced
# dirs; LINTS.md documents the contract the annotation asserts.
STATIC_FN_RE = re.compile(r"#\s*dlint:\s*static-fn")


def _param_is_static(name: str, annot: str) -> bool:
    if name in _STATIC_NAMES:
        return True
    if name.startswith(_STATIC_PREFIXES) or name.endswith(_STATIC_SUFFIXES):
        return True
    return any(a in annot for a in _STATIC_ANNOT)


def _annot_str(a: ast.expr | None) -> str:
    if a is None:
        return ""
    try:
        return ast.unparse(a)
    except Exception:  # pragma: no cover - unparse is total on parsed trees
        return ""


class _FnIndex:
    """Module-level function defs across the traced dirs, by bare name
    (collisions merge — reachability stays conservative)."""

    def __init__(self, files: list[SourceFile]):
        self.defs: dict[str, list[tuple[SourceFile, ast.FunctionDef]]] = {}
        for sf in files:
            if sf.tree is None:
                continue
            for node in ast.walk(sf.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self.defs.setdefault(node.name, []).append((sf, node))

    def called_names(self, fn: ast.AST) -> set[str]:
        out: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                f = node.func
                if isinstance(f, ast.Name):
                    out.add(f.id)
                elif isinstance(f, ast.Attribute):
                    out.add(f.attr)
            # a function passed by reference (e.g. a lax.scan body or a
            # step1 callback) is traced too
            elif isinstance(node, ast.Name) and not isinstance(
                    getattr(node, "ctx", None), ast.Store):
                if node.id in self.defs:
                    out.add(node.id)
        return out


def _jit_roots(project: Project) -> set[str]:
    """Names of functions handed to plan_scoped_jit/jax.jit/shard_map
    anywhere in the package (call args + jit decorators, including
    ``@functools.partial(jax.jit, ...)``)."""
    roots: set[str] = set()
    for sf in project.walk(PKG):
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.Call):
                fname = dotted(node.func)
                tail = fname.rsplit(".", 1)[-1] if fname else None
                if tail in _JIT_WRAPPERS and node.args:
                    name = dotted(node.args[0])
                    if name:
                        roots.add(name.rsplit(".", 1)[-1])
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    names = {dotted(n) for n in ast.walk(dec)
                             if isinstance(n, (ast.Attribute, ast.Name))}
                    if any(n and (n == "jit" or n.endswith(".jit"))
                           for n in names):
                        roots.add(node.name)
    return roots


def traced_functions(project: Project):
    """(SourceFile, FunctionDef) pairs reachable from the jit roots,
    restricted to models//ops//parallel/."""
    files = [sf for sf in project.walk(*TRACED_DIRS)]
    index = _FnIndex(files)
    reach: set[str] = set()
    frontier = [r for r in _jit_roots(project) if r in index.defs]
    while frontier:
        name = frontier.pop()
        if name in reach:
            continue
        reach.add(name)
        for _, node in index.defs.get(name, ()):
            for callee in index.called_names(node):
                if callee in index.defs and callee not in reach:
                    frontier.append(callee)
    out = []
    for name in sorted(reach):
        out.extend(index.defs[name])
    return out


# -- taint walk ---------------------------------------------------------------

def _static_fns(files: list[SourceFile]) -> set[str]:
    """Names of functions annotated ``# dlint: static-fn`` (def line or
    the line above) across the traced dirs."""
    out: set[str] = set()
    for sf in files:
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            for lineno in (node.lineno, node.lineno - 1):
                if 1 <= lineno <= len(sf.lines) and \
                        STATIC_FN_RE.search(sf.lines[lineno - 1]):
                    out.add(node.name)
    return out


class _Taint:
    """Order-sensitive single-pass taint over one function body: params
    not matching the STATIC conventions are tracers; assignment from a
    tainted expression taints the target; metadata reads and declared
    static-fn calls un-taint."""

    def __init__(self, fn: ast.FunctionDef, inherited: set[str],
                 static_fns: set[str] = frozenset()):
        self.static_fns = set(static_fns)
        self.tainted = set(inherited)
        args = fn.args
        for a in (args.posonlyargs + args.args + args.kwonlyargs
                  + ([args.vararg] if args.vararg else [])
                  + ([args.kwarg] if args.kwarg else [])):
            if not _param_is_static(a.arg, _annot_str(a.annotation)):
                self.tainted.add(a.arg)

    def expr(self, node: ast.expr | None) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in _METADATA_ATTRS:
                return False
            return self.expr(node.value)
        if isinstance(node, ast.Call):
            fname = dotted(node.func)
            if fname in _SAFE_CALLS:
                return False
            if fname and fname.rsplit(".", 1)[-1] in self.static_fns:
                return False
            parts = ([self.expr(a) for a in node.args]
                     + [self.expr(kw.value) for kw in node.keywords])
            # a method call on a tainted object yields a tainted result
            if isinstance(node.func, ast.Attribute):
                parts.append(self.expr(node.func.value))
            return any(parts)
        if isinstance(node, ast.Compare):
            return self.expr(node.left) or any(
                self.expr(c) for c in node.comparators)
        if isinstance(node, (ast.BoolOp, ast.BinOp, ast.UnaryOp, ast.IfExp,
                             ast.Tuple, ast.List, ast.Set, ast.Starred,
                             ast.Subscript, ast.Slice, ast.JoinedStr,
                             ast.FormattedValue, ast.Dict)):
            return any(self.expr(c) for c in ast.iter_child_nodes(node)
                       if isinstance(c, ast.expr))
        return False

    def assign_targets(self, target: ast.expr) -> list[str]:
        out = []
        for node in ast.walk(target):
            if isinstance(node, ast.Name):
                out.append(node.id)
        return out

    def mark(self, target: ast.expr, value_tainted: bool) -> None:
        for name in self.assign_targets(target):
            if value_tainted:
                self.tainted.add(name)
            else:
                self.tainted.discard(name)


def _is_none_check(test: ast.expr) -> bool:
    """``x is None`` / ``x is not None`` (possibly or-ed): static-ness
    checks on optional tracers are trace-time constants."""
    if isinstance(test, ast.BoolOp):
        return all(_is_none_check(v) for v in test.values)
    return (isinstance(test, ast.Compare)
            and all(isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops)
            and all(isinstance(c, ast.Constant) and c.value is None
                    for c in test.comparators))


def _branch_tainted(taint: _Taint, test: ast.expr) -> bool:
    """Branch-condition taint with none-check pruning: in
    ``res is None and force`` the tracer only appears inside the
    ``is None`` (a static check), so the branch is trace-safe."""
    if _is_none_check(test):
        return False
    if isinstance(test, ast.BoolOp):
        return any(_branch_tainted(taint, v) for v in test.values)
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        return _branch_tainted(taint, test.operand)
    return taint.expr(test)


def _scan_traced_body(sf: SourceFile, fn: ast.FunctionDef,
                      inherited: set[str],
                      findings: list[Finding],
                      static_fns: set[str] = frozenset()) -> None:
    taint = _Taint(fn, inherited, static_fns)

    def hazard_calls(node: ast.Call) -> None:
        fname = dotted(node.func)
        if fname:
            if fname.startswith(_AMBIENT_PREFIXES):
                findings.append(Finding(
                    "tracer-ambient", sf.rel, node.lineno,
                    f"{fname}() inside traced function "
                    f"{fn.name!r} bakes one trace-time value into the "
                    f"compiled program (ambient host state)"))
                return
            if fname in _NP_SYNC and any(
                    taint.expr(a) for a in node.args):
                findings.append(Finding(
                    "tracer-host-sync", sf.rel, node.lineno,
                    f"{fname}() on a traced value inside {fn.name!r} "
                    f"forces a device→host sync (or crashes on an "
                    f"abstract tracer)"))
                return
            if fname in _HOST_CASTS and any(
                    taint.expr(a) for a in node.args):
                findings.append(Finding(
                    "tracer-host-sync", sf.rel, node.lineno,
                    f"{fname}() cast of a traced value inside "
                    f"{fn.name!r} forces a host sync "
                    f"(ConcretizationTypeError on an abstract tracer)"))
                return
        if isinstance(node.func, ast.Attribute) and node.func.attr == "item":
            findings.append(Finding(
                "tracer-host-sync", sf.rel, node.lineno,
                f".item() inside traced function {fn.name!r} is a "
                f"device→host sync"))

    def scan_exprs(st: ast.stmt) -> None:
        """Hazard scan over the statement's own expression fields (block
        bodies are statement lists and recurse separately; nested defs
        are re-scanned with their own taint frame). Lambdas stay in the
        walk — a lambda inside a traced body is traced too."""
        for field, value in ast.iter_fields(st):
            exprs = [value] if isinstance(value, ast.expr) else [
                v for v in (value if isinstance(value, list) else [])
                if isinstance(v, ast.expr)]
            if isinstance(value, list):  # `with a, b:` items
                exprs += [v.context_expr for v in value
                          if isinstance(v, ast.withitem)]
            for e in exprs:
                for node in ast.walk(e):
                    if isinstance(node, ast.Call):
                        hazard_calls(node)
                    elif isinstance(node, ast.IfExp) and \
                            _branch_tainted(taint, node.test):
                        findings.append(Finding(
                            "tracer-branch", sf.rel, node.lineno,
                            f"ternary on a traced value inside "
                            f"{fn.name!r} — use jnp.where"))

    def visit(stmts: list[ast.stmt]) -> None:
        for st in stmts:
            if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef)):
                _scan_traced_body(sf, st, set(taint.tainted), findings,
                                  static_fns)
                continue
            if isinstance(st, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                t = taint.expr(st.value)
                if isinstance(st, ast.AugAssign):
                    t = t or taint.expr(st.target)
                targets = (st.targets if isinstance(st, ast.Assign)
                           else [st.target])
                for tgt in targets:
                    taint.mark(tgt, t)
            if isinstance(st, (ast.If, ast.While)):
                if _branch_tainted(taint, st.test):
                    findings.append(Finding(
                        "tracer-branch", sf.rel, st.lineno,
                        f"Python branch on a traced value inside "
                        f"{fn.name!r} — TracerBoolConversionError at "
                        f"trace time (use lax.cond/jnp.where, or make "
                        f"the input STATIC trace config)"))
            if isinstance(st, ast.Assert) and \
                    _branch_tainted(taint, st.test):
                findings.append(Finding(
                    "tracer-branch", sf.rel, st.lineno,
                    f"assert on a traced value inside {fn.name!r} — "
                    f"TracerBoolConversionError at trace time (assert "
                    f"on .shape/.ndim metadata instead)"))
            if isinstance(st, ast.For) and taint.expr(st.iter):
                taint.mark(st.target, True)
            scan_exprs(st)
            # recurse into block bodies with the running taint state
            for attr in ("body", "orelse", "finalbody"):
                sub = getattr(st, attr, None)
                if isinstance(sub, list) and sub and \
                        isinstance(sub[0], ast.stmt):
                    visit(sub)
            for h in getattr(st, "handlers", []) or []:
                visit(h.body)

    visit(fn.body)


@rule("tracer-hazard",
      "traced function bodies are free of host syncs, ambient host "
      "state, and Python branches on traced values")
def check_tracer_hazards(project: Project):
    findings: list[Finding] = []
    fns = traced_functions(project)
    static_fns = _static_fns([sf for sf in project.walk(*TRACED_DIRS)])
    for sf, fn in fns:
        _scan_traced_body(sf, fn, set(), findings, static_fns)
    return findings, (f"{len(fns)} traced functions (call-graph closure "
                      f"of every jit/shard_map root): no host syncs, no "
                      f"ambient state, no tracer branches "
                      f"({len(static_fns)} declared static-fn gates)")


# -- rule: guarded-twin -------------------------------------------------------

_LLAMA = f"{PKG}/models/llama.py"
_MULTIHOST = f"{PKG}/parallel/multihost.py"


def _module_defs(sf: SourceFile) -> dict[str, ast.FunctionDef]:
    out: dict[str, ast.FunctionDef] = {}
    if sf.tree is None:
        return out
    for node in sf.tree.body:  # module level only
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            out[node.name] = node
    return out


@rule("guarded-twin",
      "every decode program in the *_step family carries the tripwire: it "
      "takes `poison` and ends in _guarded (PR5 contract)")
def check_guarded_twins(project: Project):
    findings: list[Finding] = []
    checked = 0

    def family(sf: SourceFile, member) -> None:
        nonlocal checked
        for name, fn in sorted(_module_defs(sf).items()):
            if name.startswith("_") or "forward" in name:
                continue
            if not member(name.removesuffix("_guarded")):
                continue
            checked += 1
            args = fn.args
            takes = {a.arg for a in (*args.posonlyargs, *args.args,
                                     *args.kwonlyargs)}
            if not name.endswith("_guarded") or "poison" not in takes:
                findings.append(Finding(
                    "guarded-twin", sf.rel, fn.lineno,
                    f"decode program {name!r} carries no tripwire (it must "
                    f"take `poison` and end in _guarded): the non-finite "
                    f"tripwire (PR5) cannot ride its dispatches. There is "
                    f"one program a kind; put the tripwire in it, and pass "
                    f"poison 0.0 where no injection is wanted"))

    llama = project.file(_LLAMA)
    if llama is not None:
        family(llama, lambda n: n.endswith(("_step", "_steps")))
    elif project.file(PKG) is not None:  # pragma: no cover
        findings.append(Finding("guarded-twin", _LLAMA, 0, "file missing"))
    mh = project.file(_MULTIHOST)
    if mh is not None:
        family(mh, lambda n: n.startswith("replicated_"))
    return findings, (f"{checked} decode-family programs all carry the "
                      f"tripwire")

"""The documents a new owner reads first name only files that exist.

One case per document: every repository path it spells (``tools/...``,
``tests/...``, ``dllama_tpu/...``, ``benchmark/...``, a package-relative
``runtime/x.py``, a root file such as ``chip_smoke.py`` or
``QUALITY_BASELINE.json``) is in the tree, and every ``make <target>`` the
README shows is a target of the Makefile. A file that goes takes its
mentions with it, or this fails and names them.
"""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ("README.md", "Makefile", ".github/workflows/main.yml", "LINTS.md",
        "dllama_tpu/runtime/TELEMETRY.md", ".claude/skills/verify/SKILL.md")

# a path that starts at the root of the repository
_ROOTED = re.compile(
    r"(?<![\w/.*-])((?:tools|tests|dllama_tpu|benchmark|examples)/[\w./*-]+)")
# a path inside the package, spelled from one of its sub-packages
_PACKAGE = re.compile(
    r"(?<![\w/.*-])((?:runtime|serve|ops|models|parallel|formats|native)"
    r"/[\w./*-]+\.(?:py|md|cpp|hpp))")
# a bare file name: any *.py, and the root's upper-case records
_BARE = re.compile(
    r"(?<![\w/.*-])([A-Za-z_]\w*\.py|[A-Z][A-Z0-9_]+\w*\*?\.(?:jsonl|json|md))"
    r"(?![\w/])")

# what building, testing and running leave behind (.gitignore lists them)
_GENERATED = ("dllama_tpu/native/libdllama_native.so",
              "dllama_tpu/native/tsan_stress")
_LEFT_BEHIND = ("__pycache__", "chiprun_out", "chip_smoke_model")


@functools.lru_cache(maxsize=None)
def _basenames() -> frozenset[str]:
    """The names of the tree's files, without what .gitignore keeps out
    of it (scratch and caches are dot-directories; an unpacked parent
    commit among them would answer for files that are gone)."""
    out = set()
    for _, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d in (".github", ".claude")
                   or not (d.startswith(".") or d in _LEFT_BEHIND)]
        out.update(names)
    return frozenset(out)


def _exists(path: str) -> bool:
    if "*" in path:
        return bool(glob.glob(os.path.join(REPO, path)))
    return os.path.exists(os.path.join(REPO, path))


def _missing(text: str) -> list[str]:
    bad = []
    for m in _ROOTED.finditer(text):
        path = m.group(1).rstrip(".")
        if not _exists(path) and not path.startswith(_GENERATED):
            bad.append(path)
    for m in _PACKAGE.finditer(text):
        path = m.group(1).rstrip(".")
        if not _exists(os.path.join("dllama_tpu", path)):
            bad.append(path)
    for m in _BARE.finditer(text):
        name = m.group(1)
        if not (_exists(name) if "*" in name else name in _basenames()):
            bad.append(name)
    return sorted(set(bad))


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_paths_that_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    assert _missing(text) == [], f"{doc} names files that are not in the tree"


def test_the_scan_sees_a_missing_file():
    """The scan itself: each kind of mention, present and absent."""
    assert _missing("see `tools/gemv_sweep.py` and tests/conftest.py.") == []
    assert _missing("run `gone.py`, then tools/no_such_tool.py 1b") == [
        "gone.py", "tools/no_such_tool.py"]
    assert _missing("`ops/linear.py` and `ops/gone.py`") == ["ops/gone.py"]
    assert _missing("GONE_RECORD.json, QUALITY_BASELINE.json, "
                    "GONE_r0*.json") == ["GONE_RECORD.json", "GONE_r0*.json"]
    assert _missing("tools/dlint/*.py and tools/gone_*.py") == [
        "tools/gone_*.py"]


def test_readme_make_targets_exist():
    with open(os.path.join(REPO, "Makefile"), encoding="utf-8") as f:
        targets = set(re.findall(r"^([a-z][\w-]*):", f.read(), re.M))
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    # `make x` in an inline code span, or at the start of a code-block line
    named = set(re.findall(r"`make ([a-z][\w-]*)", readme))
    named |= set(re.findall(r"^make ([a-z][\w-]*)", readme, re.M))
    assert named, "the README shows no make target at all"
    assert named <= targets, sorted(named - targets)

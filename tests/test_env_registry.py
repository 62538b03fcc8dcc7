"""Every ``DLLAMA_*`` variable the package reads is in README's one table.

One case per variable found by scanning the source under ``dllama_tpu/``:
it has a row under "Environment variables". One case the other way round:
the table names no variable that nothing reads. An option nobody can list
is an option nobody can remove.
"""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_VAR = re.compile(r"\bDLLAMA_[A-Z0-9]+(?:_[A-Z0-9]+)*\b")


def _read_by_source() -> list[str]:
    found = set()
    for root, dirs, names in os.walk(os.path.join(REPO, "dllama_tpu")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for n in names:
            if n.endswith(".py"):
                with open(os.path.join(root, n), encoding="utf-8") as f:
                    found.update(_VAR.findall(f.read()))
    return sorted(found)


@functools.lru_cache(maxsize=None)
def _table_rows() -> tuple[str, ...]:
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        readme = f.read()
    section = readme.split("\n## Environment variables\n", 1)[1]
    section = section.split("\n## ", 1)[0]
    return tuple(re.findall(r"^\| `(DLLAMA_[A-Z0-9_]+)` \|", section, re.M))


@pytest.mark.parametrize("var", _read_by_source())
def test_variable_has_a_row_in_the_readme_table(var):
    rows = _table_rows()
    assert rows.count(var) == 1, (
        f"{var} is read under dllama_tpu/ and needs exactly one row in "
        f"README.md, 'Environment variables' (found {rows.count(var)})")


def test_table_names_no_variable_that_nothing_reads():
    assert sorted(set(_table_rows()) - set(_read_by_source())) == []

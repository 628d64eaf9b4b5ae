"""The documents name files that exist, and the README's table of
``TM_*`` environment names is the set the package reads.

A document outlives what it describes unless something fails when the
file it cites goes: the page of "measured" speeds described a benchmark
for twenty PRs after the benchmark had moved (PR 46 removed both).
``ROADMAP.md`` and ``CHANGES.md`` are history and are left out, as
are ``PERF.md``'s sections after the third (findings and open
questions cite what was, on purpose).
"""

import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
DOCUMENTS = ["README.md", "PERF.md", ".claude/skills/verify/SKILL.md",
             *sorted(f"docs/{p.name}" for p in (ROOT / "docs").glob("*.md"))]
#: what a back-quoted token has to end in to be held to the tree; a
#: trailing slash makes it a directory
SUFFIXES = (".py", ".md", ".json", ".jsonl", ".sh", ".cc", ".toml", "/")
#: where a document's shorthand is rooted (``parallel/moe.py``,
#: ``test_router.py``, ``layer_metrics/_blocks.py``)
BASES = ["", "theanompi_tpu", "tests", "docs", "benchmark"]
_TOKEN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[\w.\-]+(/[\w.\-]+)*/?$")


def cited_paths(text: str) -> set[str]:
    """Back-quoted tokens that read as a path of this repository:
    made of path characters, ending in a suffix of ``SUFFIXES``; a
    ``::test`` or ``:line`` tail is dropped."""
    out = set()
    for token in _TOKEN.findall(text):
        token = re.sub(r":\d+(-\d+)?$", "", token.split("::")[0])
        if _PATH.match(token) and token.endswith(SUFFIXES):
            out.add(token)
    return out


def exists(path: str) -> bool:
    if any((ROOT / base / path).exists() for base in BASES):
        return True
    # a bare file name: anywhere in the code (``decoder.py``)
    return "/" not in path and any(
        next((ROOT / top).rglob(path), None)
        for top in ("theanompi_tpu", "benchmark", "scripts", "tests"))


def text_of(document: str) -> str:
    text = (ROOT / document).read_text()
    if document == "PERF.md":       # sections 1-3: what is, not what was
        text = text[:re.search(r"^## 4", text, re.M).start()]
    return text


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_cites_exists(document):
    cited = cited_paths(text_of(document))
    assert cited, f"{document} cites no path at all: the pattern is off"
    gone = sorted(p for p in cited if not exists(p))
    assert not gone, f"{document} cites {gone}"


def test_the_readme_lists_the_environment_names_the_package_reads():
    """ROADMAP D10's census as a test: every ``TM_*`` name under
    ``theanompi_tpu/`` has a row in the README's Environment table,
    and the table has no row the package does not read."""
    name = re.compile(r"\bTM_[A-Z0-9_]+\b")
    read = set()
    for pattern in ("*.py", "*.cc"):
        for path in (ROOT / "theanompi_tpu").rglob(pattern):
            read |= set(name.findall(path.read_text()))
    readme = (ROOT / "README.md").read_text()
    table = readme[readme.index("**Environment**"):]
    table = table[:table.index("\n\n**")]
    rows = {m.group(1) for m in re.finditer(r"^\| `(TM_[A-Z0-9_]+)` \|",
                                            table, re.M)}
    assert rows == read, (sorted(read - rows), sorted(rows - read))
    assert f"({len(read)};" in table.splitlines()[0]

"""The documents cite files that exist.

Every path with a file extension that README.md, docs/*.md,
examples/README.md or the verify skill puts in backticks or in a link
must be a file of this tree: as written from the root, from the
document's own directory, or from ``faabric_tpu/`` (the docs name
modules as ``mpi/world.py``); a bare name must be some tracked file's.
Line numbers and test ids behind a path are not checked. CHANGES.md,
ROADMAP.md and PERF.md are left out on purpose: they cite history.
"""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DOCUMENTS = ["README.md",
             *sorted(os.path.relpath(p, REPO) for p in
                     glob.glob(os.path.join(REPO, "docs", "*.md"))),
             "examples/README.md",
             ".claude/skills/verify/SKILL.md"]

_PATH = re.compile(r"(?<![\w./-])([\w.-]+(?:/[\w.-]+)*"
                   r"\.(?:py|md|json|jsonl|sh|cpp|txt|ini|toml))(?![\w-])")
# Names the documents may cite though the tree does not hold them
_A_RUN_WRITES = {"planner.snapshot.json", "perf.json", "perf-cluster.json",
                 "before.json", "after.json", "metrics.txt"}
_OF_THE_REFERENCE = {"check.cpp", "server.cpp", "mpi_isendrecv.cpp",
                     "mpi_status.cpp"}


@pytest.fixture(scope="module")
def file_names() -> set:
    """Base names of the tree's files, the directories that .gitignore
    lists left out (a scratch copy of an older commit lives in one)."""
    with open(os.path.join(REPO, ".gitignore")) as f:
        skip = {".git"} | {line.strip().rstrip("/") for line in f
                           if line.strip().endswith("/")}
    names = set()
    for _dir, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in skip]
        names.update(files)
    return names


def cited_paths(text: str) -> set:
    spans = re.findall(r"`([^`\n]+)`", text)
    spans += re.findall(r"\]\(([^)\s]+)\)", text)
    return {path for span in spans if not set(span) & set("<>*{}$")
            for path in _PATH.findall(span)}


def test_cited_paths_reads_backticks_and_links():
    text = ("see `faabric_tpu/mpi/world.py:12`, [x](../PERF.md) and "
            "`tests/unit/test_ops.py::test_a`; not `flight-<pid>.json`, "
            "not plain words like gone.py")
    assert cited_paths(text) == {"faabric_tpu/mpi/world.py", "../PERF.md",
                                 "tests/unit/test_ops.py"}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_cited_path_exists(document, file_names):
    with open(os.path.join(REPO, document)) as f:
        cited = cited_paths(f.read())
    assert cited, f"{document} cites no file: is the pattern broken?"
    missing = []
    for path in sorted(cited - _A_RUN_WRITES - _OF_THE_REFERENCE):
        if "/" not in path and path in file_names:
            continue
        roots = (REPO, os.path.join(REPO, os.path.dirname(document)),
                 os.path.join(REPO, "faabric_tpu"))
        if not any(os.path.exists(os.path.normpath(os.path.join(r, path)))
                   for r in roots):
            missing.append(path)
    assert not missing, f"{document} cites files that are gone: {missing}"

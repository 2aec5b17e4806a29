"""The documents are held to the tree: a path they name exists, and a
``TPUSNAPSHOT_*`` variable they name is read by some ``.py`` file. A
deletion that leaves its name behind in a document fails here."""

import glob
import os
import re

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCUMENTS = [
    "README.md",
    "PARITY.md",
    "docs/api.md",
    "docs/design.md",
    "docs/OBSERVABILITY.md",
    "docs/FAULTS.md",
    "docs/PROTOCOL.md",
    "docs/ANALYSIS.md",
]
_EXTENSIONS = (".py", ".md", ".json", ".yaml", ".sh")


def _ignored_dirs():
    # What building, testing and running leave behind (a parent copy
    # under _checkout/ among it) is not the tree.
    with open(os.path.join(_REPO, ".gitignore")) as f:
        lines = [line.strip() for line in f]
    return {".git"} | {line.rstrip("/") for line in lines if line.endswith("/")}


_IGNORED = _ignored_dirs()
_FENCED = re.compile(r"^```.*?$(.*?)^```", re.S | re.M)
_INLINE = re.compile(r"`([^`\n]+)`")


def _code_words(text):
    """Every whitespace-separated word of the document's code: fenced
    blocks and back-quoted spans."""
    for code in _FENCED.findall(text) + _INLINE.findall(_FENCED.sub("", text)):
        yield from code.split()


def _repo_paths(text):
    """The words that are paths into the repository: they begin with a
    top-level directory or are a top-level file's name, and end in one
    of ``_EXTENSIONS`` once ``:line`` or ``::test`` is cut off. Storage
    objects (``.report.json``) and the reference's files (``setup.py``)
    are neither."""
    top = [e for e in os.listdir(_REPO) if e not in _IGNORED]
    dirs = tuple(e + "/" for e in top if os.path.isdir(os.path.join(_REPO, e)))
    for word in _code_words(text):
        path = word.strip("\"'()[],;.").split("::")[0]
        path = re.sub(r":[\d,:\-]*$", "", path)
        if path.endswith(_EXTENSIONS) and (path.startswith(dirs) or path in top):
            yield path


@pytest.fixture(scope="module")
def python_sources():
    sources = []
    for root, dirs, files in os.walk(_REPO):
        dirs[:] = [d for d in dirs if d not in _IGNORED]
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name), errors="replace") as f:
                    sources.append(f.read())
    return "\n".join(sources)


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_document_names_what_exists(document, python_sources):
    with open(os.path.join(_REPO, document)) as f:
        text = f.read()
    missing = sorted(
        {p for p in _repo_paths(text) if not glob.glob(os.path.join(_REPO, p))}
    )
    assert not missing, f"{document} names paths that do not exist: {missing}"
    unread = sorted(
        {
            name
            for name in re.findall(r"TPUSNAPSHOT_[A-Z0-9_]+", text)
            if name not in python_sources
        }
    )
    assert not unread, f"{document} names variables no .py file reads: {unread}"

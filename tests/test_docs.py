"""The documents and the package's docstrings name files that exist.

A deletion must not leave a citation behind: every file a document puts
in backticks, and every record, tool or document a docstring of the
package names, is looked up in the tree.  A reference that fails is
corrected where it is written, never skipped here.
"""

import functools
import os
import re

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
PACKAGE = os.path.join(ROOT, "analytics_zoo_tpu")

DOCS = ["README.md"] + sorted(
    f"docs/{n}" for n in os.listdir(os.path.join(ROOT, "docs"))
    if n.endswith(".md"))

#: files the program writes at run time, which no checkout holds
WRITTEN_AT_RUN_TIME = frozenset({"manifest.json", "ACCURACY.md"})

#: the directories git tracks; a walk of ROOT itself would also find
#: what .gitignore lists (a second checkout, the chip's outputs)
TRACKED_DIRS = ("analytics_zoo_tpu", "benchmarks", "docs", "examples",
                "native", "tests", "tools")

_TOKEN = re.compile(r"`([^`\n]+)`")
_SUFFIX = re.compile(r"(::[\w.]+|:\d+(-\d+)?)$")
_ENDINGS = (".py", ".json", ".jsonl", ".md")


@functools.lru_cache(maxsize=None)
def _file_names():
    names = {n for n in os.listdir(ROOT)
             if os.path.isfile(os.path.join(ROOT, n))}
    for d in TRACKED_DIRS:
        for _dir, _subdirs, files in os.walk(os.path.join(ROOT, d)):
            names.update(files)
    return names


def _file_tokens(text):
    """The words of ``text``'s backticked tokens that name a file (a
    token of several words is a command: each word is looked at)."""
    for token in _TOKEN.findall(text):
        for word in token.split():
            word = _SUFFIX.sub("", word.strip("(),;'\""))
            if not word.endswith(_ENDINGS):
                continue
            if any(c in word for c in "*<{") or word.startswith("/"):
                continue
            yield word


def _exists(token, names):
    if token in WRITTEN_AT_RUN_TIME:
        return True
    if any(os.path.exists(os.path.join(base, token))
           for base in (ROOT, PACKAGE)):
        return True
    return "/" not in token and token in names


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_what_exists(doc):
    assert len(WRITTEN_AT_RUN_TIME) <= 5, "correct the document instead"
    with open(os.path.join(ROOT, doc)) as f:
        tokens = sorted(set(_file_tokens(f.read())))
    assert tokens, f"{doc} names no file at all: is the pattern broken?"
    names = _file_names()
    missing = [t for t in tokens if not _exists(t, names)]
    assert not missing, f"{doc} names files that do not exist: {missing}"


_CITED = re.compile(
    r"\b[A-Z][A-Za-z0-9_]*\.jsonl?\b"       # a record at the root
    r"|\bbench\.py\b"
    r"|\btools/\w+\.py\b"
    r"|\bdocs/\w+\.md\b")


def test_package_cites_what_exists():
    missing, n_cited = [], 0
    for dirpath, _subdirs, files in os.walk(PACKAGE):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    for cited in _CITED.findall(line):
                        n_cited += 1
                        if not os.path.exists(os.path.join(ROOT, cited)):
                            missing.append(
                                f"{os.path.relpath(path, ROOT)}:{lineno}"
                                f" {cited}")
    assert n_cited > 20, "the pattern finds too little to guard anything"
    assert not missing, missing

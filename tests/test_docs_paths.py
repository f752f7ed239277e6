"""Every repo path a document names exists.

One case a document: ``README.md``, ``PERF.md``, ``ROADMAP.md``'s Open items
and each ``docs/*.md``. A path is a token inside backticks that ends in
``.py``, ``.md``, ``.json`` or ``.sh``, or in ``/`` (a directory), after a
trailing ``:line`` or ``::test`` is cut off. It exists when a file (or
directory) of the tree has it as its whole path or as the end of its path at
a component boundary: the documents write ``engine/engine.py`` for
``dynamo_tpu/engine/engine.py``. ``<name>`` and ``*`` match anything,
``{a,b}`` is every alternative.

Not repo paths, and skipped: absolute paths and URLs (``/metrics``,
``/root/TESTS_LAST_RUN.json``), options (``--flag``), and whatever lies under
a name ``.gitignore`` lists (run-time outputs such as ``chipbench_out/``).
The tree is what is on disk less ``.git`` and those names, so the case reads
the same in a checkout that never ran anything.
"""

import fnmatch
import functools
import glob
import itertools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "PERF.md", "ROADMAP.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
)


@functools.lru_cache(maxsize=None)
def _ignored_names() -> tuple[str, ...]:
    with open(os.path.join(REPO, ".gitignore")) as f:
        return tuple(ln.strip().rstrip("/") for ln in f
                     if ln.strip() and not ln.startswith("#"))


@functools.lru_cache(maxsize=None)
def _tree() -> tuple[list[str], list[str]]:
    ignored = _ignored_names()
    files, dirs = [], []
    for d, subdirs, names in os.walk(REPO):
        subdirs[:] = [x for x in subdirs if x != ".git"
                      and not any(fnmatch.fnmatch(x, i) for i in ignored)]
        rel = os.path.relpath(d, REPO)
        if rel != ".":
            dirs.append(rel)
        files += [os.path.normpath(os.path.join(rel, n)) for n in names
                  if not any(fnmatch.fnmatch(n, i) for i in ignored)]
    return files, dirs


def _text(doc: str) -> str:
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    if doc == "ROADMAP.md":  # the queues; Recent is history and may name what is gone
        text = text[text.index("\n## Open items"):text.index("\n## Recent")]
    return text


def _paths(text: str) -> set[str]:
    out = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for tok in span.split():
            tok = tok.strip("(),;").rstrip(".")
            tok = re.sub(r"(::[\w\[\]\-.]+)+$", "", tok)
            tok = re.sub(r":[\d,\-]+$", "", tok)
            if re.search(r"\.(py|md|json|sh)$", tok) or tok.endswith("/"):
                out.add(tok)
    return out


def _alternatives(tok: str) -> list[str]:
    m = re.search(r"\{([^{}]*,[^{}]*)\}", tok)
    if not m:
        return [tok]
    return list(itertools.chain.from_iterable(
        _alternatives(tok[:m.start()] + alt + tok[m.end():])
        for alt in m.group(1).split(",")
    ))


def _exists(tok: str) -> bool:
    files, dirs = _tree()
    if tok.startswith(("/", "~", "-", "$", "http")) or "://" in tok:
        return True
    if any(fnmatch.fnmatch(tok.split("/", 1)[0], i) for i in _ignored_names()):
        return True
    for alt in _alternatives(tok):
        pool = dirs if alt.endswith("/") else files
        pat = re.sub(r"<[^<>]*>", "*", alt).rstrip("/")
        if pat.startswith("./"):
            pat = pat[2:]
        if not any(fnmatch.fnmatch(p, pat) or fnmatch.fnmatch(p, "*/" + pat)
                   for p in pool):
            return False
    return True


@pytest.mark.parametrize("doc", DOCS)
def test_every_repo_path_a_document_names_exists(doc):
    missing = sorted(t for t in _paths(_text(doc)) if not _exists(t))
    assert not missing, f"{doc} names paths that are not in the tree: {missing}"

"""The documents name files that exist.

One case a document (README.md, the verify skill, every doc/*.md): each
back-ticked word that ends in ``.py``, ``.sh`` or ``.md`` must resolve
in this tree.  A document that still points at a deleted file, or at
a module that moved, fails here instead of on a reader's first day.
"""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "gpu_mapreduce_tpu"

# what running and testing leave behind (.gitignore), never a document's
# target; chip_scratch/ holds a copy of the parent commit during a chip
# run, deleted files and all
_SKIP_DIRS = {"__pycache__", "chiprun_out", "chip_scratch",
              os.path.join("benchmark", "cache")}

# files of the reference (baoxuezhao/GPU-mapreduce), named where a
# document maps the reference's layout onto this tree: they are not
# meant to exist here
REFERENCE_FILES = {"oink/Make.py", "Make.py"}

_SPAN = re.compile(r"`([^`\n]+)`")
_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_PATH = re.compile(r"^[\w./-]+\.(?:py|sh|md)$")
_TAIL = re.compile(r"(?::[\d,-]+|#[\w-]*)$")      # :line, :a-b, #anchor


# a record, not a guide: what accepted PRs wrote into PERF.md, kept word
# for word, so it names files that later PRs deleted
RECORDS = {"doc/perf-history.md"}


def _documents():
    docs = ["README.md", ".claude/skills/verify/SKILL.md"]
    docs += sorted("doc/" + f for f in os.listdir(os.path.join(REPO, "doc"))
                   if f.endswith(".md"))
    return [d for d in docs if d not in RECORDS]


def _tree():
    """(relative paths, basenames) of the files in the checkout."""
    paths = set()
    for root, dirs, files in os.walk(REPO):
        rel = os.path.relpath(root, REPO)
        dirs[:] = [d for d in dirs
                   if d not in _SKIP_DIRS
                   and os.path.normpath(os.path.join(rel, d)) not in _SKIP_DIRS
                   and (not d.startswith(".") or d == ".claude")]
        for f in files:
            paths.add(os.path.normpath(os.path.join(rel, f)))
    return paths, {os.path.basename(p) for p in paths}


def _tokens(text):
    """Back-ticked words of a document: the words of every inline span
    (fenced blocks hold a user's own commands, ``python app.py``, and
    are left out), trailing ``:line`` / ``#anchor`` cut."""
    for span in _SPAN.findall(_FENCE.sub("", text)):
        for word in span.split():
            word = _TAIL.sub("", word.strip("`'\"()[],;"))
            if any(c in word for c in "*<{$"):
                continue
            if _PATH.match(word):
                yield word


def unresolved(text, paths, basenames):
    """The tokens of ``text`` that name no file of the tree."""
    top = {p.split(os.sep)[0] for p in paths if os.sep in p}
    sub = {p.split(os.sep)[1] for p in paths
           if p.startswith(PACKAGE + os.sep) and p.count(os.sep) > 1}
    bad = []
    for tok in _tokens(text):
        if tok in REFERENCE_FILES:
            continue
        norm = os.path.normpath(tok)
        head = norm.split(os.sep)[0]
        if os.sep not in norm:
            ok = norm in basenames
        elif head in top:
            ok = norm in paths
        elif head in sub:
            ok = os.path.join(PACKAGE, norm) in paths
        else:
            continue            # another tree's path (the reference's)
        if not ok:
            bad.append(tok)
    return bad


@pytest.fixture(scope="module")
def tree():
    return _tree()


@pytest.mark.parametrize("doc", _documents())
def test_document_names_files_that_exist(doc, tree):
    with open(os.path.join(REPO, doc)) as f:
        bad = unresolved(f.read(), *tree)
    assert bad == [], f"{doc} names files that are not in the tree: {bad}"


def test_rule_catches_a_name_that_resolves_to_nothing(tree):
    """The rule has teeth: what a deleted file's name would look like in
    a document does not resolve, bare, inside a command, under a
    top-level directory or under a sub-package."""
    text = ("run `python gone_harness.py --gate`, see `scripts/gone_gate.py`,\n"
            "`ops/pallas/gone_kernel.py:12` and `gone_script.py`; "
            "`benchmark/run.py` and `plan/fuser.py#x` are fine\n")
    assert unresolved(text, *tree) == [
        "gone_harness.py", "scripts/gone_gate.py",
        "ops/pallas/gone_kernel.py", "gone_script.py"]

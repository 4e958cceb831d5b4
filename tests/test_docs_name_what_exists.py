"""The documents name only what exists: every ``*.py`` / ``*.sh`` a manual
names is a file of this tree, and every ``make <target>`` it shows is a
target of the Makefile.  A manual that outlives what it describes is how
the README came to document a harness nothing read any more (ISSUE 31)."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FILE = re.compile(r"(?<![\w./*}>-])([\w.-]+(?:/[\w.-]+)*\.(?:py|sh))\b")
_CODE_SPAN = re.compile(r"```.*?```|`[^`\n]+`", re.S)


def _tree():
    """Relative paths of the tree's files; hidden directories (``.git``,
    caches, unpacked copies) are no part of it, ``.claude`` is."""
    files = []
    for top, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if d == ".claude"
                   or not (d.startswith(".") or d in ("__pycache__", "chiprun_out"))]
        rel = os.path.relpath(top, REPO)
        files += [os.path.normpath(os.path.join(rel, n)) for n in names]
    return files


def _missing_files(text, files):
    tops = {f.split(os.sep)[0] for f in files if os.sep in f}
    have = set(files)
    bare = {os.path.basename(f) for f in files}
    missing = set()
    for name in set(_FILE.findall(text)):
        path = os.path.normpath(name)
        if os.sep not in path:
            ok = path in bare
        elif path.split(os.sep)[0] in tops:
            ok = path in have
        else:  # relative to a package directory: `engine/engine.py`
            ok = any(f.endswith(os.sep + path) for f in files)
        if not ok:
            missing.add(name)
    return sorted(missing)


def _missing_targets(text, makefile):
    """``make <target>`` counts inside backticks or a code block (prose may
    say "make sure"), ``$(MAKE) <target>`` anywhere."""
    targets = set(re.findall(r"^([A-Za-z][\w-]*):", makefile, re.M))
    asked = set(re.findall(r"\$\(MAKE\) +([a-z][\w-]*)", text))
    for span in _CODE_SPAN.findall(text):
        asked.update(re.findall(r"\bmake +([a-z][\w-]*)", span))
    return sorted(asked - targets)


@pytest.mark.parametrize(
    "doc", ["README.md", "Makefile", ".claude/skills/verify/SKILL.md",
            "benchmarks/BLOCK_DIFFUSION.md", "benchmarks/SSM_MOE.md",
            "benchmarks/OLMO_HYBRID.md", "benchmarks/LAGUNA_MOE.md"])
def test_document_names_only_what_exists(doc):
    with open(os.path.join(REPO, "Makefile")) as f:
        makefile = f.read()
    if doc == "Makefile":
        text = makefile
    else:
        with open(os.path.join(REPO, doc)) as f:
            text = f.read()
    assert _FILE.findall(text), f"{doc}: the pattern finds no file name"
    assert _missing_files(text, _tree()) == []
    assert _missing_targets(text, makefile) == []

"""Every repository path a document cites exists.

One case per document (``README.md``, each ``docs/*.md``, the verify
skill): every ``*.py`` / ``*.md`` / ``*.json`` path it cites in backticks
or in a fenced block resolves in the checkout, and every ``make <target>``
is a target of the ``Makefile``; a deleted file's name fails on its line.
"""

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = (
    ["README.md"]
    + sorted(
        os.path.relpath(p, ROOT)
        for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
    )
    + [".claude/skills/verify/SKILL.md"]
)

# Inline code (it may wrap over a line end) or a whole fenced block.
SPAN = re.compile(r"```.*?```|`[^`]+`", re.S)
# No placeholder, glob or absolute prefix before it; a whole extension after.
PATH = re.compile(
    r"(?<![\w./*<>{}$~-])([\w.-]+(?:/[\w.-]+)*\.(?:py|md|json))(?![\w/*])"
)
MAKE = re.compile(r"\bmake ([a-z][\w-]*)")
# Bare names of files a command writes or an operator supplies. In a
# fenced block (a console example) every bare ``.json`` name is one.
ARTIFACTS = {"manifest.json", "tuned.json", "A.json", "B.json"}


@functools.cache
def _basenames() -> frozenset[str]:
    names: set[str] = set()
    for dirpath, dirnames, filenames in os.walk(ROOT):
        dirnames[:] = [
            d for d in dirnames
            if d not in {".git", "__pycache__", ".jax_cache", "chiprun_out"}
        ]
        names.update(filenames)
    return frozenset(names)


def _exists(doc: str, path: str, basenames: frozenset[str]) -> bool:
    # From the root, the package or the document's directory; a bare name anywhere.
    for base in ("", "dynamo_exp_tpu", os.path.dirname(doc)):
        if os.path.exists(os.path.join(ROOT, base, path)):
            return True
    return "/" not in path and (path in basenames or path in ARTIFACTS)


@pytest.mark.parametrize("doc", DOCS)
def test_every_cited_path_exists(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    with open(os.path.join(ROOT, "Makefile")) as f:
        targets = set(re.findall(r"^([a-z][\w-]*):", f.read(), re.M))
    basenames = _basenames()
    missing = []
    for m in SPAN.finditer(text):
        n = text.count("\n", 0, m.start()) + 1
        example = m.group().startswith("```")
        missing += [
            f"{doc}:{n}: `{p}`"
            for p in PATH.findall(m.group())
            if not _exists(doc, p, basenames)
            and not (example and "/" not in p and p.endswith(".json"))
        ]
        missing += [
            f"{doc}:{n}: make {t}"
            for t in MAKE.findall(m.group())
            if t not in targets
        ]
    assert not missing, "cited but not in the checkout:\n" + "\n".join(missing)

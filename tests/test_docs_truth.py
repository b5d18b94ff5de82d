"""The documents a newcomer follows name only files that exist.

Every back-ticked token that ends in a source or record suffix resolves
from the repo's root, from ``paddle_tpu/`` or from the document's own
directory, and every ``python <script>`` names a script that is there.
"""
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", ".claude/skills/verify/SKILL.md",
             "chipbench/README.md", "paddle_tpu/observability/__init__.py"]
SUFFIXES = (".py", ".md", ".json", ".jsonl", ".spec")
# what a user supplies (a script) or a run writes (a checkpoint's
# manifest): names of no file of the repo
EXAMPLES = {"train.py", "manifest.json"}
TOKEN = re.compile(r"`+([^`\s]+)`+")
COMMAND = re.compile(r"\bpython3?\s+(?:-u\s+)?([\w./-]+\.py)\b")
NOT_A_PATH = re.compile(r"[<*{…]")


def _missing(document):
    with open(os.path.join(REPO, document)) as f:
        text = f.read()
    if document.endswith(".py"):  # the module's docstring only
        text = text.split('"""')[1]
    named = {re.sub(r":[:\d].*$", "", t) for t in TOKEN.findall(text)}
    named = {t for t in named if t.endswith(SUFFIXES)}
    named.update(COMMAND.findall(text))
    roots = [REPO, os.path.join(REPO, "paddle_tpu"),
             os.path.join(REPO, os.path.dirname(document))]
    return sorted(
        t for t in named
        if not (t.startswith("/") or NOT_A_PATH.search(t) or t in EXAMPLES
                or any(os.path.exists(os.path.join(r, t)) for r in roots)))


@pytest.mark.parametrize("document", DOCUMENTS)
def test_the_document_names_only_files_that_exist(document):
    assert _missing(document) == []

"""A deleted file cannot stay documented.

Every backticked name in the documents below that ends in ``.py`` or
``.md`` must match the end of a tracked file's path (``kvcache/paged.py``,
``batching.py`` and ``tools/kernel_parity.py`` all do).  Generated outputs
(``.json``) are not checked, and there is no list of exceptions: a name
that matches nothing is corrected in the document.
"""

import functools
import re
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCUMENTS = ("README.md", "docs/DESIGN.md", "PARITY.md",
             ".claude/skills/verify/SKILL.md")
BACKTICKED = re.compile(r"`+([^`\n]+?)`+")


@functools.cache
def tracked_files():
    """The files git would commit and that are on disk; in a checkout
    without ``.git``, the files that are there."""
    try:
        out = subprocess.run(["git", "ls-files"], cwd=REPO, check=True,
                             capture_output=True, text=True).stdout
        names = [n for n in out.splitlines() if (REPO / n).is_file()]
    except (OSError, subprocess.CalledProcessError):
        names = []
    return names or [str(p.relative_to(REPO)) for p in REPO.rglob("*")
                     if p.is_file()]


def named_files(text):
    """The last word of every backticked span that ends in .py or .md
    (`python chip_smoke.py` names ``chip_smoke.py``)."""
    for span in BACKTICKED.findall(text):
        if span.endswith((".py", ".md")):
            yield span.split()[-1]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_file_a_document_names_exists(document):
    paths = ["/" + n for n in tracked_files()]
    named = set(named_files((REPO / document).read_text()))
    missing = sorted(name for name in named
                     if not any(p.endswith("/" + name) for p in paths))
    assert not missing, f"{document} names files that are not in the tree: {missing}"

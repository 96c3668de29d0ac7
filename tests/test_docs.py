"""No document points at a file that is not there.

A backticked repo-relative path in ``README.md`` or ``docs/*.md`` must
exist.  A path is what starts with a tracked top-level directory, or a
root-level ``*.py`` name or upper-case ``*.md`` / ``*.json`` name (the
lower-case ``*.json`` names in the documents are files a run writes
into its checkpoint directory).
``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are not cases: they are
records, and name what was deleted.
"""

import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCUMENTS = ["README.md"] + sorted(
    f"docs/{p.name}" for p in (ROOT / "docs").glob("*.md")
)
_DIRS = ("libskylark_tpu", "benchmarks", "tests", "docs", "examples",
         "experiments")
_ROOT_FILE = re.compile(r"[\w.-]+\.py|[A-Z][A-Z0-9_][\w-]*\.(?:md|json)")


def _pointers(text):
    for span in re.findall(r"`([^`\n]+)`", text):
        path = re.split(r"::|:\d", span.strip(), maxsplit=1)[0]
        if "*" in path or "<" in path or " " in path:
            continue
        if path.split("/", 1)[0] in _DIRS and "/" in path:
            yield path
        elif _ROOT_FILE.fullmatch(path):
            yield path


@pytest.mark.parametrize("document", DOCUMENTS)
def test_no_pointer_to_a_missing_file(document):
    text = (ROOT / document).read_text()
    missing = sorted(
        {p for p in _pointers(text) if not (ROOT / p).exists()}
    )
    assert not missing, f"{document} names files that do not exist: {missing}"

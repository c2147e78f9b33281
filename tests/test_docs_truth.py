"""The documents name files, tests and knobs that exist.

``README.md``, every file of ``docs/``, the tier-1 workflow and the verify
notes are read for the paths and file names they cite: a path with a
directory part has to exist in the checkout, a ``tests/x.py::test_y`` has to
be a test of that file, a bare ``*.py`` / ``*.json`` name in backticks has to
be the basename of a tracked file, and every ``LMRS_*`` knob of
``docs/KNOBS.md`` has to be read somewhere in the code an operator runs.  A
document that sends its reader to a deleted script fails here, not in front
of the reader.
"""

from __future__ import annotations

import re
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = ["README.md", ".github/workflows/tier1.yml",
        ".claude/skills/verify/SKILL.md"] + sorted(
    f"docs/{p.name}" for p in (REPO / "docs").glob("*.md"))

# the reference's own files, which docs/PARITY.md maps onto this tree
REFERENCE_FILES = frozenset({
    "main.py", "big_chunkeroosky.py", "llm_executor.py",
    "result_aggregator.py", "simple_aggregator.py", "preprocessor.py",
    "transcript-example.json"})

_PATH = re.compile(
    r"(?<![\w./-])((?:lmrs_tpu|scripts|tests|benchmarks|docs|examples|native)"
    r"/[\w./-]*\w)")
_BARE = re.compile(r"`([\w-]+\.(?:py|json))`")
_NODE = re.compile(r"(tests/[\w/]+\.py)::(\w+)")


def _tracked() -> list[str]:
    out = subprocess.run(["git", "ls-files"], cwd=REPO, capture_output=True,
                         text=True, timeout=60)
    if out.returncode == 0 and out.stdout.strip():
        return out.stdout.split()
    # a checkout without its .git (an unpacked archive): what is on disk
    return [str(p.relative_to(REPO)) for p in REPO.rglob("*")
            if p.is_file() and ".git" not in p.parts]


@pytest.fixture(scope="module")
def basenames() -> frozenset[str]:
    return frozenset(Path(p).name for p in _tracked())


@pytest.mark.parametrize("doc", DOCS)
def test_every_file_a_document_names_exists(doc, basenames):
    text = (REPO / doc).read_text(encoding="utf-8")
    missing = sorted({
        p for p in _PATH.findall(text)
        if "*" not in p and "<" not in p and not (REPO / p).exists()})
    assert not missing, f"{doc} names paths that do not exist: {missing}"
    gone = sorted({
        f"{f}::{t}" for f, t in _NODE.findall(text)
        if f"def {t}(" not in (REPO / f).read_text(encoding="utf-8")})
    assert not gone, f"{doc} names tests that do not exist: {gone}"
    unknown = sorted({
        n for n in _BARE.findall(text)
        if n not in basenames and n not in REFERENCE_FILES})
    assert not unknown, f"{doc} names files no tracked file has: {unknown}"


def test_every_documented_knob_is_read_somewhere():
    knobs = set(re.findall(r"`(LMRS_[A-Z0-9_]+)`",
                           (REPO / "docs" / "KNOBS.md").read_text("utf-8")))
    assert knobs
    sources = [REPO / "chip_smoke.py"]
    for d in ("lmrs_tpu", "scripts", "benchmarks"):
        sources += sorted((REPO / d).rglob("*.py"))
    code = "\n".join(p.read_text(encoding="utf-8") for p in sources)
    read = set(re.findall(r"LMRS_[A-Z0-9_]+", code))
    assert not knobs - read, f"docs/KNOBS.md documents knobs nothing reads: " \
                             f"{sorted(knobs - read)}"

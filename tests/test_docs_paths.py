"""The documents name files and make targets that exist.

No session remembers another, so the documents are how a reader finds the
code; a path that left the tree sends them to a dead end. Each case takes
one document, collects from its code spans (inline back-ticks and fenced
blocks, word by word) every token that begins ``llmtrain_tpu/``,
``tools/`` or ``benchmarks/`` or is a bare ``name.py``, strips a trailing
``:line`` / ``::name``, and asserts that a prefixed path is a tracked file
or directory and that a bare ``name.py`` is the basename of a tracked
file; and that every ``make <target>`` is a target of the Makefile.
Prefixes shared with the reference repo (``tests/``, ``configs/``,
``k8s/``, ``src/``) are not checked, so ``docs/parity.md`` and
``docs/migration.md`` need no special case. ``CHANGES.md``, ``PERF.md``,
``ROADMAP.md`` and ``SURVEY.md`` are history, or cite the reference.
"""

from __future__ import annotations

import re
import subprocess
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
DOCUMENTS = [
    "README.md",
    *sorted(f"docs/{p.name}" for p in (REPO / "docs").glob("*.md")),
    ".claude/skills/verify/SKILL.md",
]
PREFIXES = ("llmtrain_tpu/", "tools/", "benchmarks/")
# What running the program leaves behind: never a document's subject.
UNTRACKED_DIRS = {".git", ".cache", "runs", "mlruns", "chiprun_out", "__pycache__", ".pytest_cache"}


@pytest.fixture(scope="module")
def tracked() -> set[str]:
    """Repo-relative paths of the tracked files: git's list where the
    checkout is a repository, the tree less what running leaves behind
    where it is a plain copy."""
    try:
        listed = subprocess.run(
            ["git", "ls-files"], cwd=REPO, capture_output=True, text=True, timeout=60
        )
    except (OSError, subprocess.TimeoutExpired):
        listed = None
    if listed is not None and listed.returncode == 0 and listed.stdout.strip():
        return {line for line in listed.stdout.splitlines() if (REPO / line).exists()}
    return {
        str(path.relative_to(REPO))
        for path in REPO.rglob("*")
        if path.is_file() and not UNTRACKED_DIRS & set(path.relative_to(REPO).parts)
    }


@pytest.fixture(scope="module")
def make_targets() -> set[str]:
    text = (REPO / "Makefile").read_text(encoding="utf-8")
    return set(re.findall(r"^([A-Za-z][\w\-]*):", text, flags=re.M))


def code_words(text: str) -> list[str]:
    """Every whitespace-separated word of the document's code spans."""
    fenced = re.findall(r"^```[^\n]*\n(.*?)^```", text, flags=re.S | re.M)
    rest = re.sub(r"^```[^\n]*\n.*?^```", "", text, flags=re.S | re.M)
    spans = fenced + re.findall(r"`([^`\n]+)`", rest)
    return [word for span in spans for word in span.split()]


def named_path(word: str) -> str | None:
    """The path a code word names, or None where it names none we check."""
    word = word.strip("\"'()[],;").split(":", 1)[0].rstrip(".,")
    if re.search(r"[*<>{}$…]|\.\.\.", word):
        return None  # a glob or a placeholder
    if word.startswith(PREFIXES) or re.fullmatch(r"[A-Za-z_][\w\-]*\.py", word):
        return word
    return None


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_files_and_targets_that_exist(document, tracked, make_targets):
    text = (REPO / document).read_text(encoding="utf-8")
    words = code_words(text)
    basenames = {path.rsplit("/", 1)[-1] for path in tracked}
    missing = []
    for path in sorted({p for p in map(named_path, words) if p}):
        if "/" not in path:
            found = path in basenames
        else:
            stem = path.rstrip("/")
            found = stem in tracked or any(t.startswith(stem + "/") for t in tracked)
        if not found:
            missing.append(path)
    assert not missing, f"{document} names paths that are not tracked: {missing}"
    named = {after.strip(".,;)") for word, after in zip(words, words[1:]) if word == "make"}
    unknown = sorted(t for t in named - make_targets if re.fullmatch(r"[a-z][\w\-]*", t))
    assert not unknown, f"{document} names make targets the Makefile lacks: {unknown}"

"""The documents a newcomer reads first must be true of the tree:
every file they cite exists, the README's account of the repo's speed
names the benchmark's cells, and no document sends the reader to the
knobs of a bench script that is gone."""
from __future__ import annotations

import functools
import json
import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCUMENTS = ["README.md",
             *sorted(f"docs/{p.name}" for p in (REPO / "docs").glob("*.md")),
             ".claude/skills/verify/SKILL.md"]

# What a run leaves behind or the reader brings: cited, never committed.
NOT_IN_THE_TREE = {
    ".jax_cache": "the compile cache, made by the first run in a checkout",
    ".bench_out": "the benchmark's run logs and traces, made by a run",
    "logs": "where an operator's config points telemetry and captures",
    "user_script.py": "the verify skill's stand-in for the reader's script",
}
# the front door's crash dumps: <prefix>.flight.jsonl / <prefix>.trace.json
MADE_BY_A_CRASH = (".flight.jsonl", ".trace.json")

SUFFIXES = (".py", ".md", ".json", ".jsonl", ".yml", ".yaml", ".txt",
            ".cpp", ".toml")
PATH = re.compile(r"^\.?[\w-][\w.-]*(?:/[\w.-]+)*/?$")
TICKED = re.compile(r"`([^`\n]+)`")
LINKED = re.compile(r"\]\(([^)\s]+)\)")
COMMAND = re.compile(r"python3?\s+(?:-m\s+pytest\s+)?([\w./-]+\.py)\b")


@functools.cache
def _tracked() -> tuple[str, ...]:
    """Every file of the checkout, repo-relative, but for ``.git`` and
    the directories ``.gitignore`` says a run leaves behind (a checkout
    need not be a git repository, so git is not asked)."""
    ignored = {".git"} | {
        line.strip().rstrip("/")
        for line in (REPO / ".gitignore").read_text().splitlines()
        if line.strip().endswith("/") and "*" not in line}
    found = []
    for folder, subfolders, files in os.walk(REPO):
        subfolders[:] = [d for d in subfolders if d not in ignored]
        found += [os.path.relpath(os.path.join(folder, f), REPO)
                  for f in files]
    return tuple(found)


def _cited(text: str, top_level: set[str]):
    """Backticked tokens that are paths (a known suffix, or a path under
    one of the repo's top-level directories; ``::test`` and ``:line``
    dropped), local markdown link targets, and ``python <file>.py``."""
    for match in TICKED.finditer(text):
        token = match.group(1).strip()
        if " " in token and token.split()[0].endswith(".py"):
            token = token.split()[0]            # `file.py --option ...`
        token = token.split("::")[0]
        token = re.sub(r":\d+(-\d+)?$", "", token)
        if PATH.match(token) and (
                token.endswith(SUFFIXES)
                or "/" in token and token.split("/")[0] in top_level):
            yield token
    for match in LINKED.finditer(text):
        target = match.group(1).split("#")[0]
        if target and not re.match(r"^[a-z]+://", target):
            yield target
    for match in COMMAND.finditer(text):
        yield match.group(1)


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_cites_exists(document):
    """A path is looked for from the repo's root, beside the document,
    and under the package (``serving/engine.py``) and its serving part
    (``frontend/scheduler.py``), as the documents write them; a bare
    file name must be the name of some tracked file."""
    tracked = _tracked()
    names = {Path(t).name for t in tracked}
    top_level = {t.split("/")[0] for t in tracked if "/" in t}
    where = (REPO, (REPO / document).parent, REPO / "torchbooster_tpu",
             REPO / "torchbooster_tpu" / "serving")
    missing = set()
    for path in _cited((REPO / document).read_text(), top_level):
        if path.split("/")[0] in NOT_IN_THE_TREE \
                or path.endswith(MADE_BY_A_CRASH):
            continue
        if "/" not in path.rstrip("/") and path in names:
            continue
        if not any((base / path).exists() for base in where):
            missing.add(path)
    assert not missing, f"{document} cites what is not there: " \
                        f"{sorted(missing)}"


def test_the_readme_names_every_cell_of_the_benchmark():
    """The README's performance section tells the benchmark's story:
    every cell of ``BENCHMARK.json`` by name. And no tracked document
    but the log of changes still names a knob of the retired bench
    script."""
    readme = (REPO / "README.md").read_text()
    section = readme.split("\n## Performance", 1)[1].split("\n## ", 1)[0]
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = [cell["name"] for cell in manifest["workloads"]]
    assert cells and not [c for c in cells if c not in section]
    knob = re.compile(r"BENCH_[A-Z_]+")
    left = {name: sorted(set(knob.findall((REPO / name).read_text())))
            for name in _tracked()
            if name.endswith(".md") and name not in ("CHANGES.md",
                                                     "ISSUE.md")}
    assert not {name: knobs for name, knobs in left.items() if knobs}

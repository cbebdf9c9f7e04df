"""The repository's records describe the tree that is there.

One benchmark (``benchmarks/run.py``, declared by ``BENCHMARK.json``; the
CPU-era ladder and its records went in PR 30), and entry documents (the
README, the docs index, the ``verify`` skill) that name no file which is
not in the tree.
"""

import fnmatch
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

LADDER = ("bench*.py", "BENCH_r*.json", "MULTICHIP_r*.json",
          "BENCH_DEFINITION.md")
ENTRY_DOCUMENTS = ("README.md", "docs/index.md",
                   ".claude/skills/verify/SKILL.md")
TREES = ("ratis_tpu/", "tests/", "benchmarks/", "docs/")
ROOT_FILE = re.compile(r"[\w.-]+\.(py|md|json)")
CODE_OR_LINK = re.compile(r"`([^`]+)`|\]\(([^)\s]+)\)")


def test_benchmarks_is_the_only_benchmark():
    left = [f for f in os.listdir(REPO)
            if any(fnmatch.fnmatch(f, pat) for pat in LADDER)]
    assert not left, f"the CPU-era ladder is back at the root: {left}"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    scripts = [a for a in command if a.endswith(".py")]
    assert scripts and all(
        os.path.isfile(os.path.join(REPO, s)) for s in scripts), command


def _braces(word: str) -> list[str]:
    """``a/{b,c}.py`` -> ``a/b.py``, ``a/c.py``."""
    m = re.search(r"\{([^{}]*)\}", word)
    if m is None:
        return [word]
    return [w for alt in m.group(1).split(",")
            for w in _braces(word[:m.start()] + alt.strip() + word[m.end():])]


def named_paths(text: str) -> set[str]:
    """Every repo-relative path a document names in back-ticks or as a
    link's target: a root ``*.py`` / ``*.md`` / ``*.json``, or anything
    under TREES; ``:line`` and ``::test`` stripped, brace lists expanded,
    patterns and placeholders left out."""
    out = set()
    for code, link in CODE_OR_LINK.findall(text):
        # a brace list may wrap over a line: it is one word
        named = re.sub(r"\{[^{}]*\}",
                       lambda m: re.sub(r"\s+", "", m.group(0)), code or link)
        for word in named.split():
            word = word.split("::")[0].split("#")[0]
            word = re.sub(r":[\d,-]+$|:\w+$", "", word).strip("\"'(),;.")
            if not word or re.search(r"[*<>$|]|\.\.\.", word):
                continue
            for w in _braces(word):
                if w.startswith(TREES) or ROOT_FILE.fullmatch(w):
                    out.add(w)
    return out


@pytest.mark.parametrize("document", ENTRY_DOCUMENTS)
def test_entry_documents_name_files_that_exist(document):
    with open(os.path.join(REPO, document)) as f:
        named = named_paths(f.read())
    assert named, f"{document} names no path at all: the reader is broken"
    here = os.path.dirname(os.path.join(REPO, document))
    missing = sorted(p for p in named
                     if not os.path.exists(os.path.join(REPO, p))
                     and not os.path.exists(os.path.join(here, p)))
    assert not missing, f"{document} names what is not in the tree: {missing}"

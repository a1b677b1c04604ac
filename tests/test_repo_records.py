"""The repository's account of itself is the repository.

README.md, docs/*.md and the verify skill are what a new session reads
first.  A file they name in back-ticks exists; a file that went is
named only beside the commit that last held it.  The root holds no
record of speed but ``PERF_LEDGER.jsonl`` (explained by ``PERF.md``):
the ``BENCH_r*`` / ``MULTICHIP_r*`` / ``BENCH_TPU_*`` files of the
rounds before the ledger were CPU runs of toy presets and are gone, and
every ``*.json`` that is there parses.

``PERF.md``, ``ROADMAP.md``, ``CHANGES.md`` and the ledger are not
scanned: histories name files as they were.  No jax here.
"""
import functools
import glob
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md"))))

# a back-ticked token is read as a path into the repository when it
# starts with one of these, or is a bare *.py / *.json / *.md name
_DIRS = ("mxnet_tpu/", "tools/", "chipbench/", "tests/", "benchmark/",
         "docs/", "example/")
_BARE = re.compile(r"^[^/]+\.(py|json|md)$")
# files a run writes or a model's source publishes: names, not paths
_WRITTEN_ELSEWHERE = {"MANIFEST.json", "meta.json", "config.json"}
# `path` ... commit `247ae3f`: the one way a document names a file
# that is no longer in the tree
_HELD_BY = re.compile(r"commit\s+`[0-9a-f]{7,40}`")
_TOKEN = re.compile(r"`([^`\n]+)`")


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if d == ".claude" or not d.startswith((".", "__"))]
        names.update(files)
    return names


def _missing(text):
    out = []
    for m in _TOKEN.finditer(text):
        token = m.group(1)
        if any(c in token for c in "<*{ "):       # placeholder or glob
            continue
        path = re.split(r"::|:(?=\d)", token)[0].rstrip("/")
        if path.startswith(_DIRS):
            there = os.path.exists(os.path.join(REPO, path))
        elif _BARE.match(path):
            there = path in _basenames() or path in _WRITTEN_ELSEWHERE
        else:
            continue
        if not there and not _HELD_BY.search(text, m.end(), m.end() + 120):
            out.append(token)
    return out


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_files_that_exist(document):
    with open(os.path.join(REPO, document)) as f:
        assert _missing(f.read()) == []


def test_the_scan_sees_a_missing_file_and_the_commit_beside_it():
    assert _missing("run `tools/no_such_tool.py --fast`") == []  # a space
    assert _missing("see `tools/no_such_tool.py`") == ["tools/no_such_tool.py"]
    assert _missing("see `no_such_record.json`.") == ["no_such_record.json"]
    assert _missing("`tests/test_repo_records.py::test_x`, "
                    "`mxnet_tpu/base.py:206`, `docs/`") == []
    assert _missing("`tools/no_such_tool.py` (last held by commit\n"
                    "`247ae3f`)") == []


@pytest.mark.parametrize("name", sorted(
    os.path.basename(p) for p in glob.glob(os.path.join(REPO, "*.json"))))
def test_root_json_parses(name):
    with open(os.path.join(REPO, name)) as f:
        json.load(f)


def test_no_record_of_speed_beside_the_ledger():
    old = [n for n in os.listdir(REPO)
           if n.startswith(("BENCH_r", "MULTICHIP_r", "BENCH_TPU_"))]
    assert old == []

"""The documents refer only to files that exist.

Every back-quoted word of a document that reads as a path into the
checkout must resolve: a deletion that leaves its citation behind fails
here. `PERF.md`, `ROADMAP.md` and `CHANGES.md` are records of what was
and may name what is gone.
"""

import functools
import os
import re

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DOCS = ["README.md", "MIGRATION.md", "benchmark/README.md",
        ".claude/skills/verify/SKILL.md",
        "docs/observability.md", "docs/op_audit.md",
        "docs/performance.md", "docs/robustness.md", "docs/serving.md",
        "docs/surface_audit.md"]

# git-ignored outputs, and files the program writes at run time
_IGNORED_DIRS = ("chiprun_out/", ".jax_cache/", "csrc/build/")
_RUNTIME_OUTPUTS = {"meta.json", "index.json", "metrics_sample.json",
                    "trace_sample.timeline.json"}
_FILE = re.compile(r"\.(py|json|jsonl|md|txt)$")


def _tracked(dirs):
    """Neither hidden (`.git`, `.jax_cache`, scratch copies) nor the
    chip tool's output."""
    return [d for d in dirs if not d.startswith(".") and d != "chiprun_out"]


@functools.lru_cache(maxsize=None)
def _top_level_dirs():
    return frozenset(d for d in _tracked(os.listdir(_REPO))
                     if os.path.isdir(os.path.join(_REPO, d)))


@functools.lru_cache(maxsize=None)
def _basenames():
    names = set()
    for _dir, dirs, files in os.walk(_REPO):
        dirs[:] = _tracked(dirs)
        names.update(files)
    return frozenset(names)


def _cited_paths(text):
    """Back-quoted words that read as paths, without a trailing
    `:line`, `:name` or `::test`."""
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            if any(c in word for c in "<*…") or word.startswith("/"):
                continue
            word = re.sub(r":.*$", "", word).rstrip(",.;)")
            if word and not word.startswith(_IGNORED_DIRS):
                yield word


def _missing(doc):
    # the root, the document's own directory, and the package roots
    # the documents write `serving/engine.py`-style paths from
    bases = [_REPO, os.path.join(_REPO, os.path.dirname(doc)),
             os.path.join(_REPO, "paddle_tpu"),
             os.path.join(_REPO, "benchmark")]
    with open(os.path.join(_REPO, doc)) as f:
        text = f.read()
    missing = []
    for path in _cited_paths(text):
        if "/" not in path:
            # a bare file name: some file of the checkout bears it
            ok = (not _FILE.search(path) or path in _basenames()
                  or path in _RUNTIME_OUTPUTS)
        elif path.split("/")[0] in _top_level_dirs() or _FILE.search(path):
            ok = any(os.path.exists(os.path.join(b, path)) for b in bases)
        else:
            ok = True        # `models/gpt.build_kv_step`: not a file
        if not ok:
            missing.append(path)
    return missing


@pytest.mark.parametrize("doc", DOCS)
def test_document_cites_only_files_that_exist(doc):
    assert _missing(doc) == []

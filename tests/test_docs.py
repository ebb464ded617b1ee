"""The documents a builder follows name only what the tree holds (ISSUE 31).

README.md and PERF.md send every later session to files, ``serve`` flags,
benchmark cells and metrics.  A name that went with a deleted file, a
renamed flag or a retired metric sends them to measure with the wrong
instrument, so each document is held to the tree: its paths resolve (or
are in ``test_bringup._DELETED``: said to be gone), the flags it gives
``serve`` are the parser's, and the cells and metrics it names in
``BENCHMARK.json``'s forms are ``BENCHMARK.json``'s.  A file still to be
written is named with its new part in angle brackets
(``perfbench/configs/<name>.json``), which no check reads.  ROADMAP.md is
left out: the session that re-anchors it runs no tests."""

import contextlib
import functools
import io
import json
import os
import re

import pytest

from test_bringup import _DELETED

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", "PERF.md")

# A path is recognised by its first segment, or as a bare file name.
_ROOT_SEGMENTS = ("kubernetes_tpu", "perfbench", "tests", "scripts", "go",
                  "native", "proto")
_BARE = re.compile(r"^[\w.-]+\.(py|json|md)$")
_NOT_A_PATH = set("<>*{}…$")
# Written when something runs, so never in a checkout: named as outputs.
_WRITTEN_AT_RUN_TIME = {"fleet-trace.json", "standby.json"}


@functools.lru_cache(maxsize=None)
def _spans(doc: str) -> tuple:
    """The document's code: every line of a fenced block, and every
    back-ticked span of the prose (a span may wrap over a line end)."""
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    spans = []
    for i, part in enumerate(re.split(r"^```.*$", text, flags=re.M)):
        if i % 2:
            spans += [line.strip() for line in part.splitlines() if line.strip()]
        else:
            spans += [" ".join(s.split()) for s in re.findall(r"`([^`]+)`", part)]
    return tuple(spans)


def _words(doc: str):
    for span in _spans(doc):
        for word in span.split():
            yield word.strip("\"'()[],;").rstrip(".:")


@functools.lru_cache(maxsize=None)
def _segments() -> frozenset:
    """First segments a path may begin with: the tracked top-level
    directories, and the packages' own (``engine/pass_.py``)."""
    found = set(_ROOT_SEGMENTS)
    for pkg in ("kubernetes_tpu", "perfbench"):
        for name in os.listdir(os.path.join(REPO, pkg)):
            if os.path.isdir(os.path.join(REPO, pkg, name)) and name[0] not in "._":
                found.add(name)
    return frozenset(found)


@functools.lru_cache(maxsize=None)
def _basenames() -> frozenset:
    names = set(os.listdir(REPO))
    for top in _ROOT_SEGMENTS + ("soak_dumps",):
        for _root, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            names.update(files)
    return frozenset(names)


@functools.lru_cache(maxsize=None)
def _gone() -> frozenset:
    """``_DELETED`` as a document may write it: whole, without its
    package (``benchmarks/integrated.py``), or as a bare name."""
    gone = set()
    for path in _DELETED:
        parts = path.split(os.sep)
        gone.update("/".join(parts[i:]) for i in range(len(parts)))
    return frozenset(gone)


def _resolves(path: str) -> bool:
    if path in _gone() or path in _WRITTEN_AT_RUN_TIME:
        return True
    if "/" not in path:
        return path in _basenames()
    # `engine/pipeline.drain_commit`: a name inside `engine/pipeline.py`.
    head, _, last = path.rpartition("/")
    module = f"{head}/{last.split('.', 1)[0]}.py"
    return any(
        os.path.exists(os.path.join(REPO, base, candidate))
        for base in ("", "kubernetes_tpu", "perfbench")
        for candidate in (path, module)
    )


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists_or_is_said_to_be_gone(doc):
    missing, seen = [], 0
    for word in _words(doc):
        # `scheduler.py:876–901`, `tests/test_x.py::test_y`: the file's part.
        word = re.sub(r":[:\d].*$", "", word)
        path = word.rstrip("/")
        if not path or _NOT_A_PATH & set(path):
            continue
        if "/" in word:
            # `perfbench/slice_start` is an event of the trace, `pass/dispatch`
            # a span: a path ends in a file name or in a slash.
            if path.split("/", 1)[0] not in _segments() or not (
                word.endswith("/") or "." in path.rsplit("/", 1)[-1]
            ):
                continue
        elif not _BARE.match(path):
            continue
        seen += 1
        if not _resolves(path):
            missing.append(path)
    assert seen, f"{doc}: no path recognised; the reader is broken"
    assert not missing, f"{doc} names what the tree does not hold: {sorted(set(missing))}"


@functools.lru_cache(maxsize=None)
def _serve_flags() -> frozenset:
    from kubernetes_tpu.__main__ import main

    out = io.StringIO()
    with contextlib.redirect_stdout(out), pytest.raises(SystemExit):
        main(["serve", "--help"])
    return frozenset(re.findall(r"--[a-z][a-z0-9-]*", out.getvalue()))


@pytest.mark.parametrize("doc", DOCS)
def test_every_flag_a_document_gives_serve_is_the_parsers(doc):
    assert {"--socket", "--journal-dir", "--speculate"} <= _serve_flags()
    unknown, seen = [], 0
    for span in _spans(doc):
        m = re.search(r"(?:^|[\s.])serve(\s.*)$", span)
        if m:
            # One command a span; a pipe or a second command ends it.
            tail = re.split(r"[|;&]|\spython3?\s", m.group(1))[0]
            given = re.findall(r"(?<![\w-])--[a-z][a-z0-9-]*", tail)
            seen += len(given)
            unknown += [f"{flag} in `{span[:80]}`" for flag in given
                        if flag not in _serve_flags()]
    assert seen, f"{doc}: no serve command recognised; the reader is broken"
    assert not unknown, f"{doc} gives serve flags it does not take: {unknown}"


@functools.lru_cache(maxsize=None)
def _benchmark() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("doc", DOCS)
def test_every_cell_and_metric_a_document_names_is_the_benchmarks(doc):
    """A cell is ``<config>.<mix>`` on one of the benchmark's configs.  A
    metric is recognised by the forms only the benchmark's names have: a
    cost counted per pod or per batch, or a name qualified by
    ``.arrivals`` / ``.observed``."""
    bench = _benchmark()
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    metrics = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    qualifiers = {n.split(".", 1)[1] for n in metrics if "." in n}
    metric_form = re.compile(
        r"^[a-z][a-z0-9_]*(_per_(pod|batch)|\.(%s))$" % "|".join(sorted(qualifiers))
    )
    unknown, seen = [], 0
    for word in _words(doc):
        if _NOT_A_PATH & set(word):
            continue
        config, _, mix = word.partition(".")
        if config in configs and re.fullmatch(r"[a-z_0-9]+", mix):
            seen += 1
            if word not in cells:
                unknown.append(word)
        elif metric_form.match(word):
            seen += 1
            # `pass_fetch_wait_ms_per_batch.arrivals` beside its plain form.
            if word not in metrics:
                unknown.append(word)
    assert seen, f"{doc}: no cell or metric recognised; the reader is broken"
    assert not unknown, f"{doc} names what BENCHMARK.json does not: {sorted(set(unknown))}"

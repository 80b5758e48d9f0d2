"""The documents that tell a session what to run name only what exists.

Every path one of them names in backticks (inline or in a fenced block)
must be in the tree: a PR that deletes a file and leaves the instruction
to use it fails here. ``PERF.md``, ``ROADMAP.md`` and ``CHANGES.md`` are
histories, name deleted files rightly, and are not scanned.
"""

import functools
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "chipbench/README.md", ".claude/skills/verify/SKILL.md"]
# a document may name a file from the repo's root, from inside the
# package (`coll/device.py`) or from inside the harness (`run.py`)
BASES = ("", "mvapich2_tpu", "chipbench")
EXTS = (".py", ".json", ".md", ".sh")
# made at run time, the caller's own operands, or MPICH's test names
ALLOW = {"PERF_LEDGER.jsonl", "chiprun_out/", "chipbench/.trace/",
         "OLD.json", "NEW.json", "trace.json",
         "coll/nbicallgather", "coll/nbicalltoall"}
PATH_CHARS = re.compile(r"^[A-Za-z0-9_.\-/]+$")


@functools.cache
def _basenames():
    out = set()
    for _root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "chiprun_out"]
        out.update(files)
    return out


def _named(doc):
    """The words of ``doc``'s backticked spans that claim to be a path of
    this tree: a known extension, or a ``/`` under one of the tree's own
    directories (``MPI_Send/Recv`` and ``p/254`` are not). A trailing
    ``:line``, ``:symbol`` or ``::test`` is stripped; globs,
    ``<placeholders>``, options and paths outside the tree are skipped."""
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    out = set()
    for span in re.findall(r"```.*?```|`[^`\n]+`", text, re.S):
        for w in span.strip("`").split():
            w = re.sub(r":[:A-Za-z_0-9,\-]*$", "",
                       w.strip("()[],;'\"")).rstrip(".:")
            if not w or w.startswith(("/", "-", "./")) \
                    or not PATH_CHARS.match(w):
                continue
            top = w.split("/")[0]
            if w.endswith(EXTS) or ("/" in w and any(
                    os.path.isdir(os.path.join(REPO, b, top))
                    for b in BASES)):
                out.add(w)
    return out


def _exists(w):
    if "/" not in w:
        return w in _basenames()    # `shm.py`: a file of that name
    tries = [w]
    head, _, last = w.rpartition("/")
    if "." in last and not w.endswith(EXTS):
        # `coll/tuning.device_tier`: a name inside coll/tuning.py
        tries.append(f"{head}/{last.split('.')[0]}.py")
    return any(os.path.exists(os.path.join(REPO, b, t))
               for b in BASES for t in tries)


@pytest.mark.parametrize("doc", DOCS)
def test_every_path_a_document_names_exists(doc):
    named = _named(doc)
    assert len(named) >= 5, f"{doc}: the scan found next to nothing"
    missing = sorted(w for w in named if w not in ALLOW and not _exists(w))
    assert not missing, f"{doc} names what the tree does not hold: {missing}"


def test_benchmark_json_names_files_that_exist():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    script = [a for a in bench["command"] if a.endswith(".py")]
    assert script and all(
        os.path.isfile(os.path.join(REPO, a)) for a in script), script
    for cfg in bench["configs"]:
        assert os.path.isfile(os.path.join(REPO, cfg["file"])), cfg["file"]
    for d in bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, d)), d

"""Documents name files that exist.

One case a document: every back-ticked repo path it names (``*.py``,
``*.sh``, ``*.md``, ``*.json``, or a directory written with its trailing
``/``) is in the tree. A path may be written from the root of the repo,
from the package (``serving/frontend.py``), from the document's own
directory, or by its tail (``engine_v2.py``, ``v2/engine_v2.py``: any file
that ends so). History
sections are left out: they say what was there.

This is the guard for a deletion: a PR that removes a file and leaves a
document describing the system by it fails here.
"""

import os
import re
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: documents checked whole, and ROADMAP.md's one paragraph
DOCUMENTS = ["README.md", "docs/CONCURRENCY.md", "docs/CONFIG.md",
             "docs/DIVERGENCES.md", "docs/OBSERVABILITY.md",
             "docs/SERVING.md", "docs/TRAINING.md",
             ".claude/skills/verify/SKILL.md", "benchmark/README.md",
             "ROADMAP.md#Tier-1 verify"]

#: headings whose sections are history (to the next heading of that level)
HISTORY = re.compile(r"^(#+) (Recent|.*\bFindings\b|.*\bHistory\b)",
                     re.MULTILINE)

#: written when the program runs, or an operator's own choice of name
RUN_TIME = {".jax_cache/", "chiprun_out/", ".scratch/", "t.json",
            "fleet_trace.json", "config.json", "ds_config.json",
            "latest/", "results_dir/"}

#: the reference project's own files, named where a document says what
#: a module here is the counterpart of; and JAX's name-stack segments
NOT_OURS = {"stage3.py", "stage_1_and_2.py", "layers.py",
            "deepspeed/inference/quantization/quantize.py",
            "checkpoint/", "checkpoint/rematted_computation/"}

SPAN = re.compile(r"`([^`\n]+)`")      # a name, or a command line
PATH = re.compile(r"^[\w.\-/]+(\.(py|sh|md|json)|/)$")


@pytest.fixture(scope="module")
def tree():
    """The files git would commit, and every directory above them."""
    out = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
        cwd=ROOT, capture_output=True, text=True, check=True).stdout
    files = {f for f in out.splitlines() if os.path.exists(
        os.path.join(ROOT, f))}
    dirs = set()
    for f in files:
        while "/" in f:
            f = f.rsplit("/", 1)[0]
            dirs.add(f + "/")
    return files, dirs


def _text(document):
    name, _, paragraph = document.partition("#")
    with open(os.path.join(ROOT, name)) as fh:
        text = fh.read()
    if paragraph:
        start = text.index(f"**{paragraph}:**")
        return name, text[start:text.index("\n\n", start)]
    for m in reversed(list(HISTORY.finditer(text))):
        nxt = re.compile(rf"^#{{1,{len(m.group(1))}}} ", re.MULTILINE)
        end = nxt.search(text, m.end())
        text = text[:m.start()] + (text[end.start():] if end else "")
    return name, text


def _named_paths(text):
    for span in SPAN.findall(text):
        for token in span.split():
            token = re.sub(r":\d+([-,]\d+)*$", "", token.rstrip(".,;:"))
            if PATH.match(token) and not token.startswith(("/", "~")):
                yield token


def _exists(path, document, files, dirs):
    here = os.path.dirname(document)
    bases = ["", "deepspeed_tpu/", "tests/", here + "/" if here else ""]
    pool = dirs if path.endswith("/") else files
    if any(os.path.normpath(b + path) + ("/" if path.endswith("/") else "")
           in pool for b in bases):
        return True
    return any(p.endswith("/" + path) for p in pool)   # the tail of a path


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_real_files(document, tree):
    files, dirs = tree
    name, text = _text(document)
    named = sorted(set(_named_paths(text)) - RUN_TIME - NOT_OURS)
    assert named, f"{document}: no path found — the reader is broken"
    missing = [p for p in named if not _exists(p, name, files, dirs)]
    assert not missing, f"{document} names files that are not there: {missing}"

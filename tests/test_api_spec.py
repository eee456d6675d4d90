"""Golden API-surface test (paddle/fluid/API.spec +
tools/print_signatures.py parity): the committed API.spec must match the
live public signatures; regenerate deliberately with
`python tools/print_signatures.py --update` when the API changes. Beside
it, the other committed documents are held to the live tree: every path
they name exists."""

import glob
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import print_signatures  # noqa: E402


def test_api_spec_matches_committed_golden():
    live = list(print_signatures.iter_spec())
    with open(os.path.join(REPO, "API.spec")) as f:
        committed = [
            line for line in f.read().splitlines()
            # '#' lines annotate DELIBERATE absences vs the reference
            # surface (async-pserver methods etc.); they are docs, not
            # signatures
            if line.strip() and not line.lstrip().startswith("#")
        ]
    live_set, committed_set = set(live), set(committed)
    removed = committed_set - live_set
    added = live_set - committed_set
    msg = []
    if removed:
        msg.append("API signatures removed/changed:\n  " +
                   "\n  ".join(sorted(removed)[:20]))
    if added:
        msg.append("API signatures added (update API.spec):\n  " +
                   "\n  ".join(sorted(added)[:20]))
    assert not msg, (
        "\n".join(msg) +
        "\nIf intentional: python tools/print_signatures.py --update"
    )


def test_api_spec_covers_core_surface():
    with open(os.path.join(REPO, "API.spec")) as f:
        spec = f.read()
    for must in [
        "paddle_tpu.layers.nn.fc ",
        "paddle_tpu.layers.nn.conv2d ",
        "paddle_tpu.layers.detection.ssd_loss ",
        "paddle_tpu.optimizer.Adam CLASS",
        "paddle_tpu.io.save_inference_model ",
        "paddle_tpu.backward.append_backward ",
    ]:
        assert must in spec, "missing from API.spec: %r" % must


# a path under tools/, benchmark/, docs/ or tests/, or one of the two
# scripts at the root, as documents and docstrings write it
_WRITTEN_PATH = re.compile(
    r"(?<![\w/.-])((?:tools|benchmark|docs|tests)/[\w./-]*\w\.(?:py|sh|md|json)"
    r"|bench\.py|chip_smoke\.py)\b")
# the reference system's own tree, which docstrings cite for parity
_REFERENCE = ("benchmark/fluid/", "benchmark/paddle/", "tests/unittests/",
              "tests/book/", "tools/timeline.py")


def test_every_path_the_documents_name_exists():
    """README.md, docs/, the verify skill and the source text of
    paddle_tpu/ and tools/ may name only files the tree has: a deleted
    tool leaves no pointer behind."""
    sources = [os.path.join(REPO, "README.md"),
               os.path.join(REPO, ".claude", "skills", "verify", "SKILL.md")]
    sources += glob.glob(os.path.join(REPO, "docs", "*.md"))
    sources += glob.glob(os.path.join(REPO, "tools", "*.py"))
    sources += glob.glob(os.path.join(REPO, "paddle_tpu", "**", "*.py"),
                         recursive=True)
    missing = []
    for source in sources:
        with open(source) as f:
            for path in set(_WRITTEN_PATH.findall(f.read())):
                if not (path.startswith(_REFERENCE)
                        or os.path.exists(os.path.join(REPO, path))):
                    missing.append("%s names %s" % (
                        os.path.relpath(source, REPO), path))
    assert not missing, "\n".join(sorted(missing))

"""The documents name only files that exist.

README.md, every doc/*.md and tools/run_suite.sh send a reader to
files of this repo; a path that is gone sends them nowhere (before PR
32 they pointed at a harness and record files the benchmark never
read).  Candidates are found by one regular expression: (i) a path
under tools/, doc/, lightning_tpu/, tests/ or benchmarks/ that ends in
.py, .md, .json, .jsonl or .sh; (ii) a bare top-level *.py, or an
upper-case *.md / *.json / *.jsonl name (README.md, BENCHMARK.json,
PERF_LEDGER.jsonl).  Lower-case example names (out.json, snap.json, a
bundle's manifest.json) are no candidates.  Jax-free, milliseconds.
"""
from __future__ import annotations

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PATH = re.compile(
    r"(?<![\w/.*-])(?:"
    r"(?:tools|doc|lightning_tpu|tests|benchmarks)/[\w./-]*\.(?:py|md|jsonl|json|sh)"
    r"|\w+\.py"
    r"|[A-Z][A-Z0-9_]*\.(?:md|jsonl|json)"
    r")(?![\w/])")

DOCUMENTS = ["README.md", "tools/run_suite.sh"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "doc", "*.md")))


def named_paths(text: str) -> set[str]:
    return set(_PATH.findall(text))


def test_the_rule_reads_what_it_should():
    text = ("see `tools/perf_report.py --selfcheck`, doc/perf.md and "
            "`PERF_LEDGER.jsonl`; run chip_smoke.py; write out.json, "
            "gossip/verify.py:12 and tests/test_zz_*.py stay out")
    assert named_paths(text) == {
        "tools/perf_report.py", "doc/perf.md", "PERF_LEDGER.jsonl",
        "chip_smoke.py"}


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_path_a_document_names_exists(document):
    with open(os.path.join(REPO, document)) as f:
        names = named_paths(f.read())
    # a markdown link is relative to its document: doc/'s [RPC.md](RPC.md)
    roots = (REPO, os.path.dirname(os.path.join(REPO, document)))
    missing = sorted(n for n in names if not any(
        os.path.exists(os.path.join(r, n)) for r in roots))
    assert not missing, f"{document} names files that are not there"

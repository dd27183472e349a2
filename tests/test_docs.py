"""Documentation drift guards: the evidence and design docs cite repo
files and symbols; a rename or deletion must fail HERE, not silently
rot the docs (stale citations were the most common review-finding
class while these docs grew)."""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = [
    "README.md", "PERF.md", "BASELINE.md",
    "docs/DESIGN.md", "docs/PARITY.md", "docs/PORTING.md",
    "docs/OPERATIONS.md",
]

# symbols the docs name as load-bearing API
DOC_SYMBOLS = [
    ("bench.py", "def run_with_hard_timeout"),
    ("bench.py", "def run_json_child"),
    ("gelly_streaming_tpu/ops/neighborhood.py", "def _make_pane_reduce"),
    ("gelly_streaming_tpu/ops/neighborhood.py", "def window_stack_combine"),
    ("gelly_streaming_tpu/ops/segment.py",
     "def segmented_reduce_associative"),
    ("gelly_streaming_tpu/ops/triangles.py", "def resolve_xla_intersect"),
    ("gelly_streaming_tpu/ops/triangles.py", "def _tuned_kb"),
    ("gelly_streaming_tpu/parallel/sharded.py",
     "def make_sharded_pane_reduce"),
    ("gelly_streaming_tpu/core/platform.py", "def use_cpu"),
]


def _exists_somewhere(path: str) -> bool:
    cands = [path, os.path.join("gelly_streaming_tpu", path),
             os.path.join("tests", path), os.path.join("docs", path),
             os.path.join("tools", path), os.path.join("examples", path)]
    if os.path.basename(path) == path and path.startswith("test_"):
        return any(path in files
                   for _r, _d, files in os.walk(os.path.join(REPO, "tests")))
    return any(os.path.exists(os.path.join(REPO, c)) for c in cands)


def test_doc_file_citations_resolve():
    bad = []
    for doc in DOCS:
        text = open(os.path.join(REPO, doc), encoding="utf-8").read()
        cited = set(re.findall(
            r"`([A-Za-z_][A-Za-z0-9_/.]*\.(?:py|sh|md|json|cpp))`", text))
        cited |= set(re.findall(r"\b(tests/[a-z0-9_/]+\.py)\b", text))
        cited |= set(re.findall(r"\b(test_[a-z0-9_]+\.py)\b", text))
        for c in sorted(cited):
            # driver/queue-produced per-round artifacts may not exist
            # yet (BENCH_r02.json lands at end of round;
            # BENCH_chip_rNN.json is the queue's in-window snapshot)
            if re.match(r"(BENCH|MULTICHIP)(_chip)?_r(\{?N\}?|NN|\d+)",
                        os.path.basename(c)):
                continue
            if not _exists_somewhere(c):
                bad.append((doc, c))
    assert not bad, bad


def test_doc_symbol_citations_resolve():
    bad = [(f, sym) for f, sym in DOC_SYMBOLS
           if sym not in open(os.path.join(REPO, f),
                              encoding="utf-8").read()]
    assert not bad, bad

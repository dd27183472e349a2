"""Example-CLI smoke tests: every reference workload has a CLI twin
under examples/ (SURVEY.md §2.3); these pin the entry points' argument
surface and end-to-end output on a tiny graph, in hermetic CPU
subprocesses (the CLIs pick their own backend; tests must not touch
the real chip)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EDGES = "1 2 100\n1 3 150\n3 2 200\n2 4 250\n3 4 300\n4 5 400\n"


def _run(args, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True,
                          timeout=timeout)


@pytest.fixture(scope="module")
def edge_file(tmp_path_factory):
    p = tmp_path_factory.mktemp("cli") / "edges.txt"
    p.write_text(EDGES)
    return str(p)


def test_window_triangles_cli(edge_file, tmp_path):
    out = str(tmp_path / "tri.txt")
    r = _run(["examples/window_triangles.py", edge_file, out, "200"])
    assert r.returncode == 0, r.stderr[-500:]
    lines = sorted(open(out).read().split())
    # triangle {2,3,4} completes in the 200-399 window
    assert "(1,399)" in lines


def test_connected_components_cli(edge_file, tmp_path):
    out = str(tmp_path / "cc.txt")
    r = _run(["examples/connected_components.py", edge_file, out, "100"])
    assert r.returncode == 0, r.stderr[-500:]
    text = open(out).read()
    assert text.strip(), "no component output"


def test_bipartiteness_cli(edge_file, tmp_path):
    out = str(tmp_path / "bip.txt")
    r = _run(["examples/bipartiteness_check.py", edge_file, out, "100"])
    assert r.returncode == 0, r.stderr[-500:]
    text = open(out).read()
    # the graph has triangles -> odd cycle -> not bipartite at the end
    assert "false" in text.lower()


def test_sliding_degree_sums_cli(edge_file, tmp_path):
    out = str(tmp_path / "slide.txt")
    r = _run(["examples/sliding_degree_sums.py", edge_file, out,
              "200", "100"])
    assert r.returncode == 0, r.stderr[-500:]
    lines = sorted(open(out).read().split())
    # vertex 1's [0,200) window sums edges (1,2,100)+(1,3,150) = 250
    assert "1,250" in lines


def test_measurements_cli_degrees(edge_file):
    r = _run(["examples/measurements.py", "degrees", edge_file, "8"])
    assert r.returncode == 0, r.stderr[-500:]
    import json

    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["workload"] == "degrees" and row["edges"] == 6


@pytest.mark.parametrize("cli", [
    "iterative_connected_components",
    "broadcast_triangle_count",
    "incidence_sampling_triangle_count",
    "centralized_weighted_matching",
    "degree_aggregate",
    "streaming_analytics",
])
def test_remaining_clis_run_with_defaults(cli):
    """Every example CLI must at least run its built-in default data
    end-to-end (argument-surface regressions fail loudly here; the
    deeper output checks live in the per-workload tests above and in
    tests/library/)."""
    r = _run([f"examples/{cli}.py"])
    assert r.returncode == 0, (cli, r.stderr[-500:])


def test_centralized_weighted_matching_on_movielens_file():
    """The matching example end-to-end on a MovieLens-format file
    (user\\titem\\trating\\ttimestamp, timestamp-sorted — the shape of
    the reference's hard-coded movielens_10k_sorted.txt input,
    CentralizedWeightedMatching.java:44): a committed 2,000-line
    fixture with ml-100k's id ranges and a zipf-ish popularity skew."""
    fixture = os.path.join(REPO, "tests", "fixtures",
                           "movielens_2k_sorted.txt")
    r = _run(["examples/centralized_weighted_matching.py", fixture])
    assert r.returncode == 0, r.stderr[-500:]
    out = r.stdout
    # the matcher must have emitted add/replace events and the
    # reference-format runtime line
    assert "ADD" in out, out[:500]
    assert "Runtime:" in out
    # user/item id spaces: items are shifted by 1,000,000 (reference
    # parsing contract) — every matched edge respects it
    import re

    pairs = re.findall(r"ADD (\d+),(\d+),\d+", out)
    assert pairs, "no matched edges printed"
    assert all(int(b) > 1_000_000 > int(a) for a, b in pairs)


@pytest.fixture(scope="module")
def citation_file(tmp_path_factory):
    """The full calibrated cit-HepPh-shaped stream (421,578 edges,
    utils/realgraph.py — validated against SNAP's published stats in
    tests/library/test_realgraph.py) as a 'src dst ts' file."""
    import numpy as np

    from gelly_streaming_tpu.utils.realgraph import citation_stream

    src, dst, ts = citation_stream()
    p = tmp_path_factory.mktemp("cit") / "citation.txt"
    with open(p, "w") as f:
        np.savetxt(f, np.stack([src, dst, ts], 1), fmt="%d")
    return str(p)


# Seed-pinned goldens for the calibrated stream, computed by the
# measured host tier and cross-checked against the native C++ tier
# (tests/library/test_triangles.py proves both match the device kernel
# and brute force). ts = arrival index, so window_ms = 32768 gives
# exactly 32768-edge windows.
CITATION_WINDOW_COUNTS = [
    129829, 8285, 4259, 2894, 2335, 1915, 1384, 1259, 1270, 1029,
    945, 714, 525]
CITATION_TOTAL_TRIANGLES = 1_315_736   # == realgraph's calibrated total
CITATION_NODES = 34_546


def test_window_triangles_cli_on_citation_stream(citation_file,
                                                 tmp_path):
    """VERDICT r3 item 6: the headline workload end-to-end through the
    CLI surface on real-shaped data — 13 windows, every per-window
    count exact. A dropped window, a shifted boundary, or a lost chunk
    anywhere in file→parse→window→count→sink changes a line."""
    out = str(tmp_path / "cit_tri.txt")
    r = _run(["examples/window_triangles.py", citation_file, out,
              "32768", "--fused"], timeout=300)
    assert r.returncode == 0, r.stderr[-500:]
    lines = open(out).read().split()
    # wmax is the window's nominal end boundary (Flink TimeWindow
    # maxTimestamp), also for the ragged final window
    want = ["(%d,%d)" % (c, (w + 1) * 32768 - 1)
            for w, c in enumerate(CITATION_WINDOW_COUNTS)]
    assert lines == want


def test_window_triangles_cli_citation_whole_graph(citation_file,
                                                   tmp_path):
    """One window covering the whole stream reproduces the graph's
    calibrated triangle total through the CLI."""
    out = str(tmp_path / "cit_tri1.txt")
    r = _run(["examples/window_triangles.py", citation_file, out,
              "1000000", "--fused"], timeout=300)
    assert r.returncode == 0, r.stderr[-500:]
    assert open(out).read().split() == [
        "(%d,999999)" % CITATION_TOTAL_TRIANGLES]


def test_connected_components_cli_on_citation_stream(citation_file,
                                                     tmp_path):
    """Streaming CC through the CLI on the full citation stream: the
    final merged DisjointSet must contain every one of the 34,546
    papers in one component (verified against an independent
    union-find oracle over the same file), so any dropped edge batch
    that disconnects the merge shows up."""
    import re

    import numpy as np

    out = str(tmp_path / "cit_cc.txt")
    r = _run(["examples/connected_components.py", citation_file, out,
              "1000"], timeout=300)
    assert r.returncode == 0, r.stderr[-500:]
    last = open(out).read().strip().split("\n")[-1]
    n_components = last.count("[")
    members = sorted(int(m) for m in re.findall(
        r"(?<=[\[\s,])\d+(?=[,\]\s])", last[last.index("=") :]))
    # independent oracle: plain union-find over the parsed file
    src, dst = np.loadtxt(citation_file, dtype=np.int64,
                          usecols=(0, 1)).T
    parent = np.arange(CITATION_NODES)

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in zip(src, dst):
        ra, rb = find(a), find(b)
        if ra != rb:
            parent[ra] = rb
    roots = {find(v) for v in range(CITATION_NODES)}
    assert n_components == len(roots) == 1
    assert members == list(range(CITATION_NODES))


def test_measurements_cli_reduce(edge_file):
    """BASELINE config #2's measured leg (columnar reduceOnEdges
    sum-of-weights) runs through the CLI surface."""
    r = _run(["examples/measurements.py", "reduce", edge_file, "8"])
    assert r.returncode == 0, r.stderr[-500:]
    import json

    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["workload"].startswith("reduce_on_edges")
    assert row["edges"] == 6 and row["windows"] >= 1

"""A copy of the benchmark in a temporary directory with a tiny twin of
every cell (`tiny.<cell>`): the same entry, traffic shape and metrics,
at sizes a CPU test holds. A test may add cells of its own (`extra`,
entries shaped as BENCHMARK.json's), as a later PR would: the serve
and mesh cells, whose code stays tested until a PR measures them. Runs
go through `harness.main` in-process with `--cpu`, so nothing looks
for a chip."""

import contextlib
import copy
import io
import json
import os
import shutil

from benchmark import harness

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SHRINK_CONFIG = {
    "driver": {"num_vertices": 1024, "vertex_bucket": 1024},
    "serve": {"tenants": 4, "edge_bucket": 256, "vertex_bucket": 512},
}
SHRINK_TRAFFIC = {
    "driver": {"call_edges": 4096, "pool_calls": 4, "warm_calls": 1,
               "settle_calls": 1, "check_per_call": 2},
    "serve": {"feed_edges": 64, "rate_edges_per_s": 4000, "connections": 2,
              "drain_wait_s": 20},
}


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_tree(dst: str, extra: dict = None) -> str:
    """Copy BENCHMARK.json and the benchmark's directory to `dst`, add
    the entries of `extra`, and add the tiny cells. Returns `dst`."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(dst, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _with(json.load(open(os.path.join(dst, "BENCHMARK.json"))),
                  extra)
    for cfg in list(bench["configs"]):
        body = json.load(open(os.path.join(dst, cfg["file"])))
        body.update(SHRINK_CONFIG[body["entry"]])
        path = "benchmark/configs/tiny.%s.json" % cfg["name"]
        _dump(body, os.path.join(dst, path))
        bench["configs"].append(dict(cfg, name="tiny." + cfg["name"],
                                     file=path))
    for wl in list(bench["workloads"]):
        entry = json.load(open(os.path.join(
            dst, [c["file"] for c in bench["configs"]
                  if c["name"] == wl["config"]][0])))["entry"]
        tr_path = os.path.join(dst, "benchmark", "traffic",
                               wl["traffic"] + ".json")
        traffic = json.load(open(tr_path))
        traffic.update(SHRINK_TRAFFIC[entry])
        if "window_edges" in traffic:
            traffic["window_edges"] = max(64, traffic["window_edges"] // 128)
        _dump(traffic, os.path.join(dst, "benchmark", "traffic",
                                    "tiny." + wl["traffic"] + ".json"))
        bench["workloads"].append(dict(
            wl, name="tiny." + wl["name"], config="tiny." + wl["config"],
            traffic="tiny." + wl["traffic"]))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += ["tiny." + w for w in m["workloads"]]
    _dump(bench, os.path.join(dst, "BENCHMARK.json"))
    return dst


def _with(bench: dict, extra: dict = None) -> dict:
    """`bench` with the entries of `extra` added; a metric that is
    there already gets the extra entry's cells added to its own."""
    for key, entries in copy.deepcopy(extra or {}).items():
        have = {e["name"]: e for e in bench[key]}
        for e in entries:
            if e["name"] in have:
                have[e["name"]]["workloads"] += e["workloads"]
            else:
                bench[key].append(e)
    return bench


def cells(extra: dict = None) -> list:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in _with(json.load(f), extra)["workloads"]]


KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def assert_rehearsal(root: str, cell: str) -> None:
    """A tiny run of `cell` is correct and its last line has the
    contract's keys, `checks` last."""
    line = run(root, "tiny." + cell)
    assert KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    names = set(line["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert all(m["value"] > 0 for m in line["metrics"].values())


def assert_caught(root: str, cell: str, fault: str) -> None:
    """A tiny run of `cell` with `fault` planted reads not correct."""
    line = run(root, "tiny." + cell, seed=2 ** 33 + 11, fault=fault)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def run(root: str, workload: str, seed: int = 2 ** 40 + 7,
        seconds: float = 1.0, trace: int = 0, fault: str = None) -> dict:
    """One in-process CPU run; returns the parsed last stdout line."""
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(trace), "--cpu"]
    if fault:
        argv += ["--fault", fault]
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(argv, root=root)
    assert rc == 0, rc
    return json.loads(out.getvalue().strip().splitlines()[-1])

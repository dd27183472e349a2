"""The harness is driven by data: BENCHMARK.json keeps to the contract's
shape, a new configuration, traffic mix and per-layer metric run from
files alone, and a run without a chip, or without the program, prints
no result."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from tests.bench import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    root = tiny.REPO
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(root, p))
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
        body = json.load(open(os.path.join(root, c["file"])))
        assert os.path.exists(os.path.join(
            root, "benchmark", "entries", body["entry"] + ".py"))
        assert set(c["reduced"]) == set(body.get("reduced", {}))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            root, "benchmark", "traffic", w["traffic"] + ".json"))
        reported = [m for m in bench["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in bench["per_layer"])
    for m in bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert os.path.exists(os.path.join(
            root, "benchmark", "metrics", m["name"] + ".py"))
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
    assert len(json.dumps(bench)) < 64 * 1024


def test_new_config_traffic_and_metric_from_files_alone(tmp_path):
    root = tiny.make_tree(str(tmp_path))
    b = os.path.join(root, "benchmark")
    cfg = json.load(open(os.path.join(b, "configs", "tiny.twitter-wtc.json")))
    cfg.update(num_vertices=2048, vertex_bucket=2048,
               analytics=["degrees", "cc", "bipartite", "triangles"])
    json.dump(cfg, open(os.path.join(b, "configs", "extra.json"), "w"))
    json.dump({"window_edges": 128, "call_edges": 2048, "pool_calls": 3,
               "warm_calls": 1, "check_per_call": 1},
              open(os.path.join(b, "traffic", "extra-128.json"), "w"))
    with open(os.path.join(b, "metrics", "calls_seen.py"), "w") as f:
        f.write("def read(run, trace):\n"
                "    return float(run.counters['calls'])\n")
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    bench["configs"].append({"name": "extra", "source": "a test",
                             "file": "benchmark/configs/extra.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "extra.cell", "config": "extra",
                               "traffic": "extra-128", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"]:
        if "edges_per_s" == m["name"]:
            m["workloads"].append("extra.cell")
    bench["per_layer"].append({"name": "calls_seen", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "edges_per_s",
                               "workloads": ["extra.cell"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    plain = tiny.run(root, "extra.cell")
    assert plain["correct"] and set(plain["metrics"]) == {"edges_per_s",
                                                          "setup_s"}
    traced = tiny.run(root, "extra.cell", trace=1)
    assert traced["correct"] and traced["metrics"]["calls_seen"]["value"] > 0
    assert set(traced["device"]) >= {"busy_s", "window_s"}
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}


def _run_py(cwd, extra_env, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env.update(extra_env)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "wtc-32k",
         "--seed", "5", "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    got = _run_py(tiny.REPO, {"JAX_PLATFORMS": "cpu"})
    assert got.returncode != 0 and got.stdout.strip() == ""
    assert "no result" in got.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), tmp_path)
    for p in ("benchmark", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(tiny.REPO, p), tmp_path / p)
    got = _run_py(str(tmp_path), {"JAX_PLATFORMS": "cpu"}, "--cpu")
    assert got.returncode != 0 and got.stdout.strip() == ""

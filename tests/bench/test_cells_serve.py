"""CPU rehearsal of the serve cell at tiny size through a loopback
StreamServer and the JAX-free load generator, and the faults it can
have, planted in the server: a state left unchanged, half of each feed
left out, an answer altered, and the control (an edge re-sent).

The cell is not in BENCHMARK.json yet (PERF.md, Open questions): the
test adds it as a later PR would, with entries of the manifest's shape
over the files already in `benchmark/`."""

import pytest

from tests.bench import tiny

SERVE = {
    "configs": [{"name": "tenant-serve", "source": "YCSB core workloads",
                 "file": "benchmark/configs/tenant-serve.json",
                 "reduced": ["tenants"], "why": "served tenant streams"}],
    "workloads": [{"name": "serve-zipf64", "config": "tenant-serve",
                   "traffic": "zipf64-poisson", "chips": 1,
                   "why": "open-loop Poisson feeds over the wire"}],
    "end_to_end": [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": ["serve-zipf64"]}
        for n in ("window_p50_ms", "window_p95_ms")],
    "per_layer": [
        {"name": n, "unit": u, "better": b, "source": s, "layer": layer,
         "moves": "window_p95_ms", "workloads": ["serve-zipf64"]}
        for n, u, b, s, layer in (
            ("idle_pct.serve", "%", "lower", "device_trace", "device"),
            ("cohort_windows_per_dispatch", "windows", "higher",
             "device_trace", "tenancy and driver"),
            ("feed_rtt_p95_ms", "ms", "lower", "host_clock",
             "serve front end"))],
}
CELLS = ["serve-zipf64"]
FAULTS = [(c, f) for c in CELLS
          for f in ["state_unchanged", "half_batch", "altered", "control"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_tree(str(tmp_path_factory.mktemp("bench")), SERVE)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line(root, cell):
    tiny.assert_rehearsal(root, cell)


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_caught(root, cell, fault):
    tiny.assert_caught(root, cell, fault)

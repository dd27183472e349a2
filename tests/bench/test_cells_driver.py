"""CPU rehearsal of the driver cells at tiny size, and the faults each
can have, planted in the timed path: a state left unchanged, half of
each window left out, the exchange between chips left out (mesh), an
answer altered, and the control (the reference with a stated guarantee
broken: an edge re-sent in place of another).

The mesh cell is not in BENCHMARK.json now (PERF.md, Open questions):
the test adds it as a later PR would, over the files in `benchmark/`."""

import pytest

from tests.bench import tiny

P4 = {
    "configs": [{"name": "twitter-wtc-p4", "source": "twitter-wtc, 4 chips",
                 "file": "benchmark/configs/twitter-wtc-p4.json",
                 "reduced": ["num_vertices"], "why": "sharded driver"}],
    "workloads": [{"name": "wtc-32k-p4", "config": "twitter-wtc-p4",
                   "traffic": "count-32k", "chips": 4,
                   "why": "the driver over a 4-chip mesh"}],
    "end_to_end": [{"name": "edges_per_s", "workloads": ["wtc-32k-p4"]}],
    "per_layer": [
        {"name": "idle_pct.bulk", "workloads": ["wtc-32k-p4"]},
        {"name": "windows_per_dispatch", "workloads": ["wtc-32k-p4"]},
        {"name": "exposed_collective_pct", "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "engines",
         "moves": "edges_per_s", "workloads": ["wtc-32k-p4"]}],
}
CELLS = [c for c in tiny.cells(P4) if not c.startswith("serve")]
FAULTS = [(c, f) for c in CELLS
          for f in ["state_unchanged", "half_batch", "altered", "control"]
          + (["no_exchange"] if c.endswith("p4") else [])]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_tree(str(tmp_path_factory.mktemp("bench")), P4)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_line(root, cell):
    tiny.assert_rehearsal(root, cell)


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_caught(root, cell, fault):
    tiny.assert_caught(root, cell, fault)

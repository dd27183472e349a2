"""The mesh cell (wtc-32k-p4, config twitter-wtc-p4) is in BENCHMARK.json,
while `test_cells_driver.py` still adds it as an extra entry, as a later
PR would. `tiny._with` would meet that config and cell by name and raise
KeyError, since neither carries `workloads`. Here an extra entry that the
manifest already has adds only the cells its metric lacks; the manifest's
own config, cell and metrics are kept as they are."""

import copy

from tests.bench import tiny


def _with(bench: dict, extra: dict = None) -> dict:
    for key, entries in copy.deepcopy(extra or {}).items():
        have = {e["name"]: e for e in bench[key]}
        for e in entries:
            if e["name"] not in have:
                bench[key].append(e)
            elif "workloads" in have[e["name"]]:
                cells = have[e["name"]]["workloads"]
                cells += [w for w in e.get("workloads", []) if w not in cells]
    return bench


tiny._with = _with

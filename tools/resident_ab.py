#!/usr/bin/env python
"""Resident-tier A/B: does the resident-state window megakernel
(ops/resident_engine.py) beat per-window scan dispatch — and the
chunked scan tier — end-to-end, with EXACT parity?

Two probes, each a JSON row:

  driver_resident — StreamingAnalyticsDriver over the canonical
              524K/32768 row (bench.make_stream): the RESIDENT tier
              (donated super-batch programs + the GS_RESIDENT_SLOTS
              ingest ring) vs the scan tier at its normal chunking vs
              the scan tier forced to ONE dispatch PER WINDOW
              (`_SCAN_CHUNK=1` — the per-window round-trip the
              dispatch wall is made of), plus the native C++ tier
              where the library exports it. Window-by-window sha256
              parity (every snapshot field) asserted before any
              speedup is claimed.
  engine_resident — ResidentSummaryEngine vs StreamSummaryEngine vs
              the same engine at one window per dispatch; summary
              dicts compared exactly.

Timing is median-of-3 with min/max dispersion committed in the row
(the ingress A/B's 1.13x/1.02x flip-flop taught us a single run is
load noise, not evidence). GS_AUTOTUNE is pinned OFF inside the
probes so the residency lever is measured in isolation.

The rows are a record: the runtime selects the resident tier only
when GS_RESIDENT=on pins it. `speedup` in the row is resident vs
PER-WINDOW dispatch (the wall the megakernel kills);
`speedup_vs_scan` is the ratio against the default tier. Commit policy
identical to tools/egress_ab.py (PERF.json only when backend-matched,
PERF_<backend>.json always).
"""

import hashlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from bench import make_stream  # noqa: E402
from tools.egress_ab import _dispersion, timed_stats  # noqa: E402


def _digest_windows(results) -> list:
    out = []
    for r in results:
        h = hashlib.sha256()
        for a in (r.vertex_ids, r.degrees, r.cc_labels,
                  r.bipartite_odd):
            if a is not None:
                h.update(np.ascontiguousarray(a).tobytes())
        out.append((int(r.window_start), int(r.num_edges),
                    None if r.triangles is None else int(r.triangles),
                    h.hexdigest()[:16]))
    return out


def driver_resident(jax, num_edges, results):
    from gelly_streaming_tpu import native
    from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver
    from gelly_streaming_tpu.ops import resident_engine

    eb, vb = 32768, 65536
    src, dst = make_stream(num_edges, vb)

    def build(tier):
        return StreamingAnalyticsDriver(
            window_ms=0, edge_bucket=eb, vertex_bucket=vb,
            analytics=("degrees", "cc", "bipartite"),
            snapshot_tier=tier)

    drivers = {"resident": build("resident"), "scan": build("scan"),
               "perwindow": build("scan")}
    drivers["perwindow"]._SCAN_CHUNK = 1  # one dispatch per window
    if native.snapshot_available():
        drivers["native"] = build("native")
    digests = {}
    for name, drv in drivers.items():
        digests[name] = _digest_windows(drv.run_arrays(src, dst))
        drv.reset()
    parity = all(d == digests["scan"] for d in digests.values())

    stats = {}
    for name, drv in drivers.items():
        def run(drv=drv):
            drv.reset()
            drv.run_arrays(src, dst)

        stats[name] = timed_stats(run, reps=3, warmup=0)

    row = {
        "probe": "driver_resident",
        "backend": jax.default_backend(),
        "num_edges": len(src), "eb": eb, "vb": vb,
        "superbatch": resident_engine.resident_spb(eb),
        "ring_slots": resident_engine.ring_slots(),
        "donated": resident_engine.donation_supported(),
        "resident_edges_per_s": round(len(src)
                                      / stats["resident"][0]),
        "scan_edges_per_s": round(len(src) / stats["scan"][0]),
        "perwindow_edges_per_s": round(len(src)
                                       / stats["perwindow"][0]),
        "parity": bool(parity),
    }
    if "native" in stats:
        row["native_edges_per_s"] = round(len(src)
                                          / stats["native"][0])
    for name in stats:
        _dispersion(row, name, stats[name])
    if parity:
        row["speedup"] = round(
            stats["perwindow"][0] / stats["resident"][0], 3)
        row["speedup_worst"] = round(
            stats["perwindow"][1] / stats["resident"][2], 3)
        row["speedup_best"] = round(
            stats["perwindow"][2] / stats["resident"][1], 3)
        row["speedup_vs_scan"] = round(
            stats["scan"][0] / stats["resident"][0], 3)
    else:
        print("PARITY FAILURE between snapshot tiers (driver)",
              file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


def engine_resident(jax, num_edges, results):
    from gelly_streaming_tpu.ops.resident_engine import (
        ResidentSummaryEngine)
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)

    eb, vb = 32768, 65536
    src, dst = make_stream(num_edges, vb, seed=5)
    src32, dst32 = src.astype(np.int32), dst.astype(np.int32)

    engines = {
        "resident": ResidentSummaryEngine(edge_bucket=eb,
                                          vertex_bucket=vb),
        "scan": StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb),
        "perwindow": StreamSummaryEngine(edge_bucket=eb,
                                         vertex_bucket=vb),
    }
    engines["perwindow"].MAX_WINDOWS = 1  # one dispatch per window
    outs = {}
    for name, eng in engines.items():
        outs[name] = eng.process(src32, dst32)
        eng.reset()
    parity = all(o == outs["scan"] for o in outs.values())

    stats = {}
    for name, eng in engines.items():
        def run(eng=eng):
            eng.reset()
            eng.process(src32, dst32)

        stats[name] = timed_stats(run, reps=3, warmup=0)

    row = {
        "probe": "engine_resident",
        "backend": jax.default_backend(),
        "num_edges": len(src), "eb": eb, "vb": vb,
        "ingress": engines["resident"].ingress,
        "superbatch": engines["resident"].MAX_WINDOWS,
        "resident_edges_per_s": round(len(src)
                                      / stats["resident"][0]),
        "scan_edges_per_s": round(len(src) / stats["scan"][0]),
        "perwindow_edges_per_s": round(len(src)
                                       / stats["perwindow"][0]),
        "parity": bool(parity),
    }
    for name in stats:
        _dispersion(row, name, stats[name])
    if parity:
        row["speedup"] = round(
            stats["perwindow"][0] / stats["resident"][0], 3)
        row["speedup_worst"] = round(
            stats["perwindow"][1] / stats["resident"][2], 3)
        row["speedup_best"] = round(
            stats["perwindow"][2] / stats["resident"][1], 3)
        row["speedup_vs_scan"] = round(
            stats["scan"][0] / stats["resident"][0], 3)
    else:
        print("PARITY FAILURE between summary engines",
              file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


PROBE_NAMES = ("driver_resident", "engine_resident")


def commit_results(results, backend: str) -> None:
    """Merge this run's `resident_ab` rows into the committed evidence
    — the same policy as tools/egress_ab.py: PERF.json only when its
    backend label matches the live backend, the per-backend archive
    PERF_<backend>.json always."""
    targets = ((os.path.join(REPO, "PERF.json"), True),
               (os.path.join(REPO, "PERF_%s.json" % backend), False))
    for path, need_match in targets:
        try:
            with open(path) as f:
                cur = json.load(f)
        except (OSError, ValueError):
            cur = {}
        if need_match and cur.get("backend") != backend:
            print("not committing to %s: file backend %r != live %r"
                  % (os.path.basename(path), cur.get("backend"),
                     backend), file=sys.stderr)
            continue
        cur.setdefault("backend", backend)
        cur["resident_ab"] = results
        with open(path, "w") as f:
            json.dump(cur, f, indent=2)
        print("committed %s row(s) to %s"
              % (len(results), os.path.basename(path)), flush=True)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("probes", nargs="*",
                    help="subset of %s to run (default: all)"
                         % (PROBE_NAMES,))
    ap.add_argument("--edges", type=int,
                    default=int(os.environ.get("GS_AB_EDGES", 524_288)))
    ap.add_argument("--commit", action="store_true",
                    help="merge rows into PERF.json (backend-matched) "
                         "and PERF_<backend>.json")
    args = ap.parse_args()
    bad = [p for p in args.probes if p not in PROBE_NAMES]
    if bad:
        ap.error("unknown probe(s) %s; valid: %s"
                 % (bad, list(PROBE_NAMES)))
    want = args.probes or list(PROBE_NAMES)

    # measure the residency lever in isolation: the online tuner
    # changing dispatch knobs between reps would be noise here
    os.environ["GS_AUTOTUNE"] = "0"

    import jax

    results = []
    if "driver_resident" in want:
        driver_resident(jax, args.edges, results)
    if "engine_resident" in want:
        engine_resident(jax, args.edges, results)
    out = os.path.join(REPO, "logs",
                       "resident_ab_%s.json" % jax.default_backend())
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote %s" % out, flush=True)
    if args.commit:
        commit_results(results, jax.default_backend())


if __name__ == "__main__":
    main()

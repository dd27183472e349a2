#!/usr/bin/env python
"""Egress A/B: does delta-compacted d2h egress (ops/delta_egress.py)
beat full-vector snapshot shipping end-to-end — with EXACT parity?

Two probes, each a JSON row:

  driver_ab — StreamingAnalyticsDriver over the canonical 524K/32768
              row (bench.make_stream), scan tier pinned, full vs
              delta egress; bit parity asserted window-by-window
              (sha256 over every snapshot field INCLUDING the delta
              tuples) before any speedup is claimed.
  reduce_ab — WindowedEdgeReduce monoid device tier at a
              vbp >> eb shape (where the touched-cell wire actually
              shrinks bytes), full vs delta; cells AND counts
              bit-identical per window.

Timing is median-of-3 with min/max dispersion committed in the row
(the ingress A/B's 1.13x/1.02x flip-flop taught us a single run is
load noise, not evidence). GS_AUTOTUNE is pinned OFF inside the
probes so the egress lever is measured in isolation.

The rows are a record: the runtime selects delta egress only when
GS_EGRESS=delta pins it. Run alone on the chip's host; commit policy
identical to tools/ingress_ab.py (PERF.json only when backend-matched,
PERF_<backend>.json always).
"""

import hashlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from bench import make_stream  # noqa: E402


def timed_stats(fn, reps=3, warmup=1):
    """median/min/max wall seconds of fn() — the dispersion trio every
    A/B row commits so the adoption bar is never decided by one
    load-noisy draw."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return (float(np.median(ts)), float(np.min(ts)), float(np.max(ts)))


def _dispersion(row: dict, prefix: str, stats) -> None:
    med, lo, hi = stats
    row[prefix + "_s"] = round(med, 4)
    row[prefix + "_s_min"] = round(lo, 4)
    row[prefix + "_s_max"] = round(hi, 4)


def _digest_windows(results) -> list:
    out = []
    for r in results:
        h = hashlib.sha256()
        for a in (r.vertex_ids, r.degrees, r.cc_labels,
                  r.bipartite_odd):
            if a is not None:
                h.update(np.ascontiguousarray(a).tobytes())
        for t in (r.delta_degrees, r.delta_cc, r.delta_bipartite):
            if t is not None:
                h.update(np.ascontiguousarray(t[0]).tobytes())
                h.update(np.ascontiguousarray(t[1]).tobytes())
        out.append((int(r.window_start), int(r.num_edges),
                    None if r.triangles is None else int(r.triangles),
                    h.hexdigest()[:16]))
    return out


def driver_ab(jax, num_edges, results):
    from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver
    from gelly_streaming_tpu.ops import delta_egress

    eb, vb = 32768, 65536
    src, dst = make_stream(num_edges, vb)

    def build(egress):
        return StreamingAnalyticsDriver(
            window_ms=0, edge_bucket=eb, vertex_bucket=vb,
            snapshot_tier="scan", egress=egress, emit_deltas=True)

    drivers = {e: build(e) for e in ("full", "delta")}
    digests = {}
    for e, drv in drivers.items():
        digests[e] = _digest_windows(drv.run_arrays(src, dst))  # warm
        drv.reset()
    parity = digests["full"] == digests["delta"]

    stats = {}
    for e, drv in drivers.items():
        def run(drv=drv):
            drv.reset()
            drv.run_arrays(src, dst)

        stats[e] = timed_stats(run, reps=3, warmup=0)

    row = {
        "probe": "driver_ab",
        "backend": jax.default_backend(),
        "num_edges": len(src), "eb": eb, "vb": vb,
        "cap": delta_egress.egress_cap(eb, vb),
        "full_edges_per_s": round(len(src) / stats["full"][0]),
        "delta_edges_per_s": round(len(src) / stats["delta"][0]),
        "parity": bool(parity),
    }
    _dispersion(row, "full", stats["full"])
    _dispersion(row, "delta", stats["delta"])
    if parity:
        row["speedup"] = round(stats["full"][0] / stats["delta"][0], 3)
        # worst/best-case ratio across the dispersion envelope: the
        # adoption bar should clear even the pessimistic pairing
        row["speedup_worst"] = round(
            stats["full"][1] / stats["delta"][2], 3)
        row["speedup_best"] = round(
            stats["full"][2] / stats["delta"][1], 3)
    else:
        print("PARITY FAILURE between egress forms (driver)",
              file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


def reduce_ab(jax, num_edges, results):
    from gelly_streaming_tpu.ops.windowed_reduce import (
        WindowedEdgeReduce)

    eb, vb = 4096, 65536  # vbp >> eb: the shape the wire shrinks
    src, dst = make_stream(num_edges, vb, seed=11)
    src64 = src.astype(np.int64)
    dst64 = dst.astype(np.int64)
    val = (1 + (src + 3 * dst) % 97).astype(np.int64)

    engines = {e: WindowedEdgeReduce(
        vertex_bucket=vb, edge_bucket=eb, name="sum",
        direction="out", egress=e) for e in ("full", "delta")}
    rows = {e: eng._device_process_stream(src64, dst64, val)
            for e, eng in engines.items()}  # warm + parity material
    parity = len(rows["full"]) == len(rows["delta"]) and all(
        np.array_equal(np.asarray(c0), np.asarray(c1))
        and np.array_equal(np.asarray(n0), np.asarray(n1))
        for (c0, n0), (c1, n1) in zip(rows["full"], rows["delta"]))

    stats = {e: timed_stats(
        lambda eng=eng: eng._device_process_stream(src64, dst64, val),
        reps=3, warmup=0) for e, eng in engines.items()}

    row = {
        "probe": "reduce_ab",
        "backend": jax.default_backend(),
        "num_edges": len(src), "eb": eb, "vb": vb, "name": "sum",
        "full_edges_per_s": round(len(src) / stats["full"][0]),
        "delta_edges_per_s": round(len(src) / stats["delta"][0]),
        "parity": bool(parity),
    }
    _dispersion(row, "full", stats["full"])
    _dispersion(row, "delta", stats["delta"])
    if parity:
        row["speedup"] = round(stats["full"][0] / stats["delta"][0], 3)
        row["speedup_worst"] = round(
            stats["full"][1] / stats["delta"][2], 3)
        row["speedup_best"] = round(
            stats["full"][2] / stats["delta"][1], 3)
    else:
        print("PARITY FAILURE between egress forms (reduce)",
              file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


PROBE_NAMES = ("driver_ab", "reduce_ab")


def commit_results(results, backend: str) -> None:
    """Merge this run's `egress_ab` rows into the committed evidence —
    the same policy as tools/ingress_ab.py: PERF.json only when its
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
        cur["egress_ab"] = results
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

    # measure the egress lever in isolation: the online tuner changing
    # dispatch knobs between reps would be noise here
    os.environ["GS_AUTOTUNE"] = "0"

    import jax

    results = []
    if "driver_ab" in want:
        driver_ab(jax, args.edges, results)
    if "reduce_ab" in want:
        reduce_ab(jax, args.edges, results)
    out = os.path.join(REPO, "logs",
                       "egress_ab_%s.json" % jax.default_backend())
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote %s" % out, flush=True)
    if args.commit:
        commit_results(results, jax.default_backend())


if __name__ == "__main__":
    main()

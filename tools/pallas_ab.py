#!/usr/bin/env python
"""Fused-window-megakernel A/B: does the Pallas window megakernel
(ops/pallas_window.py) beat the XLA scan-of-gathers end-to-end, with
EXACT parity?

Two committed probes, each a JSON row in the `pallas_ab` section:

  engine_pallas — StreamSummaryEngine over the canonical 524K/32768
              row: GS_PALLAS_WINDOW=on (the megakernel body) vs off
              (the XLA fused scan), window-by-window sha256 parity
              of the summary dicts, plus the numpy host twin
              (parallel/host_twin.HostSummaryEngine) as the
              tier-independent oracle.
  stream_pallas — TriangleWindowKernel._count_stream_device (the
              tier selection bypassed, so the device program is
              measured on every backend): megakernel counter vs XLA
              counter, exact count parity against
              ops/host_triangles.count_stream.

Timing is median-of-3 with min/max dispersion committed in the row
(the ingress A/B's 1.13x/1.02x flip-flop taught us a single run is
load noise, not evidence). GS_AUTOTUNE is pinned OFF inside the
probes so the kernel lever is measured in isolation.

The rows are a record: the runtime runs the megakernel only when
GS_PALLAS_WINDOW=on pins it. On a CPU backend the kernel runs in
INTERPRET mode — parity is real evidence there, speed is not.
Commit policy identical to tools/resident_ab.py.

--sweep drives the `pallas_window` DispatchTuner family (edge-tile ×
K-chunk arms) through two full measurement passes and persists the
winning arm to the per-backend tuning cache, which
pallas_window.resolve_tiles seeds production builds from — run it in
the chip window before `--commit`.
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


def _pin(value: str):
    """Flip the selection pin and drop the memoized verdicts/programs
    so each leg builds exactly what it claims to measure."""
    from gelly_streaming_tpu.ops import pallas_window as pw

    os.environ["GS_PALLAS_WINDOW"] = value
    pw._reset_pallas_window()


def _digest_summaries(summaries) -> str:
    h = hashlib.sha256()
    for s in summaries:
        h.update(json.dumps(s, sort_keys=True).encode())
    return h.hexdigest()[:16]


def engine_pallas(jax, num_edges, results):
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)
    from gelly_streaming_tpu.parallel.host_twin import (
        HostSummaryEngine)

    eb, vb = 32768, 65536
    src, dst = make_stream(num_edges, vb)
    s32, d32 = src.astype(np.int32), dst.astype(np.int32)

    def build(pin):
        _pin(pin)
        return StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)

    engines = {"pallas": build("on"), "xla": build("off")}
    digests, pallas_used = {}, None
    for name, eng in engines.items():
        digests[name] = _digest_summaries(eng.process(s32, d32))
        if name == "pallas":
            pallas_used = bool(eng._pallas)
        eng.reset()
    host = HostSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
    digests["host"] = _digest_summaries(host.process(s32, d32))
    parity = (pallas_used
              and len(set(digests.values())) == 1)

    stats = {}
    for name, eng in engines.items():
        _pin("on" if name == "pallas" else "off")

        def run(eng=eng):
            eng.reset()
            eng.process(s32, d32)

        stats[name] = timed_stats(run, reps=3, warmup=0)
    _pin("")

    row = {
        "probe": "engine_pallas",
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "num_edges": len(src), "eb": eb, "vb": vb,
        "kb": engines["pallas"].kb,
        "ingress": engines["pallas"].ingress,
        "pallas_edges_per_s": round(len(src) / stats["pallas"][0]),
        "xla_edges_per_s": round(len(src) / stats["xla"][0]),
        "parity": bool(parity),
    }
    for name in stats:
        _dispersion(row, name, stats[name])
    if parity:
        row["speedup"] = round(stats["xla"][0] / stats["pallas"][0], 3)
        row["speedup_worst"] = round(
            stats["xla"][1] / stats["pallas"][2], 3)
        row["speedup_best"] = round(
            stats["xla"][2] / stats["pallas"][1], 3)
    else:
        print("PARITY FAILURE between window bodies (engine)"
              if pallas_used else
              "megakernel body was NOT selected (gate/probe refused)",
              file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


def stream_pallas(jax, num_edges, results):
    from gelly_streaming_tpu.ops import host_triangles
    from gelly_streaming_tpu.ops import triangles as tri_ops

    eb, vb = 32768, 65536
    src, dst = make_stream(num_edges, vb, seed=5)
    s32, d32 = src.astype(np.int32), dst.astype(np.int32)

    def build(pin):
        _pin(pin)
        return tri_ops.TriangleWindowKernel(edge_bucket=eb,
                                            vertex_bucket=vb)

    kernels = {"pallas": build("on"), "xla": build("off")}
    counts = {name: k._count_stream_device(s32, d32)
              for name, k in kernels.items()}
    counts["host"] = host_triangles.count_stream(s32, d32, eb)
    pallas_used = bool(kernels["pallas"]._pallas_counter)
    parity = (pallas_used
              and counts["pallas"] == counts["xla"] == counts["host"])

    stats = {}
    for name, k in kernels.items():
        _pin("on" if name == "pallas" else "off")
        stats[name] = timed_stats(
            lambda k=k: k._count_stream_device(s32, d32),
            reps=3, warmup=0)
    _pin("")

    row = {
        "probe": "stream_pallas",
        "backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "num_edges": len(src), "eb": eb, "vb": vb,
        "kb": kernels["pallas"].kb,
        "pallas_edges_per_s": round(len(src) / stats["pallas"][0]),
        "xla_edges_per_s": round(len(src) / stats["xla"][0]),
        "parity": bool(parity),
    }
    for name in stats:
        _dispersion(row, name, stats[name])
    if parity:
        row["speedup"] = round(stats["xla"][0] / stats["pallas"][0], 3)
        row["speedup_worst"] = round(
            stats["xla"][1] / stats["pallas"][2], 3)
        row["speedup_best"] = round(
            stats["xla"][2] / stats["pallas"][1], 3)
    else:
        print("PARITY FAILURE between stream counters"
              if pallas_used else
              "megakernel counter was NOT selected (gate/probe "
              "refused)", file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


def sweep_tiles(jax, num_edges) -> None:
    """Drive the `pallas_window` DispatchTuner family (edge-tile ×
    K-chunk arms) through two full measurement passes over the arm
    grid and persist the incumbent to the per-backend tuning cache
    (GS_TUNE_CACHE) — the committed-evidence seed
    pallas_window.resolve_tiles builds production kernels from. NOT a
    committed PERF row: the cache is the artifact."""
    import itertools

    from gelly_streaming_tpu.ops import pallas_window as pw
    from gelly_streaming_tpu.ops import scan_analytics as sa
    from gelly_streaming_tpu.ops import triangles as tri_ops

    eb, vb = 32768, 65536
    edges = min(num_edges, 8 * eb)  # two passes × |arms| engine runs
    src, dst = make_stream(edges, vb, seed=9)
    s32, d32 = src.astype(np.int32), dst.astype(np.int32)
    kb = tri_ops._tuned_kb(eb)
    tuner = pw.tile_tuner(eb, vb, kb)
    arms = [dict(zip(tuner.space, vals)) for vals in
            itertools.product(*(tuner.space[k]
                                for k in tuner.space))]
    try:
        for _pass in range(2):
            for arm in arms:
                # the tile pins are how an arm reaches the engine's
                # build (pallas_window.resolve_tiles reads them at
                # body-build time, explicit pins beating the cache)
                os.environ["GS_PALLAS_TILE"] = str(arm["tile_e"])
                os.environ["GS_PALLAS_CK"] = str(arm["ck"])
                _pin("on")
                eng = sa.StreamSummaryEngine(edge_bucket=eb,
                                             vertex_bucket=vb)
                if not eng._pallas:
                    print("arm %s: megakernel refused (probe) — "
                          "skipping" % json.dumps(arm),
                          file=sys.stderr)
                    continue

                def run():
                    eng.reset()
                    eng.process(s32, d32)

                med, _lo, _hi = timed_stats(run, reps=1, warmup=1)
                tuner.record(arm, len(s32), med)
                print(json.dumps({"arm": arm,
                                  "edges_per_s": round(len(s32)
                                                       / med)}),
                      flush=True)
    finally:
        os.environ.pop("GS_PALLAS_TILE", None)
        os.environ.pop("GS_PALLAS_CK", None)
        _pin("")
    tuner.save()
    print("sweep incumbent: %s" % json.dumps(tuner.best()),
          flush=True)


PROBE_NAMES = ("engine_pallas", "stream_pallas")


def commit_results(results, backend: str) -> None:
    """Merge this run's `pallas_ab` rows into the committed evidence
    — the same policy as tools/resident_ab.py: PERF.json only when
    its backend label matches the live backend, the per-backend
    archive PERF_<backend>.json always."""
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
        cur["pallas_ab"] = results
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
                    default=int(os.environ.get("GS_AB_EDGES",
                                               524_288)))
    ap.add_argument("--sweep", action="store_true",
                    help="drive the pallas_window tile tuner over "
                         "its arm grid and persist the optimum "
                         "(chip-window prelude to --commit)")
    ap.add_argument("--commit", action="store_true",
                    help="merge rows into PERF.json "
                         "(backend-matched) and PERF_<backend>.json")
    args = ap.parse_args()
    bad = [p for p in args.probes if p not in PROBE_NAMES]
    if bad:
        ap.error("unknown probe(s) %s; valid: %s"
                 % (bad, list(PROBE_NAMES)))
    want = args.probes or list(PROBE_NAMES)

    # measure the kernel lever in isolation: the online tuner
    # changing dispatch knobs between reps would be noise here
    os.environ["GS_AUTOTUNE"] = "0"

    import jax

    if args.sweep:
        sweep_tiles(jax, args.edges)
    results = []
    if "engine_pallas" in want:
        engine_pallas(jax, args.edges, results)
    if "stream_pallas" in want:
        stream_pallas(jax, args.edges, results)
    out = os.path.join(REPO, "logs",
                       "pallas_ab_%s.json" % jax.default_backend())
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote %s" % out, flush=True)
    if args.commit:
        commit_results(results, jax.default_backend())


if __name__ == "__main__":
    main()

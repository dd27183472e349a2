#!/usr/bin/env python
"""Scale validation (VERDICT r1 item 7): a deterministic ≥10M-edge
timestamped stream pushed through the real ingest paths, with the
vertex domain growing past 2^16 mid-stream so the driver's bucket-
doubling (O(log V) recompiles, then steady state) is exercised at
scale. Emits one JSON line per leg and writes SCALE_r02.json.

Legs:
  driver   — StreamingAnalyticsDriver.stream_file (bounded-memory C++
             chunk parse -> event-time windows -> all four analytics),
             with a jax_log_compiles listener asserting NO compile
             lands in the steady-state tail of the stream.
  fused    — StreamSummaryEngine.process over the same edges (the
             one-dispatch-per-64-windows throughput path).
  sharded  — ShardedSummaryEngine on the virtual 8-device CPU mesh
             (subprocess; the backend pin must precede jax import).

The fixture file is generated to --out (default /tmp, ~190MB — the
GENERATOR is committed, the data is reproducible, BASELINE.json names
real datasets this zero-egress image cannot download).
"""

import argparse
import json
import logging
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

NUM_EDGES = int(os.environ.get("GS_SCALE_EDGES", 10_000_000))
EDGES_PER_WINDOW = int(os.environ.get("GS_SCALE_WINDOW", 65_536))
WINDOW_MS = 1_000
V_START = 4_096       # driver's default vertex bucket: growth starts at once
# crosses 2^16 mid-stream -> bucket doubling under load
V_END = int(os.environ.get("GS_SCALE_VEND", 262_144))
SEED = 11


def generate(path: str) -> None:
    """Deterministic 'src dst ts' fixture: Zipf-ish endpoints over a
    vertex domain that widens linearly from V_START to V_END across the
    stream (new vertices keep arriving, the way a real edge stream's id
    space grows), timestamps ascending with exactly EDGES_PER_WINDOW
    edges per WINDOW_MS event-time window."""
    rng = np.random.default_rng(SEED)
    t0 = time.perf_counter()
    with open(path, "w") as f:
        at = 0
        while at < NUM_EDGES:
            n = min(EDGES_PER_WINDOW, NUM_EDGES - at)
            # domain grows with stream position; ranks drawn by inverse-
            # CDF of a power law (cheap, no per-draw choice(p=...))
            vmax = V_START + (V_END - V_START) * at // NUM_EDGES
            u = rng.random((2, n))
            ids = ((vmax ** u) - 1).astype(np.int64)  # ~Zipf over [0,vmax)
            ts = np.full(n, (at // EDGES_PER_WINDOW) * WINDOW_MS)
            # scatter hot ids over the space deterministically
            s = (ids[0] * 2654435761) % vmax
            d = (ids[1] * 2246822519) % vmax
            d = np.where(s == d, (d + 1) % vmax, d)
            np.savetxt(f, np.stack([s, d, ts], 1), fmt="%d")
            at += n
    print(json.dumps({
        "leg": "generate", "edges": NUM_EDGES, "path": path,
        "bytes": os.path.getsize(path),
        "seconds": round(time.perf_counter() - t0, 1)}), flush=True)


class CompileCounter(logging.Handler):
    """Counts XLA compiles via jax_log_compiles ('Finished tracing +
    compiling ...' records on the jax logger tree)."""

    def __init__(self):
        super().__init__()
        self.events = []

    def emit(self, record):
        msg = record.getMessage()
        if "compiling" in msg.lower():
            self.events.append(msg)


def run_driver(path: str) -> dict:
    import jax

    from gelly_streaming_tpu import StreamingAnalyticsDriver

    jax.config.update("jax_log_compiles", True)
    counter = CompileCounter()
    # handler ONLY on the ancestor: records issued on the child loggers
    # propagate up, so attaching to both would double-count
    logging.getLogger("jax").addHandler(counter)
    for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
        logging.getLogger(name).setLevel(logging.DEBUG)

    drv = StreamingAnalyticsDriver(window_ms=WINDOW_MS, tracing=True)
    t0 = time.perf_counter()
    windows = 0
    total_w = NUM_EDGES // EDGES_PER_WINDOW
    last_result = None
    tail_at = max(1, (3 * total_w) // 4)
    # steady-state contract: programs come from a BOUNDED set. A tail
    # window may compile only if a bucket grew in it (the driver's
    # O(log V) growth recompiles are by design), with one exception:
    # the stream's final ragged flush legitimately first-uses a new
    # W-bucket / per-window program class, exactly once. A genuine
    # per-window leak compiles in MANY tail windows; so the assert is
    # on the number of DISTINCT no-growth windows that compiled.
    prev_events = 0
    prev_caps = (0, 0)
    violation_windows = []  # (window_idx, [compile msgs])
    tail_compiles = 0
    for res in drv.stream_file(path, chunk_bytes=1 << 26):
        windows += 1
        last_result = res
        caps = (drv.vb, drv.eb)
        new_events = len(counter.events) - prev_events
        if windows >= tail_at and new_events:
            tail_compiles += new_events
            if caps == prev_caps:
                violation_windows.append(
                    (windows,
                     counter.events[prev_events:prev_events
                                    + new_events]))
        prev_events = len(counter.events)
        prev_caps = caps
    elapsed = time.perf_counter() - t0
    jax.config.update("jax_log_compiles", False)

    assert len(violation_windows) <= 1, (
        "steady-state recompiles (no bucket growth) in %d tail "
        "windows — more than the final ragged flush can explain:\n%s"
        % (len(violation_windows),
           "\n".join(m for _w, ms in violation_windows for m in ms)))
    assert last_result is not None
    nv = len(last_result.vertex_ids)
    # the bucket must have grown to hold the fixture's final vertex
    # domain (past 2^16 at the real V_END=262144) — proves doubling
    # happened mid-stream, under load
    need = V_START
    while need < V_END // 2:
        need *= 2
    assert drv.vb >= need, (
        f"fixture never grew the vertex bucket (vb={drv.vb}, "
        f"expected >= {need} for a {V_END}-vertex domain)")
    return {
        "leg": "driver-stream_file",
        "backend": jax.default_backend(),
        "edges": NUM_EDGES,
        "windows": windows,
        "vertices_final": nv,
        "vertex_bucket_final": drv.vb,
        "edges_per_sec": round(NUM_EDGES / elapsed),
        "compiles_total": len(counter.events),
        "compiles_steady_state_tail": tail_compiles,
        "tail_windows_compiling_outside_bucket_growth":
            [w for w, _m in violation_windows],
        "trace": drv.trace_report(),
    }


def run_fused(path: str) -> dict:
    import jax

    from gelly_streaming_tpu.io.sources import load_edge_arrays
    from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine
    from gelly_streaming_tpu.ops.segment import intern

    src, dst, _ts = load_edge_arrays(path)
    _uniq, (s, d) = intern(src, dst)
    eng = StreamSummaryEngine(edge_bucket=EDGES_PER_WINDOW,
                              vertex_bucket=int(max(s.max(), d.max())) + 1)
    # compile both chunk shapes + the overflow fallback outside timing
    num_w = -(-len(s) // eng.eb)
    for w in {min(num_w, eng.MAX_WINDOWS), num_w % eng.MAX_WINDOWS}:
        if w:
            zeros = np.zeros(w * eng.eb, np.int32)
            eng.process(zeros, zeros)
            eng.reset()
    eng.warm_fallback()
    t0 = time.perf_counter()
    out = eng.process(s, d)
    elapsed = time.perf_counter() - t0
    return {
        "leg": "fused-scan",
        "backend": jax.default_backend(),
        "edges": len(s),
        "windows": len(out),
        "edges_per_sec": round(len(s) / elapsed),
        "final_summary": out[-1],
    }


def run_sharded(path: str, timeout_s: int = 3600) -> dict:
    code = r"""
import json, sys, time
import numpy as np
sys.path.insert(0, %(repo)r)
from gelly_streaming_tpu.core.platform import cpu_mesh
cpu_mesh(8)
from gelly_streaming_tpu.io.sources import load_edge_arrays
from gelly_streaming_tpu.ops.segment import intern
from gelly_streaming_tpu.parallel.mesh import make_mesh
from gelly_streaming_tpu.parallel.sharded import ShardedSummaryEngine

src, dst, _ts = load_edge_arrays(%(path)r)
# the virtual CPU mesh is a sharding-correctness leg, not a perf leg:
# an eighth of the stream bounds the wall-clock
n = len(src) // 8
_uniq, (s, d) = intern(src[:n], dst[:n])
eng = ShardedSummaryEngine(make_mesh(), edge_bucket=%(epw)d,
                           vertex_bucket=int(max(s.max(), d.max())) + 1)
zeros = np.zeros(min(-(-len(s) // eng.eb), eng.MAX_WINDOWS) * eng.eb,
                 np.int32)
eng.process(zeros, zeros)
eng.reset()
eng.warm_fallback()
t0 = time.perf_counter()
out = eng.process(s, d)
elapsed = time.perf_counter() - t0
print(json.dumps({
    "leg": "sharded-fused-scan", "backend": "cpu-virtual-mesh",
    "devices": 8, "edges": len(s), "windows": len(out),
    "edges_per_sec": round(len(s) / elapsed),
    "final_summary": out[-1]}))
""" % {"repo": REPO, "path": path, "epw": EDGES_PER_WINDOW}
    # a CPU child on a virtual 8-device mesh; the code above
    # sys.path-inserts the repo itself. run_json_child kills the
    # process GROUP on timeout so a hung child costs one leg, not the
    # run.
    from bench import run_json_child

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    got = run_json_child([sys.executable, "-c", code], timeout_s, env=env)
    if "error" in got:
        got["leg"] = "sharded-fused-scan"
    return got


def run_citation(_path: str) -> dict:
    """Real-shaped leg (VERDICT r2 missing-3): the cit-HepPh-calibrated
    citation stream (utils/realgraph.py — exact published node/edge
    counts, clustering/triangles within a few percent of the SNAP
    figures, power-law degree tail, DAG timestamps) through the
    driver's batched path. The synthetic legs above characterize
    scale; this one pins throughput on real-graph shape, where hub
    rows and co-citation clustering stress the K-bucket ladder."""
    import jax

    from gelly_streaming_tpu import StreamingAnalyticsDriver
    from gelly_streaming_tpu.utils.realgraph import citation_stream

    src, dst, _ts = citation_stream()
    vb = int(max(src.max(), dst.max())) + 1
    eb = 8_192
    drv = StreamingAnalyticsDriver(window_ms=0, edge_bucket=eb,
                                   vertex_bucket=vb)
    # warm with the REAL stream, not zeros: the citation graph's hub
    # windows overflow the tuned starting K, so the escalation-rung
    # programs (and the exact-recount path) are part of what the timed
    # run executes — a zero-stream warm-up would leave them to compile
    # inside the timing
    drv.run_arrays(src, dst)
    drv.reset()
    t0 = time.perf_counter()
    res = drv.run_arrays(src, dst)
    elapsed = time.perf_counter() - t0
    return {
        "leg": "citation-driver",
        "backend": jax.default_backend(),
        "graph": "cit-HepPh-calibrated (gelly_streaming_tpu/utils/"
                 "realgraph.py; SNAP-published anchors)",
        "edges": len(src),
        "vertices": vb,
        "windows": len(res),
        "edges_per_sec": round(len(src) / elapsed),
        "window_triangles_last": res[-1].triangles,
    }


LEGS = {"driver": run_driver, "fused": run_fused, "sharded": run_sharded,
        "citation": run_citation}


def run_leg_subprocess(leg: str, fixture: str, timeout_s: int) -> dict:
    """Run one leg in its own process group with a hard timeout (same
    contract as tools/profile_kernels.py sections: a hung compile costs
    one leg, not the whole scale run). `sharded` already subprocesses
    itself with a CPU pin, so it runs in-process here."""
    from bench import run_json_child

    if leg == "sharded":
        return run_sharded(fixture, timeout_s)
    got = run_json_child(
        [sys.executable, os.path.abspath(__file__), "--leg", leg,
         "--out", fixture], timeout_s, require_key="leg")
    if "error" in got:
        got["leg"] = leg
    return got


def _chip(legs) -> bool:
    return any(leg.get("backend") == "tpu" for leg in legs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/tmp/gs_scale_fixture.txt")
    ap.add_argument("--leg", help="child mode: run ONE leg in-process")
    ap.add_argument("--cpu", action="store_true",
                    help="run the legs on JAX's CPU backend (a labelled "
                         "rehearsal, never a chip number)")
    ap.add_argument("legs", nargs="*",
                    default=["driver", "fused", "sharded", "citation"])
    args = ap.parse_args()

    if not os.path.exists(args.out):
        generate(args.out)
    if args.leg:
        print(json.dumps(LEGS[args.leg](args.out)), flush=True)
        return

    unknown = [leg for leg in args.legs if leg not in LEGS]
    if unknown:
        sys.exit("unknown leg(s) %s; valid: %s" % (unknown, list(LEGS)))
    timeout_s = int(os.environ.get("GS_SCALE_LEG_TIMEOUT", "3600"))
    out_path = os.path.join(REPO, "SCALE_r02.json")
    try:
        with open(out_path) as f:
            prior = json.load(f)
    except (OSError, ValueError):
        prior = None

    results = {"num_edges": NUM_EDGES, "edges_per_window": EDGES_PER_WINDOW,
               "v_start": V_START, "v_end": V_END, "seed": SEED,
               "legs": []}
    wrote = [None]

    def flush():
        # Same no-clobber contract as profile_kernels' PERF.json: the
        # committed scale evidence must never be degraded.
        #  - prior at a LARGER scale -> this (dev/test) run stays in
        #    .partial; legs from different NUM_EDGES are not
        #    comparable under one meta block;
        #  - prior with IDENTICAL meta (every generator parameter, not
        #    just num_edges — they are all env-overridable) -> merge
        #    per-leg, where a cpu-fallback leg never replaces a chip-
        #    measured one and a failed leg keeps the prior version;
        #  - otherwise (smaller/absent/incomparable-meta prior) ->
        #    fresh whole-file replace once any leg succeeded, unless
        #    that would swap chip evidence for a cpu fallback.
        meta_keys = ("num_edges", "edges_per_window", "v_start",
                     "v_end", "seed")
        new_ok = [leg for leg in results["legs"] if "error" not in leg]
        merged = dict(results)
        usable = bool(new_ok) and not (
            prior is not None and _chip(prior.get("legs", []))
            and not _chip(new_ok))
        if prior is not None and prior.get("num_edges", 0) > NUM_EDGES:
            usable = False
        elif prior is not None and all(
                prior.get(k) == results[k] for k in meta_keys):
            by_name = {leg.get("leg"): leg
                       for leg in prior.get("legs", [])}
            replaced = 0
            for leg in new_ok:
                old = by_name.get(leg["leg"])
                if (old is not None and old.get("backend") == "tpu"
                        and leg.get("backend") != "tpu"):
                    continue   # cpu fallback never replaces a chip leg
                by_name[leg["leg"]] = leg
                replaced += 1
            for leg in results["legs"]:
                if "error" in leg and leg["leg"] not in by_name:
                    by_name[leg["leg"]] = leg
            merged["legs"] = list(by_name.values())
            usable = replaced > 0
        path = out_path if usable else out_path + ".partial"
        with open(path, "w") as f:
            json.dump(merged if usable else results, f, indent=2)
        wrote[0] = path

    # the parent never imports JAX: each leg runs in its own child and
    # reports the backend it ran on; --cpu is an explicitly labelled
    # CPU rehearsal
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    for leg in args.legs:
        r = run_leg_subprocess(leg, args.out, timeout_s)
        results["legs"].append(r)
        print(json.dumps(r), flush=True)
        flush()
    print("wrote %s" % wrote[0], file=sys.stderr)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Ingress A/B: is the streaming window counter's end-to-end rate
bound by h2d transfer, per-dispatch latency, or device compute — and
does a compact ingress format fix it?

The standard stream dispatch (TriangleWindowKernel._run_stack) ships
9 bytes per edge-slot h2d: src int32 + dst int32 + valid bool. But
(a) vertex ids fit uint16 whenever vertex_bucket <= 65536 (every
bench scale), and (b) padding is always a per-window SUFFIX
(seg_ops.window_stack), so the [wb, eb] bool mask is reconstructible
from one int32 count per window. Compact ingress sends
uint16 src + uint16 dst + int32 nvalid[wb] = 4 bytes/slot (2.25x
fewer bytes), widening + mask reconstruction fused into the same
window program on device (VPU-cheap).

Four probes, each a JSON line:
  h2d_probe            — device_put bandwidth at both formats (bytes/s)
  latency_probe        — round-trip of a minimal 1-window dispatch (s)
  device_compute_probe — one stream-chunk program on already-resident
                         data (pure device compute; completes the
                         transfer + dispatch + compute decomposition)
  stream_ab            — full-stream end-to-end, standard vs compact,
                         identical counts asserted window-by-window

Run it alone on the chip's host (it shares the host cores with
everything else). Results go to stdout and
logs/ingress_ab_<backend>.json; the kernel only ADOPTS compact
ingress behind the same committed-evidence policy as every other
selection (ops/triangles.py docstrings).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from bench import make_stream  # noqa: E402  (the A/B stream IS the bench stream)


def _median_time(fn, reps=5, warmup=1):
    return _timed_stats(fn, reps, warmup)[0]


def _timed_stats(fn, reps=5, warmup=1):
    """(median, min, max) wall seconds — the stream A/B commits the
    whole trio so the 1.05x adoption bar is never decided by one
    load-noisy draw (the 1.13x/1.02x flip-flop across consecutive
    committed runs, PERF.md)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)), float(np.min(ts)), float(np.max(ts))


def h2d_probe(jax, jnp, eb, wb, results):
    """device_put bandwidth of one stream chunk in each format."""
    slots = wb * eb
    rng = np.random.default_rng(0)
    s32 = rng.integers(0, 65536, (wb, eb)).astype(np.int32)
    d32 = rng.integers(0, 65536, (wb, eb)).astype(np.int32)
    v8 = np.ones((wb, eb), bool)
    s16 = s32.astype(np.uint16)
    d16 = d32.astype(np.uint16)
    nv = np.full(wb, eb, np.int32)

    def put(*arrs):
        out = [jax.device_put(a) for a in arrs]
        jax.block_until_ready(out)

    t_std = _median_time(lambda: put(s32, d32, v8))
    t_cmp = _median_time(lambda: put(s16, d16, nv))
    row = {
        "probe": "h2d",
        "backend": jax.default_backend(),
        "chunk_slots": slots,
        "std_bytes": slots * 9,
        "std_s": round(t_std, 6),
        "std_bytes_per_s": round(slots * 9 / t_std),
        "compact_bytes": slots * 4 + 4 * wb,
        "compact_s": round(t_cmp, 6),
        "compact_bytes_per_s": round((slots * 4 + 4 * wb) / t_cmp),
        "speedup": round(t_std / t_cmp, 2),
    }
    results.append(row)
    print(json.dumps(row), flush=True)


def latency_probe(jax, jnp, results):
    """Fixed round-trip cost of a minimal dispatch (scalar in/out)."""
    one = jnp.ones((8,), jnp.int32)

    @jax.jit
    def tick(x):
        return x.sum()

    t = _median_time(lambda: jax.block_until_ready(tick(one)), reps=9)
    row = {"probe": "dispatch_latency",
           "backend": jax.default_backend(), "round_trip_s": round(t, 6)}
    results.append(row)
    print(json.dumps(row), flush=True)


def device_compute_probe(jax, jnp, results):
    """Pure device time of ONE stream-chunk program on ALREADY-resident
    data: with the h2d probe (transfer) and the latency probe
    (dispatch round-trip), this completes the end-to-end rate's
    decomposition — rate ≈ chunk_edges / (transfer + dispatch +
    compute) — so the residual after compact ingress + deep chunks is
    attributable, not mysterious (VERDICT r4 item 1's 'fully
    decomposed' done-criterion)."""
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    eb, vb = 32768, 65536
    k = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                             ingress="standard")
    wb = k.MAX_STREAM_WINDOWS
    rng = np.random.default_rng(5)
    s = jax.device_put(
        rng.integers(0, vb, (wb, eb)).astype(np.int32))
    d = jax.device_put(
        rng.integers(0, vb, (wb, eb)).astype(np.int32))
    valid = jax.device_put(np.ones((wb, eb), bool))
    ex = k._stream_exec(wb)   # AOT-compiled executable
    t = _median_time(
        lambda: jax.block_until_ready(ex(s, d, valid)), reps=5)
    row = {
        "probe": "device_compute",
        "backend": jax.default_backend(),
        "eb": eb, "k": k.kb, "windows_per_dispatch": wb,
        "chunk_edges": wb * eb,
        "compute_s": round(t, 4),
        "per_window_ms": round(t / wb * 1e3, 3),
        "compute_only_edges_per_s": round(wb * eb / t),
    }
    results.append(row)
    print(json.dumps(row), flush=True)


def stream_ab(jax, jnp, num_edges, results):
    """Both ingress formats through the kernel's OWN adopted dispatch
    path (TriangleWindowKernel(ingress=...)._count_stream_device), so
    the measured forms are exactly the shipping ones."""
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    eb, vb = 32768, 65536
    src, dst = make_stream(num_edges, vb)
    k_std = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                                 ingress="standard")
    k_cmp = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                                 ingress="compact")
    k_std.warm_chunks()
    k_cmp.warm_chunks()

    counts_std = counts_cmp = None

    def run_std():
        nonlocal counts_std
        counts_std = k_std._count_stream_device(src, dst)

    def run_cmp():
        nonlocal counts_cmp
        counts_cmp = k_cmp._count_stream_device(src, dst)

    t_std, t_std_min, t_std_max = _timed_stats(run_std, reps=3,
                                               warmup=1)
    t_cmp, t_cmp_min, t_cmp_max = _timed_stats(run_cmp, reps=3,
                                               warmup=1)
    # A parity failure is recorded ({parity: false}, no speedup
    # claim) instead of crashing the tool and losing the whole
    # section's probe rows.
    parity = counts_std == counts_cmp
    row = {
        "probe": "stream_ab",
        "backend": jax.default_backend(),
        "num_edges": len(src),
        "eb": eb, "k": k_std.kb,
        "windows_per_dispatch": k_std.MAX_STREAM_WINDOWS,
        "std_s": round(t_std, 3),
        "std_s_min": round(t_std_min, 3),
        "std_s_max": round(t_std_max, 3),
        "std_edges_per_s": round(len(src) / t_std),
        "compact_s": round(t_cmp, 3),
        "compact_s_min": round(t_cmp_min, 3),
        "compact_s_max": round(t_cmp_max, 3),
        "compact_edges_per_s": round(len(src) / t_cmp),
        "parity": bool(parity),
    }
    if parity:
        row["speedup"] = round(t_std / t_cmp, 3)
        # the dispersion envelope's pessimistic/optimistic pairings:
        # adopt only when even speedup_worst argues the win is real,
        # not a single lucky draw
        row["speedup_worst"] = round(t_std_min / t_cmp_max, 3)
        row["speedup_best"] = round(t_std_max / t_cmp_min, 3)
    else:
        print("PARITY FAILURE between ingress forms", file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


PROBE_NAMES = ("latency", "h2d", "device_compute", "stream_ab")


def commit_results(results, backend: str) -> None:
    """Merge this run's rows into the committed evidence under the
    same policy as tools/profile_kernels.py's flush: `ingress_ab`
    carries ONLY the stream_ab rows, the other probes land under
    `ingress_probes`; PERF.json updates only when its backend label
    matches the LIVE backend (a CPU run never overwrites chip-labeled
    rows), while the per-backend archive PERF_<backend>.json always
    takes the rows. Only keys this run
    produced are replaced — a stream_ab-only run keeps the committed
    bandwidth/latency probes."""
    ab = [r for r in results if r.get("probe") == "stream_ab"]
    probes = [r for r in results if r.get("probe") != "stream_ab"]
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
        if ab:
            cur["ingress_ab"] = ab
        if probes:
            cur["ingress_probes"] = probes
        with open(path, "w") as f:
            json.dump(cur, f, indent=2)  # the profiler's format
        print("committed %s row(s) to %s"
              % (len(ab) + len(probes), os.path.basename(path)),
              flush=True)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    # validated by hand, not via `choices`: argparse on Python <= 3.11
    # rejects an EMPTY nargs='*' list against choices, which would
    # break the documented no-argument run-everything invocation
    ap.add_argument("probes", nargs="*",
                    help="subset of %s to run (default: all)"
                         % (PROBE_NAMES,))
    ap.add_argument("--edges", type=int,
                    default=int(os.environ.get("GS_AB_EDGES", 10_485_760)))
    ap.add_argument("--commit", action="store_true",
                    help="merge rows into PERF.json (backend-matched) "
                         "and PERF_<backend>.json")
    args = ap.parse_args()
    bad = [p for p in args.probes if p not in PROBE_NAMES]
    if bad:
        ap.error("unknown probe(s) %s; valid: %s"
                 % (bad, list(PROBE_NAMES)))
    want = args.probes or list(PROBE_NAMES)

    import jax
    import jax.numpy as jnp

    results = []
    if "latency" in want:
        latency_probe(jax, jnp, results)
    if "h2d" in want:
        h2d_probe(jax, jnp, 32768, 16, results)
    if "device_compute" in want:
        device_compute_probe(jax, jnp, results)
    if "stream_ab" in want:
        stream_ab(jax, jnp, args.edges, results)
    out = os.path.join(REPO, "logs",
                       "ingress_ab_%s.json" % jax.default_backend())
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote %s" % out, flush=True)
    if args.commit:
        commit_results(results, jax.default_backend())


if __name__ == "__main__":
    main()

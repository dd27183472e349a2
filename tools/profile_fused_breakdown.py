#!/usr/bin/env python
"""Fused-scan vs driver: per-stage breakdown on identical input
(VERDICT r3 weak-4: the fused engine — built to beat the driver on
dispatch count — measured ~17% SLOWER on CPU; find where the seconds
go instead of hand-waving).

Decomposition on one quiet-host CPU run, same stream for every leg:

  driver          — StreamingAnalyticsDriver batched path, tracing on:
                    per-stage exclusive seconds (intern, snapshot_scan,
                    triangles, ...) from its StepTimer.
  fused           — StreamSummaryEngine.process as shipped (triangles
                    INSIDE the XLA scan program).
  fused_no_tri    — the same scan with the triangle stage compiled out
                    (degrees+CC+bipartite only): isolates what the
                    in-scan triangle intersect costs.
  tri_host_tier   — the driver's triangle route on a CPU backend: the
                    measurement-selected numpy tier
                    (ops/host_triangles.py), on the same windows.
  tri_xla_stream  — TriangleWindowKernel._count_stream_device: the
                    SAME XLA triangle program the fused scan embeds,
                    standalone.

The hypothesis this measures: on a 1-core CPU host the driver's
triangles ride the numpy host tier (~4.5x faster than XLA's intersect
on this host, PERF.json host_stream) while the fused engine is
structurally stuck with XLA triangles inside its scan; CPU dispatch
costs ~µs, so fusing dispatches buys nothing back. On chip (costly
dispatches, MXU intersect) the economics are expected to invert —
which is why the fused engine stays the chip-side throughput path.

Writes FUSED_BREAKDOWN.json and prints one JSON line per leg.
Run on a QUIET host (single core: any background load lands directly
in these numbers).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from gelly_streaming_tpu.core.platform import use_cpu  # noqa: E402

use_cpu()

import numpy as np  # noqa: E402


def _stream(num_edges, num_vertices, seed=7):
    rng = np.random.default_rng(seed)
    src = rng.zipf(1.9, num_edges).astype(np.int64) % num_vertices
    dst = (src + 1 + rng.zipf(1.9, num_edges).astype(np.int64)
           % (num_vertices - 1)) % num_vertices
    return src, dst


def _timeit(fn, reps=3, warmup=1):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def main():
    import jax

    from gelly_streaming_tpu import StreamingAnalyticsDriver
    from gelly_streaming_tpu.ops import scan_analytics, segment as seg_ops
    from gelly_streaming_tpu.ops import triangles as tri_ops

    eb = int(os.environ.get("GS_FB_EB", 8192))
    num_w = int(os.environ.get("GS_FB_WINDOWS", 64))
    vb = 2 * eb
    src, dst = _stream(num_w * eb, vb)
    rows = []

    def emit(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    emit({"leg": "config", "backend": jax.default_backend(),
          "edge_bucket": eb, "windows": num_w, "vertex_bucket": vb,
          "edges": num_w * eb})

    # ---- driver, tracing on: per-stage exclusive seconds
    drv = StreamingAnalyticsDriver(window_ms=0, edge_bucket=eb,
                                   vertex_bucket=vb, tracing=True)
    drv.run_arrays(src, dst)  # warm (compiles + host-tier selection)

    from gelly_streaming_tpu.utils.tracing import StepTimer

    def run_driver():
        drv.reset()
        drv.timer = StepTimer()   # per-rep stage totals (last rep kept)
        drv.run_arrays(src, dst)

    t = _timeit(run_driver)
    emit({"leg": "driver", "seconds": round(t, 3),
          "edges_per_s": round(num_w * eb / t),
          "stages": {r["op"]: {
              "seconds": round(r["total_s"], 3),
              "pct": round(100 * r["total_s"] / t, 1)}
              for r in drv.trace_report()}})

    # ---- fused engine as shipped
    eng = scan_analytics.StreamSummaryEngine(edge_bucket=eb,
                                             vertex_bucket=vb)
    eng.warm_fallback()

    def run_fused():
        eng.reset()
        eng.process(src, dst)

    t_fused = _timeit(run_fused)
    emit({"leg": "fused", "seconds": round(t_fused, 3),
          "edges_per_s": round(num_w * eb / t_fused),
          "k_bucket": eng.kb})

    # ---- the same scan WITHOUT the triangle stage: what does the
    # in-scan XLA intersect cost? (built inline: same body minus tri)
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops import unionfind

    sent = vb

    def body_no_tri(carry, xs):
        deg, labels, cover = carry
        s_, d_, valid = xs
        s = jnp.where(valid, s_, sent)
        d = jnp.where(valid, d_, sent)
        ones = jnp.where(valid, 1, 0)
        deg = deg + (jax.ops.segment_sum(ones, s, vb + 1)
                     + jax.ops.segment_sum(ones, d, vb + 1))
        max_degree = jnp.max(deg[:vb])
        labels = unionfind.cc_fixpoint(labels, s, d)
        touched = deg[:vb] > 0
        num_components = jnp.sum(
            touched & (labels[:vb] == jnp.arange(vb)), dtype=jnp.int32)
        cover = unionfind.cc_fixpoint(
            cover, jnp.concatenate([s, s + (vb + 1)]),
            jnp.concatenate([d + (vb + 1), d]))
        odd = jnp.any(touched & (cover[:vb] == cover[vb + 1:2 * vb + 1]))
        return (deg, labels, cover), (max_degree, num_components, odd)

    @jax.jit
    def run_scan_no_tri(carry, s_w, d_w, valid_w):
        return jax.lax.scan(body_no_tri, carry, (s_w, d_w, valid_w))

    _, s_w, d_w, valid_w = seg_ops.window_stack(src, dst, eb,
                                                sentinel=vb)
    carry0 = (jnp.zeros(vb + 1, jnp.int32),
              jnp.arange(vb + 1, dtype=jnp.int32),
              jnp.arange(2 * (vb + 1), dtype=jnp.int32))
    s_j, d_j, v_j = (jnp.asarray(x) for x in (s_w, d_w, valid_w))

    def run_no_tri():
        c, outs = run_scan_no_tri(carry0, s_j, d_j, v_j)
        jax.block_until_ready(outs)

    t_no_tri = _timeit(run_no_tri)
    emit({"leg": "fused_no_tri", "seconds": round(t_no_tri, 3),
          "edges_per_s": round(num_w * eb / t_no_tri),
          "implied_in_scan_triangle_seconds": round(t_fused - t_no_tri,
                                                    3)})

    # ---- the driver's CPU triangle route: numpy host tier
    from gelly_streaming_tpu.ops import host_triangles

    def run_host_tri():
        host_triangles.count_stream(src, dst, eb)

    t_host = _timeit(run_host_tri)
    emit({"leg": "tri_host_tier", "seconds": round(t_host, 3),
          "edges_per_s": round(num_w * eb / t_host)})

    # ---- the standalone XLA triangle stream program (what the fused
    # scan embeds), selection bypassed
    kern = tri_ops.TriangleWindowKernel(edge_bucket=eb,
                                        vertex_bucket=vb)
    kern._count_stream_device(src, dst)  # warm

    def run_xla_tri():
        kern._count_stream_device(src, dst)

    t_xla = _timeit(run_xla_tri)
    emit({"leg": "tri_xla_stream", "seconds": round(t_xla, 3),
          "edges_per_s": round(num_w * eb / t_xla),
          "k_bucket": kern.kb})

    # ---- the verdict, computed not asserted
    emit({"leg": "analysis",
          "fused_minus_no_tri_s": round(t_fused - t_no_tri, 3),
          "xla_vs_host_tri_ratio": round(t_xla / t_host, 2),
          "driver_wins_because":
              "driver = scan(no tri) + host-tier triangles + host "
              "assembly; fused = scan WITH XLA triangles. On this "
              "backend XLA intersect costs %.1fx the numpy tier and "
              "dispatch latency is negligible, so fusing cannot pay "
              "for it." % (t_xla / max(t_host, 1e-9))})
    with open(os.path.join(REPO, "FUSED_BREAKDOWN.json"), "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()

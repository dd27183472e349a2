#!/usr/bin/env python
"""On-chip perf characterization of the hot kernels (VERDICT r1 items
2-3): times each ⚡ path of SURVEY.md §2 on the active backend, derives
bytes-moved / FLOP / MFU-roofline estimates, and emits one JSON object
per section plus a combined PERF.json.

Sections (argv selects a subset; default: all single-chip):
  intersect  — chunked broadcast-compare vs per-row binary search
               (pins the 438ms->6.8ms claim in ops/triangles.py:94-99)
  window     — TriangleWindowKernel.count_stream per-window ms + MB/s
               (reference hot path: WindowTriangles.java:61-66)
  fused      — StreamSummaryEngine.process per-window ms (all four
               analytics fused; WindowGraphAggregation.java:54-58)
  dense      — XLA dense matmul vs Pallas fused contraction at
               V = 1024/2048/4096
  sharded    — sharded engines on the virtual 8-device CPU mesh
               (run in a subprocess so the backend pin doesn't leak)

Peak numbers for MFU/roofline are the public TPU v5e (v5 lite) specs:
197 TFLOP/s bf16 (MXU; f32 inputs run below this), 819 GB/s HBM.
Results on a CPU backend are labeled as such and never masquerade as
chip numbers (same contract as bench.py).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

PEAK_BF16_TFLOPS = 197.0   # TPU v5e MXU peak (public spec)
PEAK_HBM_GBPS = 819.0      # TPU v5e HBM bandwidth (public spec)


def _timeit(fn, reps: int = 5, warmup: int = 2) -> float:
    """Median wall seconds of fn() over reps after warmup calls. fn must
    block until the device result is ready (np.asarray / block_until_ready)."""
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _stream(num_edges: int, num_vertices: int, seed: int = 7):
    from bench import make_stream

    return make_stream(num_edges, num_vertices, seed)


def section_intersect(results: dict) -> None:
    """The dominant sparse kernel: |N(a) ∩ N(b)| per oriented edge.
    Compare the shipped chunked broadcast-equality compare against the
    vmap(searchsorted) binary-search lowering it replaced."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops.triangles import intersect_local

    ep, k, vb = 16_384, 256, 1 << 16
    rng = np.random.default_rng(3)
    # plausible sorted dedup'd neighbor rows: ~K/4 real entries per row
    fill = rng.integers(0, vb, size=(vb + 1, k), dtype=np.int32)
    fill.sort(axis=1)
    keep = np.arange(k) < k // 4
    nbr = np.where(keep[None, :], fill, vb).astype(np.int32)
    ea = rng.integers(0, vb, size=ep, dtype=np.int32)
    eb_ = rng.integers(0, vb, size=ep, dtype=np.int32)
    emask = np.ones(ep, bool)
    args = tuple(jnp.asarray(x) for x in (nbr, ea, eb_, emask))

    from gelly_streaming_tpu.ops.triangles import intersect_local_bsearch

    compare = jax.jit(intersect_local)
    binary_search = jax.jit(intersect_local_bsearch)

    from gelly_streaming_tpu.ops import pallas_intersect

    want = int(compare(*args))
    parity = want == int(binary_search(*args))
    t_cmp = _timeit(lambda: compare(*args).block_until_ready())
    t_bs = _timeit(lambda: binary_search(*args).block_until_ready())
    sweep = []
    if pallas_intersect._need_interpret():
        parity_pl, t_pl = None, None
    else:
        # Tile-shape sweep (VERDICT r4 item 6: one real iteration,
        # then decide). Candidates keep the [T, Ck, K] compare tensor
        # + three [T, K] input blocks under ~14MB of VMEM at K=256.
        # The best parity-true row becomes the section's headline
        # pallas_ms.
        for tile_e, chunk_k in ((32, 64), (32, 128), (64, 64),
                                (64, 128), (128, 64)):
            try:
                p = want == int(pallas_intersect.intersect_local_pallas(
                    *args, tile_e=tile_e, chunk_k=chunk_k))
                t = _timeit(
                    lambda: pallas_intersect.intersect_local_pallas(
                        *args, tile_e=tile_e,
                        chunk_k=chunk_k).block_until_ready())
                sweep.append({"tile_e": tile_e, "chunk_k": chunk_k,
                              "parity": p, "ms": round(t * 1e3, 3)})
            except Exception as e:   # a shape that fails to lower is
                sweep.append({"tile_e": tile_e, "chunk_k": chunk_k,
                              "error": str(e)[:160]})  # evidence too
            print(json.dumps({"intersect_sweep": sweep[-1]}),
                  flush=True)
        good = [r for r in sweep if r.get("parity") is True]
        if good:
            best = min(good, key=lambda r: r["ms"])
            parity_pl, t_pl = True, best["ms"] / 1e3
        else:
            parity_pl, t_pl = False, None
    # compare work: Ep*K*K int equality ops (+ masked sum)
    cmp_ops = ep * k * k
    results["intersect"] = {
        "ep": ep, "k": k, "parity": parity, "parity_pallas": parity_pl,
        "broadcast_compare_ms": round(t_cmp * 1e3, 3),
        "binary_search_ms": round(t_bs * 1e3, 3),
        "pallas_ms": round(t_pl * 1e3, 3) if t_pl else None,
        "pallas_sweep": sweep,
        "speedup_vs_binary_search": round(t_bs / t_cmp, 1),
        "pallas_vs_xla_compare": (round(t_cmp / t_pl, 2) if t_pl
                                  else None),
        "compare_gops_per_s": round(cmp_ops / t_cmp / 1e9, 1),
    }


def _count_overflow_recounts(kern, src, dst) -> int:
    """Run count_stream once with kern.count instrumented, returning
    how many per-window exact recounts (K-bucket overflows) the stream
    triggers; also warms every program the stream needs."""
    overflows = [0]
    orig = kern.count

    def counting(s, d, min_k=0):
        overflows[0] += 1
        return orig(s, d, min_k)

    kern.count = counting
    try:
        # the DEVICE path explicitly: on a CPU backend with committed
        # winning host_stream rows, count_stream routes to the numpy
        # tier, which would make every K/chunk sweep row time the same
        # K-independent host code (the committed-PERF feedback the
        # sweep's anchor comments guard against)
        kern._count_stream_device(src, dst)
    finally:
        kern.count = orig
    return overflows[0]


def section_window(results: dict) -> None:
    """TriangleWindowKernel.count_stream: per-window latency and h2d
    bandwidth at three window sizes (64 windows each). The K×K
    intersection compare dominates and shrinks quadratically with the
    K bucket, so each size also sweeps K below the default — a smaller
    K wins whenever the stream's max oriented out-degree stays under
    it (overflowing windows pay an exact per-window recount, counted
    here)."""
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    # 8K/32K are the bench's window sizes (bench.py's window cap).
    # Extend via GS_PROFILE_BIG=1 only when babysitting the run. CPU
    # backends the 10M-scale legs use 65536-edge windows, so sweep that size
    # too off-chip (its tuned K feeds the scale run's kernels).
    import jax

    sizes = (8_192, 32_768)
    if jax.default_backend() == "cpu":
        sizes = sizes + (65_536,)
    if os.environ.get("GS_PROFILE_BIG") == "1":
        sizes = sizes + (131_072,)
    out = []
    for eb in sizes:
        vb = 2 * eb
        num_w = 64
        src, dst = _stream(num_w * eb, vb)
        row = {"edge_bucket": eb, "windows": num_w,
               "h2d_mb_per_chunk": round(num_w * eb * 2 * 4 / 1e6, 1),
               "k_sweep": []}
        # anchor the sweep on the ANALYTIC heuristic, never the tuned
        # value, so successive profiling runs never ratchet K downward
        default_kb = min(128, 2 * int(np.sqrt(eb)))
        # the sweeps' chunk anchor: deterministic per (backend, eb) —
        # the compile-size-capped default on TPU backends
        # (ops/triangles._default_chunk), the class default elsewhere.
        from gelly_streaming_tpu.ops.triangles import _default_chunk

        anchor_chunk = _default_chunk(eb)
        kernels = {}
        for kb in sorted({default_kb, default_kb // 2, default_kb // 4}):
            kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                                        k_bucket=kb)
            kern.MAX_STREAM_WINDOWS = anchor_chunk
            kernels[kern.kb] = kern
            # one instrumented pass counts the overflow recounts an
            # undersized K pays (and warms every program it needs),
            # then the clean timing runs uninstrumented
            overflow_count = _count_overflow_recounts(kern, src, dst)
            t = _timeit(lambda: kern._count_stream_device(src, dst),
                        reps=3, warmup=0)
            row["k_sweep"].append({
                "k_bucket": kern.kb,
                "default": kern.kb == default_kb,
                "per_window_ms": round(t / num_w * 1e3, 3),
                "edges_per_s": round(num_w * eb / t),
                "overflow_recounts_per_run": overflow_count,
            })
        # chunk sweep (windows per dispatch) at the fastest clean K: on
        # the chip chunk size trades h2d size against dispatch
        # amortization; on CPU it
        # should be flat (dispatch ~free) — both facts worth pinning.
        # The stream needs AT LEAST as many windows as the largest
        # chunk (128; equality suffices — cs=128 then times one full
        # dispatch, cs=64 times two), else the biggest rows silently
        # re-time the same dispatch; reuse the k_sweep's compiled
        # kernel.
        # the fastest MEASURED row wins outright — its timing already
        # includes its own recount cost
        best_kb = min(row["k_sweep"],
                      key=lambda s: s["per_window_ms"])["k_bucket"]
        kern = kernels[best_kb]
        cnum_w = 128
        csrc, cdst = _stream(cnum_w * eb, vb, seed=8)
        row["chunk_sweep_k"] = best_kb
        row["chunk_sweep_windows"] = cnum_w
        # warms every needed program + counts recounts once
        row["chunk_sweep_overflow_recounts"] = _count_overflow_recounts(
            kern, csrc, cdst)
        row["chunk_sweep"] = []
        if jax.default_backend() == "tpu":
            # stay under the compile-size wedge line (see anchor note)
            cs_values = sorted({max(1, anchor_chunk // 4),
                                max(1, anchor_chunk // 2), anchor_chunk})
        else:
            cs_values = [32, 64, 128]
        for cs in cs_values:
            kern.MAX_STREAM_WINDOWS = cs
            kern._count_stream_device(csrc, cdst)  # warm this chunk shape
            t = _timeit(lambda: kern._count_stream_device(csrc, cdst),
                        reps=3, warmup=0)
            row["chunk_sweep"].append({
                "windows_per_dispatch": cs,
                "default": cs == anchor_chunk,
                "per_window_ms": round(t / cnum_w * 1e3, 3),
                "edges_per_s": round(cnum_w * eb / t),
            })
        # leave the kernel at the anchor chunk (the instance attr is
        # always set now — __init__ tunes it, this sweep overwrote it)
        kern.MAX_STREAM_WINDOWS = anchor_chunk
        out.append(row)
    results["window"] = out


def section_fused(results: dict) -> None:
    """StreamSummaryEngine: all four analytics (degrees, CC,
    bipartiteness, triangles) fused into one scan dispatch."""
    from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine

    out = []
    for eb in (8_192, 32_768):
        vb = 2 * eb
        num_w = 64
        src, dst = _stream(num_w * eb, vb)
        eng = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
        eng.warm_fallback()

        def run():
            eng.reset()
            eng.process(src, dst)

        t = _timeit(run, reps=3, warmup=1)
        out.append({
            "edge_bucket": eb, "windows": num_w,
            "per_window_ms": round(t / num_w * 1e3, 3),
            "edges_per_s": round(num_w * eb / t),
        })
    results["fused"] = out


def section_driver(results: dict) -> None:
    """StreamingAnalyticsDriver end-to-end: the batched fast path (one
    snapshot-scan dispatch + one triangle stack dispatch per 64-window
    chunk) vs the per-window dispatch path on the same stream — the
    dispatch-economics win this round's driver work targets."""
    from gelly_streaming_tpu import StreamingAnalyticsDriver

    eb, num_w = 8_192, 32
    vb = 2 * eb
    src, dst = _stream(num_w * eb, vb)
    out = {}
    for mode in ("batched", "per-window"):
        drv = StreamingAnalyticsDriver(window_ms=0, edge_bucket=eb,
                                       vertex_bucket=vb)

        def run():
            drv.reset()
            if mode == "batched":
                drv.run_arrays(src, dst)
            else:
                for i in range(0, len(src), eb):
                    drv.run_arrays(src[i:i + eb], dst[i:i + eb])

        t = _timeit(run, reps=3, warmup=1)
        out[mode] = {"per_window_ms": round(t / num_w * 1e3, 3),
                     "edges_per_s": round(num_w * eb / t)}
    out["speedup"] = round(
        out["per-window"]["per_window_ms"]
        / out["batched"]["per_window_ms"], 2)
    out["edge_bucket"] = eb
    out["windows"] = num_w
    results["driver"] = out


def _dense_stream(v: int):
    e = 16 * v
    rng = np.random.default_rng(5)
    src = rng.integers(0, v, size=e, dtype=np.int32)
    dst = rng.integers(0, v, size=e, dtype=np.int32)
    keep = src != dst
    return src[keep], dst[keep]


def run_dense_child(v: int, impl: str) -> None:
    """Parity-check + time ONE dense implementation at ONE V, as its
    own process: a wedged remote compile (the r04 failure mode — the
    dense section never produced a chip MFU row in four rounds) then
    costs one (V, impl) cell, not the whole section."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops import pallas_triangles
    from gelly_streaming_tpu.ops.triangles import (_dense_row_counts,
                                                   triangle_count_dense,
                                                   triangle_count_sparse)

    src, dst = _dense_stream(v)
    want = triangle_count_sparse(src, dst, v)
    sj, dj = jnp.asarray(src), jnp.asarray(dst)
    if impl == "xla":
        got = triangle_count_dense(src, dst, v)
        t = _timeit(
            lambda: _dense_row_counts(sj, dj, v).block_until_ready())
    else:
        if pallas_triangles._need_interpret():
            raise SystemExit("pallas needs a real TPU backend")
        got = pallas_triangles.triangle_count_dense_pallas(src, dst, v)
        t = _timeit(lambda: pallas_triangles._adjacency_six_t(
            sj, dj, v, False).block_until_ready())
    flops = 2 * v ** 3  # the A@A contraction dominates
    print(json.dumps({
        "v": v, "impl": impl, "ok": got == want,
        "ms": round(t * 1e3, 3),
        "mfu": round(flops / t / (PEAK_BF16_TFLOPS * 1e12), 4),
        "backend": jax.default_backend(),
    }), flush=True)


def section_dense(results: dict) -> None:
    """Dense triangle path: XLA matmul (A@A ⊙ A row sums) vs the
    Pallas fused contraction, each (V, impl) compiled+timed in its own
    hard-timeout subprocess, V ascending from 512 — so the first MFU
    rows land even if a larger shape hangs. Runs in the JAX-free
    parent (SPAWNING): each child holds the chip alone. The winner
    becomes the default dense path — see ops/triangles.triangle_count."""
    from bench import run_json_child

    backend = results["backend"]
    if backend != "tpu":
        # interpreter-mode Pallas timings are meaningless (and V=4096
        # takes hours on CPU); parity is already covered by tests
        results["dense"] = {"skipped": "non-TPU backend (interpret "
                                       "mode times nothing real)"}
        return
    out = []
    for v in (512, 1024, 2048, 4096):
        row = {"v": v, "edges": int(len(_dense_stream(v)[0]))}
        for impl in ("xla", "pallas"):
            got = run_json_child(
                [sys.executable, os.path.abspath(__file__),
                 "--dense", str(v), impl], PROBE_TIMEOUT_S)
            if got.get("ok") and got.get("backend") == backend:
                row["%s_ms" % impl] = got["ms"]
                row["%s_mfu_vs_bf16_peak" % impl] = got["mfu"]
            elif got.get("ok") is False:
                row["%s_error" % impl] = "parity failure"
            else:
                row["%s_error" % impl] = str(
                    got.get("error") or "backend mismatch")[:200]
        if "xla_ms" in row and "pallas_ms" in row:
            row["pallas_speedup"] = round(
                row["xla_ms"] / row["pallas_ms"], 2)
        out.append(row)
        print(json.dumps({"dense_progress": row}), flush=True)
    results["dense"] = out


def _cost_rows(compiled):
    """(flops, bytes_accessed) from XLA's cost model for an AOT-compiled
    executable; (None, None) when the backend doesn't report them.
    Unwraps costmodel.wrap_exec wrappers (the kernels' cached stream
    executables carry the raw executable on __wrapped__)."""
    compiled = getattr(compiled, "__wrapped__", compiled)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return ca.get("flops"), ca.get("bytes accessed")
    except Exception:
        return None, None


def _roofline_row(name, compiled, args, extra=None):
    """Time one AOT executable + place it on the v5e roofline: achieved
    GFLOP/s vs the 197 TFLOP/s bf16 MXU peak and achieved GB/s vs the
    819 GB/s HBM peak (XLA's own flops / bytes-accessed cost model; on
    a CPU backend the fractions are labeled by the section's backend
    key and are structure checks, not chip numbers)."""
    import jax

    t = _timeit(lambda: jax.tree_util.tree_map(
        lambda x: x.block_until_ready(), compiled(*args)))
    flops, bts = _cost_rows(compiled)
    row = {"program": name, "ms": round(t * 1e3, 3)}
    if flops:
        row["gflops_achieved"] = round(flops / t / 1e9, 2)
        row["mfu_vs_bf16_peak"] = round(
            flops / t / (PEAK_BF16_TFLOPS * 1e12), 5)
    if bts:
        row["gbps_achieved"] = round(bts / t / 1e9, 2)
        row["hbm_frac_of_peak"] = round(bts / t / (PEAK_HBM_GBPS * 1e9), 5)
    if flops and bts:
        # which peak the program sits closer to at this timing
        row["bound"] = ("compute" if row["mfu_vs_bf16_peak"]
                        >= row["hbm_frac_of_peak"] else "memory")
        row["arith_intensity_flops_per_byte"] = round(flops / bts, 2)
    if extra:
        row.update(extra)
    return row


def section_roofline(results: dict) -> None:
    """MFU / roofline placement of every hot program (VERDICT r3 item
    1: 'achieved GOP/s vs 197 TFLOP/s bf16 and achieved GB/s vs 819
    GB/s per kernel'). FLOP and byte counts come from XLA's compiled
    cost model (not hand math), times from warmed dispatches of the
    production configurations."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops.triangles import (TriangleWindowKernel,
                                                   _dense_row_counts,
                                                   intersect_local,
                                                   intersect_local_bsearch)

    rows = []
    # --- the streaming window program at both bench buckets, exactly
    # as the bench dispatches it (tuned K, tuned/compile-capped chunk —
    # ops/triangles._default_chunk)
    for eb in (8_192, 32_768):
        vb = 2 * eb
        kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
        num_w = kern.MAX_STREAM_WINDOWS
        src, dst = _stream(num_w * eb, vb)
        from gelly_streaming_tpu.ops import segment as seg_ops

        _, s, d, valid = seg_ops.window_stack(src, dst, kern.eb,
                                              sentinel=kern.vb)
        ex = kern._stream_exec(num_w)
        args = (jnp.asarray(s[:num_w]), jnp.asarray(d[:num_w]),
                jnp.asarray(valid[:num_w]))
        rows.append(_roofline_row(
            "window_stream_eb%d" % eb, ex, args,
            {"k_bucket": kern.kb, "windows": num_w,
             "edges_per_s": None}))
        # fill the throughput key from the measured ms
        rows[-1]["edges_per_s"] = round(
            num_w * eb / (rows[-1]["ms"] / 1e3))

    # --- the two intersect lowerings at the profile shape
    ep, k, vbi = 16_384, 256, 1 << 16
    rng = np.random.default_rng(3)
    fill = rng.integers(0, vbi, size=(vbi + 1, k), dtype=np.int32)
    fill.sort(axis=1)
    keep = np.arange(k) < k // 4
    nbr = jnp.asarray(np.where(keep[None, :], fill, vbi).astype(np.int32))
    ea = jnp.asarray(rng.integers(0, vbi, size=ep, dtype=np.int32))
    eb_ = jnp.asarray(rng.integers(0, vbi, size=ep, dtype=np.int32))
    em = jnp.ones(ep, bool)
    for name, fn in (("intersect_compare", intersect_local),
                     ("intersect_bsearch", intersect_local_bsearch)):
        ex = jax.jit(fn).lower(nbr, ea, eb_, em).compile()
        rows.append(_roofline_row(name, ex, (nbr, ea, eb_, em),
                                  {"ep": ep, "k": k}))

    # --- the dense MXU path at its cutover size
    v = 2048
    e = 16 * v
    rng = np.random.default_rng(5)
    ds = jnp.asarray(rng.integers(0, v, size=e, dtype=np.int32))
    dd = jnp.asarray(rng.integers(0, v, size=e, dtype=np.int32))
    ex = jax.jit(_dense_row_counts, static_argnums=2).lower(
        ds, dd, v).compile()
    rows.append(_roofline_row("dense_matmul_v%d" % v, ex, (ds, dd),
                              {"v": v}))
    results["roofline"] = {
        "peaks": {"bf16_tflops": PEAK_BF16_TFLOPS,
                  "hbm_gbps": PEAK_HBM_GBPS, "hw": "tpu v5e (public)"},
        "rows": rows,
    }


def section_trace(results: dict) -> None:
    """Device trace of one production 64-window stream dispatch
    (VERDICT r3 item 1: 'a device_trace of one 64-window chunk').
    Captures a jax.profiler trace to logs/device_trace_<backend>/ and
    commits the parsed per-op time breakdown (top ops by total device
    time from the Chrome-trace export) into PERF.json — the raw xplane
    stays in logs/ as the artifact."""
    import glob
    import gzip

    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops import segment as seg_ops
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    eb = 32_768
    vb = 2 * eb
    kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
    # the production chunk (compile-capped on TPU backends —
    # ops/triangles._default_chunk)
    num_w = kern.MAX_STREAM_WINDOWS
    src, dst = _stream(num_w * eb, vb)
    _, s, d, valid = seg_ops.window_stack(src, dst, kern.eb,
                                          sentinel=kern.vb)
    ex = kern._stream_exec(num_w)
    args = (jnp.asarray(s[:num_w]), jnp.asarray(d[:num_w]),
            jnp.asarray(valid[:num_w]))
    for _ in range(2):  # warm: compile + first-dispatch noise out
        ex(*args)[0].block_until_ready()
    tdir = os.path.join(REPO, "logs",
                        "device_trace_%s" % jax.default_backend())
    os.makedirs(tdir, exist_ok=True)
    jax.profiler.start_trace(tdir)
    t0 = time.perf_counter()
    ex(*args)[0].block_until_ready()
    wall = time.perf_counter() - t0
    jax.profiler.stop_trace()

    # parse the Chrome-trace export: total device time by op name
    tops, err = [], None
    try:
        traces = sorted(glob.glob(os.path.join(
            tdir, "plugins", "profile", "*", "*.trace.json.gz")),
            key=os.path.getmtime)
        with gzip.open(traces[-1], "rt") as f:
            events = json.load(f).get("traceEvents", [])
        by_name = {}
        for ev in events:
            if ev.get("ph") == "X" and ev.get("dur"):
                rec = by_name.setdefault(ev["name"], [0.0, 0])
                rec[0] += ev["dur"] / 1e3  # us -> ms
                rec[1] += 1
        tops = [{"op": n, "total_ms": round(ms, 3), "calls": c}
                for n, (ms, c) in sorted(by_name.items(),
                                         key=lambda kv: -kv[1][0])[:15]]
    except Exception as e:  # trace format drift must not sink the run
        err = "trace parse failed: %r" % e
    results["trace"] = {
        "edge_bucket": eb, "windows": num_w, "k_bucket": kern.kb,
        "dispatch_wall_ms": round(wall * 1e3, 3),
        "trace_dir": os.path.relpath(tdir, REPO),
        "top_ops": tops,
        **({"parse_error": err} if err else {}),
    }


def section_host_stream(results: dict) -> None:
    """Vectorized numpy window tier vs the device (XLA) stream kernel
    on THIS backend, per edge bucket. The rows are a record: the
    streaming counter always runs the device program. Keep the
    chip's host quiet during this section."""
    import jax

    from gelly_streaming_tpu.ops import host_triangles
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    from gelly_streaming_tpu import native

    sizes = (8_192, 32_768)
    if jax.default_backend() == "cpu":
        sizes = sizes + (65_536,)
    out = []
    for eb in sizes:
        vb = 2 * eb
        num_w = 32
        src, dst = _stream(num_w * eb, vb)
        kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
        dev = kern._count_stream_device(src, dst)   # compile + warm
        host = host_triangles.count_stream(src, dst, eb)
        t_dev = _timeit(lambda: kern._count_stream_device(src, dst),
                        reps=3, warmup=0)
        t_host = _timeit(lambda: host_triangles.count_stream(
            src, dst, eb), reps=3, warmup=0)
        row = {
            "edge_bucket": eb, "windows": num_w,
            "parity": host == dev,
            "host_edges_per_s": round(num_w * eb / t_host),
            "device_edges_per_s": round(num_w * eb / t_dev),
            "host_vs_device": round(t_dev / t_host, 2),
        }
        if native.triangles_available():
            # the C++ compact-forward tier (native/ingest.cpp) competes
            # under the same committed-evidence rule
            nat = native.triangle_count_stream(src, dst, eb)
            t_nat = _timeit(lambda: native.triangle_count_stream(
                src, dst, eb), reps=3, warmup=0)
            row["native_parity"] = list(nat) == dev
            row["native_edges_per_s"] = round(num_w * eb / t_nat)
        out.append(row)
    results["host_stream"] = out


def section_pipeline(results: dict) -> None:
    """Per-stage (prep ms / h2d ms / compute ms per chunk)
    decomposition of the pipelined stream dispatch
    (ops/ingress_pipeline.StageTimers) plus a pipelined-vs-forced-sync
    A/B of the device path at both bench buckets and both wire
    formats — committed so the next chip run can decompose the
    chip-side wall (host prep vs transfer vs compute) without new
    instrumentation. Counts parity is asserted into the row, never
    assumed."""
    from gelly_streaming_tpu.ops import compact_ingress, ingress_pipeline
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel
    from gelly_streaming_tpu.ops.windowed_reduce import WindowedEdgeReduce

    rows = []
    for eb, ingress in ((8_192, "standard"), (32_768, "standard"),
                        (32_768, "compact")):
        vb = 2 * eb
        if ingress == "compact" and not compact_ingress.supports(vb):
            continue
        num_w = 64
        src, dst = _stream(num_w * eb, vb)
        kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb,
                                    ingress=ingress)
        got = {}

        def run_pipe():
            got["pipe"] = kern._count_stream_device(src, dst)

        def run_sync():
            with ingress_pipeline.forced_sync():
                got["sync"] = kern._count_stream_device(src, dst)

        run_pipe()                       # compile + warm
        kern.stage_timers.reset()        # timers cover timed reps only
        t_pipe = _timeit(run_pipe, reps=3, warmup=0)
        snap = kern.stage_timers.snapshot()
        t_sync = _timeit(run_sync, reps=3, warmup=0)
        row = {
            "engine": "triangle_stream", "edge_bucket": eb,
            "ingress": ingress, "windows": num_w,
            "windows_per_dispatch": kern.MAX_STREAM_WINDOWS,
            "workers": ingress_pipeline.worker_count(),
            "parity": got["pipe"] == got["sync"],
            "pipelined_edges_per_s": round(num_w * eb / t_pipe),
            "sync_edges_per_s": round(num_w * eb / t_sync),
            "pipeline_speedup": round(t_sync / t_pipe, 2),
            **snap,
        }
        rows.append(row)
        print(json.dumps({"pipeline_progress": row}), flush=True)

    # one windowed-reduce row: the second engine routed through the
    # pipeline (BASELINE config #2's device path)
    eb, nv, num_w = 8_192, 16_384, 64
    src, dst = _stream(num_w * eb, nv)
    val = (1 + (src + 3 * dst) % 97).astype(np.int32)
    eng = WindowedEdgeReduce(vertex_bucket=nv, edge_bucket=eb,
                             name="sum", direction="out")
    s64, d64 = src.astype(np.int64), dst.astype(np.int64)
    got = {}

    def r_pipe():
        got["pipe"] = eng._device_process_stream(s64, d64, val)

    def r_sync():
        with ingress_pipeline.forced_sync():
            got["sync"] = eng._device_process_stream(s64, d64, val)

    r_pipe()
    eng.stage_timers.reset()
    t_pipe = _timeit(r_pipe, reps=3, warmup=0)
    snap = eng.stage_timers.snapshot()
    t_sync = _timeit(r_sync, reps=3, warmup=0)
    rows.append({
        "engine": "windowed_reduce", "edge_bucket": eb,
        "ingress": eng.ingress, "windows": num_w,
        "workers": ingress_pipeline.worker_count(),
        "parity": all(
            np.array_equal(ca, cb) and np.array_equal(na, nb)
            for (ca, na), (cb, nb) in zip(got["pipe"], got["sync"])),
        "pipelined_edges_per_s": round(num_w * eb / t_pipe),
        "sync_edges_per_s": round(num_w * eb / t_sync),
        "pipeline_speedup": round(t_sync / t_pipe, 2),
        **snap,
    })
    print(json.dumps({"pipeline_progress": rows[-1]}), flush=True)
    results["pipeline_stages"] = rows


def section_host_reduce(results: dict) -> None:
    """Columnar windowed-reduce tiers (ops/windowed_reduce.py): device
    segment kernels vs the vectorized host kernel, per monoid
    (BASELINE config #2's engine). Parity asserted row by row before
    timing."""
    import numpy as np

    from gelly_streaming_tpu.ops.windowed_reduce import WindowedEdgeReduce

    from gelly_streaming_tpu import native

    rows = []
    for name, eb in (("sum", 8_192), ("sum", 32_768), ("min", 8_192)):
        nv = 2 * eb
        num_w = 32
        src, dst = _stream(num_w * eb, nv)
        val = (1 + (src + 3 * dst) % 97).astype(np.int32)
        eng = WindowedEdgeReduce(vertex_bucket=nv, edge_bucket=eb,
                                 name=name, direction="out")
        dev = eng._device_process_stream(src, dst, val)   # compile+warm
        host = eng._host_process_stream(src, dst, val)
        parity = all(
            (np.array_equal(hc[:nv], dc[:nv])
             if name == "sum" else
             np.array_equal(hc[:nv][hn[:nv] > 0], dc[:nv][dn[:nv] > 0]))
            and np.array_equal(hn[:nv], dn[:nv])
            for (dc, dn), (hc, hn) in zip(dev, host))
        t_dev = _timeit(lambda: eng._device_process_stream(
            src, dst, val), reps=3, warmup=0)
        t_host = _timeit(lambda: eng._host_process_stream(
            src, dst, val), reps=3, warmup=0)
        row = {
            "name": name, "edge_bucket": eb, "windows": num_w,
            "parity": parity,
            "host_edges_per_s": round(num_w * eb / t_host),
            "device_edges_per_s": round(num_w * eb / t_dev),
            "host_vs_device": round(t_dev / t_host, 2),
        }
        if native.windowed_reduce_available():
            # the C++ fused form, measured alongside
            nat = eng._native_process_stream(src, dst, val)
            row["native_parity"] = nat is not None and all(
                (np.array_equal(nc[:nv], hc[:nv]) if name == "sum"
                 else np.array_equal(nc[:nv][hn[:nv] > 0],
                                     hc[:nv][hn[:nv] > 0]))
                and np.array_equal(nn[:nv], hn[:nv])
                for (nc, nn), (hc, hn) in zip(nat, host))
            t_nat = _timeit(lambda: eng._native_process_stream(
                src, dst, val), reps=3, warmup=0)
            row["native_edges_per_s"] = round(num_w * eb / t_nat)
        rows.append(row)
    results["host_reduce"] = rows


def section_sharded(out_path: str) -> dict:
    """Run the sharded engines on the virtual 8-device CPU mesh in a
    subprocess (the backend pin must precede jax import)."""
    code = r"""
import json, sys, time
import numpy as np
sys.path.insert(0, %r)
from gelly_streaming_tpu.core.platform import cpu_mesh
cpu_mesh(8)
from bench import make_stream
from gelly_streaming_tpu.parallel.mesh import make_mesh
from gelly_streaming_tpu.parallel.sharded import (ShardedSummaryEngine,
                                                  ShardedTriangleWindowKernel)

mesh = make_mesh()
eb, vb, num_w = 8192, 16384, 16
src, dst = make_stream(num_w * eb, vb)
out = {}
for name, eng in (
    ("sharded_triangles", ShardedTriangleWindowKernel(
        mesh, edge_bucket=eb, vertex_bucket=vb)),
    ("sharded_fused", ShardedSummaryEngine(
        mesh, edge_bucket=eb, vertex_bucket=vb)),
):
    run = (eng.count_stream if hasattr(eng, "count_stream")
           else eng.process)
    def call():
        if hasattr(eng, "reset"):
            eng.reset()
        run(src, dst)
    call()  # compile
    ts = []
    for _ in range(3):
        t0 = time.perf_counter(); call(); ts.append(time.perf_counter() - t0)
    t = float(np.median(ts))
    out[name] = {"edge_bucket": eb, "windows": num_w, "devices": 8,
                 "backend": "cpu-virtual-mesh",
                 "per_window_ms": round(t / num_w * 1e3, 3),
                 "edges_per_s": round(num_w * eb / t)}

# owner vs replicated neighbor-row distribution (resolve_table_mode
# picks by the modelled bytes below): wall-clock at a small-table
# shape AND the 10M-scale bucket shape, plus the analytic ICI
# accounting. The top-level *_edges_per_s keys carry the LARGE config.
from gelly_streaming_tpu.parallel.sharded import (ici_time_model,
                                                  window_collective_bytes)

tbl = {"devices": 8, "backend": "cpu-virtual-mesh", "rows": []}
for ceb, cvb, cw in ((8192, 16384, 16), (65536, 262144, 2)):
    csrc, cdst = make_stream(cw * ceb, cvb)
    row = {"edge_bucket": ceb, "vertex_bucket": cvb, "windows": cw}
    counts = {}
    for mode in ("replicated", "owner"):
        k = ShardedTriangleWindowKernel(mesh, edge_bucket=ceb,
                                        vertex_bucket=cvb, table=mode)
        counts[mode] = k.count_stream(csrc, cdst)   # compile + warm
        ts = []
        for _ in range(3):   # median of 3: a single sample on this
            t0 = time.perf_counter()   # loaded host could flip the
            k.count_stream(csrc, cdst)  # 5-percent bar by noise
            ts.append(time.perf_counter() - t0)
        t = float(np.median(ts))
        row[mode + "_edges_per_s"] = round(cw * ceb / t)
        b = window_collective_bytes(8, k.vb, k.kb, k.cap, mode)
        row[mode + "_ici_bytes_per_window"] = round(b["total"])
        b5 = ici_time_model(b)
        row[mode + "_ici_ms_v5e_model"] = round(b5["total"] * 1e3, 3)
    row["counts_match"] = counts["replicated"] == counts["owner"]
    tbl["rows"].append(row)
big = tbl["rows"][-1]
tbl["owner_edges_per_s"] = big["owner_edges_per_s"]
tbl["replicated_edges_per_s"] = big["replicated_edges_per_s"]
tbl["counts_match"] = all(r["counts_match"] for r in tbl["rows"])
out["sharded_table"] = tbl

# ---- per-collective measured-vs-modeled breakdown (VERDICT r3 item 7):
# each collective of build_sharded_window_counter microbenched ALONE at
# the exact shapes of the 10M-scale config (eb=65536, vb=262144), next
# to the analytic per-chip ICI bytes (window_collective_bytes) and the
# v5e ICI time model. On this virtual CPU mesh the measured column is
# shared-memory copy/dispatch time — a STRUCTURE validation; the same
# rows become the real ICI validation the day a multi-chip mesh exists.
import functools
import jax
from jax.sharding import PartitionSpec as P
from jax import shard_map
from gelly_streaming_tpu.parallel.mesh import SHARD_AXIS
from gelly_streaming_tpu.parallel.sharded import ici_time_model

n = 8
big_kern = ShardedTriangleWindowKernel(mesh, edge_bucket=65536,
                                       vertex_bucket=262144)
cvb, ckb, ccap = big_kern.vb, big_kern.kb, big_kern.cap
kslice = ckb // n
m = n * ccap
ax = SHARD_AXIS
rng = np.random.default_rng(11)


def smap(body, in_specs, out_specs):
    # check_vma off: these are timing microbenches of single collectives
    # (all_gather's per-shard-identical output is not provably
    # replicated to the vma checker without a no-op collective, which
    # would pollute the very timing being measured)
    try:
        wrapped = functools.partial(
            shard_map, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False)(body)
    except TypeError:   # older shard_map: no check_vma kwarg
        wrapped = functools.partial(
            shard_map, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs)(body)
    return jax.jit(wrapped)


def t_of(fn, *args):
    import jax.numpy as jnp
    jargs = tuple(jnp.asarray(a) for a in args)
    r = fn(*jargs)
    jax.block_until_ready(r)   # compile + warm
    ts = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*jargs))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


def a2a(x):
    return jax.lax.all_to_all(x, ax, split_axis=0, concat_axis=0,
                              tiled=True)


progs = {
    "psum_degrees": (
        smap(lambda x: jax.lax.psum(x[0], ax), (P(ax),), P()),
        [rng.integers(0, 9, size=(n, cvb + 1), dtype=np.int32)]),
    "all_to_all_pairs": (
        smap(lambda x, y: (a2a(x), a2a(y)), (P(ax), P(ax)),
             (P(ax), P(ax))),
        [rng.integers(0, cvb, size=(n * n, ccap), dtype=np.int32),
         rng.integers(0, cvb, size=(n * n, ccap), dtype=np.int32)]),
    "pmax_table": (
        smap(lambda x: jax.lax.pmax(x[0], ax), (P(ax),), P()),
        [np.zeros((n, cvb + 1, ckb), np.int32)]),
    "all_gather_row_ids": (
        smap(lambda x: jax.lax.all_gather(x, ax), (P(ax),), P()),
        [rng.integers(0, cvb, size=n * 2 * m, dtype=np.int32)]),
    "all_to_all_row_slices": (
        smap(a2a, (P(ax),), P(ax)),
        [rng.integers(-1, cvb, size=(n * n, 2 * m, kslice),
                      dtype=np.int32)]),
    "psum_count_and_overflow": (
        smap(lambda x: jax.lax.psum(x[0], ax), (P(ax),), P()),
        [rng.integers(0, 9, size=(n, 3), dtype=np.int32)]),
}
from gelly_streaming_tpu.parallel.sharded import window_collective_bytes
model_r = window_collective_bytes(n, cvb, ckb, ccap, "replicated")
model_o = window_collective_bytes(n, cvb, ckb, ccap, "owner")
model = dict(model_o); model.update(model_r)
tmodel = ici_time_model(model)
coll_rows = []
for cname, (prog, args) in progs.items():
    row = {
        "collective": cname,
        "modeled_ici_bytes_per_chip": round(model[cname]),
        "modeled_ms_v5e_ici": round(tmodel[cname] * 1e3, 4),
    }
    try:   # one collective's lowering quirk must not sink the section
        row["measured_ms_cpu_mesh"] = round(t_of(prog, *args), 3)
    except Exception as exc:
        row["error"] = repr(exc)[:300]
    coll_rows.append(row)
out["collectives"] = {
    "config": {"n": n, "vb": cvb, "kb": ckb, "cap": ccap,
               "edge_bucket": 65536},
    "backend": "cpu-virtual-mesh",
    "note": ("measured column is host shared-memory copy time on the "
             "virtual mesh; modeled columns are the exact per-chip ICI "
             "accounting to validate on real multi-chip hardware"),
    "rows": coll_rows,
}
print(json.dumps(out))
""" % REPO
    # a CPU child on a virtual 8-device mesh; the code above
    # sys.path-inserts the repo itself. run_json_child gives the same
    # killpg-on-timeout contract as the chip sections.
    from bench import run_json_child

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    return run_json_child([sys.executable, "-c", code], 1800, env=env)


def section_ingress_ab(results: dict) -> None:
    """Stream-chunk wire-format A/B (ops/compact_ingress.py), via the
    same probes as the standalone tools/ingress_ab.py. `ingress_ab`
    carries ONLY the stream A/B rows; the latency/bandwidth probes
    land under `ingress_probes`."""
    import jax
    import jax.numpy as jnp

    from tools.ingress_ab import (device_compute_probe, h2d_probe,
                                  latency_probe, stream_ab)

    probes, ab = [], []
    latency_probe(jax, jnp, probes)
    h2d_probe(jax, jnp, 32768, 16, probes)
    device_compute_probe(jax, jnp, probes)
    stream_ab(jax, jnp, int(os.environ.get("GS_AB_EDGES", 2_097_152)),
              ab)
    results["ingress_probes"] = probes
    results["ingress_ab"] = ab


def section_egress_ab(results: dict) -> None:
    """d2h egress-format A/B (ops/delta_egress.py), via the same
    probes as the
    standalone tools/egress_ab.py (exact parity asserted, median-of-3
    with dispersion committed). GS_AUTOTUNE is already pinned off for
    this child, so the egress lever is measured in isolation."""
    import jax

    from tools.egress_ab import driver_ab, reduce_ab

    rows = []
    edges = int(os.environ.get("GS_AB_EDGES", 524_288))
    driver_ab(jax, edges, rows)
    reduce_ab(jax, edges, rows)
    results["egress_ab"] = rows


def section_resident_ab(results: dict) -> None:
    """Resident-tier A/B (ops/resident_engine.py), via the same
    probes as the
    standalone tools/resident_ab.py: the donated super-batch
    megakernel vs chunked scan vs per-window scan dispatch (driver
    and summary engine), exact parity asserted, median-of-3 with
    dispersion. GS_AUTOTUNE is already pinned off for this child, so
    the residency lever is measured in isolation."""
    import jax

    from tools.resident_ab import driver_resident, engine_resident

    rows = []
    edges = int(os.environ.get("GS_AB_EDGES", 524_288))
    driver_resident(jax, edges, rows)
    engine_resident(jax, edges, rows)
    results["resident_ab"] = rows


def section_pallas_ab(results: dict) -> None:
    """Fused-window-megakernel A/B (ops/pallas_window.py), via the
    same probes as the standalone tools/pallas_ab.py: Pallas megakernel vs
    XLA scan-of-gathers through the summary engine AND the triangle
    stream kernel, sha256 window parity against the host twins,
    median-of-3 with dispersion. GS_AUTOTUNE is already pinned off
    for this child, so the kernel lever is measured in isolation. On
    a CPU backend the kernel runs interpreted: the parity half of the
    row is real evidence, the speed half is not."""
    import jax

    from tools.pallas_ab import engine_pallas, stream_pallas

    rows = []
    edges = int(os.environ.get("GS_AB_EDGES", 524_288))
    engine_pallas(jax, edges, rows)
    stream_pallas(jax, edges, rows)
    results["pallas_ab"] = rows


def section_autotune(results: dict) -> None:
    """Online dispatch-tuner evidence (ops/autotune.py): the triangle
    stream's device path static vs tuned-from-cold vs tuned-seeded
    (the second run starts from the first's persisted optimum), with
    the chosen arm and decision timeline committed — so the claim
    'the scheduler converges to a configuration no slower than the
    best static row' is a row, not an assertion."""
    import tempfile
    import time

    import numpy as np

    from bench import make_stream
    from gelly_streaming_tpu.ops import segment as seg_ops
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    eb, vb = 32768, 65536
    # the tuner engages only past one maximal dispatch chunk; give it
    # several rounds' worth of stream (≥4 chunks at the class default)
    edges = int(os.environ.get("GS_AUTOTUNE_EDGES", 8_388_608))
    src, dst = make_stream(edges, vb)

    def timed(fn):
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0

    os.environ["GS_AUTOTUNE"] = "0"
    k0 = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
    seg_ops.warm_stream_buckets(k0)
    base_counts = k0._count_stream_device(src, dst)  # warm run
    _, static_s = timed(lambda: k0._count_stream_device(src, dst))

    os.environ["GS_AUTOTUNE"] = "1"
    prev_cache = os.environ.get("GS_TUNE_CACHE")
    with tempfile.TemporaryDirectory(prefix="gs-tune-") as td:
        os.environ["GS_TUNE_CACHE"] = td  # cold, section-local cache
        try:
            k1 = TriangleWindowKernel(edge_bucket=eb,
                                      vertex_bucket=vb)
            counts1, cold_s = timed(
                lambda: k1._count_stream_device(src, dst))
            # a second kernel = a second process: seeds from the cache
            k2 = TriangleWindowKernel(edge_bucket=eb,
                                      vertex_bucket=vb)
            counts2, seeded_s = timed(
                lambda: k2._count_stream_device(src, dst))
        finally:
            if prev_cache is None:
                os.environ.pop("GS_TUNE_CACHE", None)
            else:
                os.environ["GS_TUNE_CACHE"] = prev_cache
    parity = base_counts == counts1 == counts2
    t2 = getattr(k2, "tuner", None)
    t1 = getattr(k1, "tuner", None)
    summary = t2.summary() if t2 else {}
    row = {
        "engine": "triangle_stream",
        "edge_bucket": eb, "vertex_bucket": vb, "num_edges": edges,
        "static_edges_per_s": round(edges / static_s),
        "tuned_cold_edges_per_s": round(edges / cold_s),
        "tuned_seeded_edges_per_s": round(edges / seeded_s),
        "seeded_vs_static": round(static_s / seeded_s, 3),
        "parity": bool(parity),
        "chosen": summary.get("chosen"),
        "rounds": summary.get("rounds"),
        "promotions": summary.get("promotions"),
        "cold_timeline": (t1.summary().get("timeline", [])
                          if t1 else []),
    }
    results["autotune"] = [row]


def section_telemetry(results: dict) -> None:
    """Flight-recorder evidence (utils/telemetry): the armed recorder
    on the 524K/32768 bench row must (a) change NO result — counts
    asserted identical to the disarmed run — and (b) cost little
    enough to leave on outside A/B sections (the armed/disarmed wall
    ratio is committed, bar <1.02). A driver leg then produces a full
    ledger that tools/trace_report.py round-trips (span table +
    Perfetto export), so the whole toolchain is exercised in the same
    window that commits the rows."""
    import tempfile

    from bench import make_stream
    from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel
    from gelly_streaming_tpu.utils import telemetry

    eb, vb = 32768, 65536
    edges = int(os.environ.get("GS_TELEMETRY_EDGES", 524288))
    src, dst = make_stream(edges, vb)
    prev = {k: os.environ.get(k)
            for k in ("GS_TELEMETRY", "GS_TRACE_DIR")}
    try:
        os.environ["GS_TELEMETRY"] = "0"
        kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
        base = kern.count_stream(src, dst)  # warm + baseline counts
        # 7-rep medians: the row is ~tens of ms on a CPU backend, so a
        # 3-rep median swings past the <2% overhead bar on host noise
        off_s = _timeit(lambda: kern.count_stream(src, dst),
                        reps=7, warmup=2)
        with tempfile.TemporaryDirectory(prefix="gs-trace-") as td:
            os.environ["GS_TELEMETRY"] = "1"
            os.environ["GS_TRACE_DIR"] = td
            telemetry.reset()
            armed = kern.count_stream(src, dst)
            if list(armed) != list(base):
                raise AssertionError(
                    "armed recorder changed the counts — the "
                    "zero-overhead contract is broken")
            on_s = _timeit(lambda: kern.count_stream(src, dst),
                           reps=7, warmup=1)
            # driver leg: the richer span tree + a real ledger the
            # report tool round-trips
            drv = StreamingAnalyticsDriver(
                window_ms=0, edge_bucket=eb, vertex_bucket=1024,
                analytics=("degrees", "cc", "bipartite"))
            drv.run_arrays(src, dst)
            rows = telemetry.summary(top=16)
            telemetry.flush()
            ledger = telemetry.ledger_path()
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "trace_report",
                os.path.join(REPO, "tools", "trace_report.py"))
            trace_report = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(trace_report)
            recs = trace_report.load(ledger)
            perfetto = trace_report.to_perfetto(recs)
            meta = {
                "engine": "triangle_stream+driver",
                "edge_bucket": eb, "num_edges": edges,
                "parity": True,
                "disarmed_edges_per_s": round(edges / off_s),
                "armed_edges_per_s": round(edges / on_s),
                "overhead_ratio": round(on_s / off_s, 3),
                "trace": telemetry.trace_id(),
                "ledger_records": len(recs),
                "perfetto_events": len(perfetto["traceEvents"]),
            }
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        telemetry.reset()
    results["telemetry"] = rows
    results["telemetry_meta"] = meta


def section_metrics(results: dict) -> None:
    """Metrics-plane evidence (utils/metrics): the armed registry on
    the 524K/32768 bench row must (a) change NO result — counts
    asserted identical to the disarmed run — and (b) stay under the
    1.05× armed-overhead bar (the plane records via the telemetry
    sink with GS_TELEMETRY=0: arming metrics never arms the ledger).
    The committed meta is the schema-validated `metrics` section
    (tools/perf_schema.py) the ISSUE-8 acceptance bar reads."""
    from bench import make_stream
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel
    from gelly_streaming_tpu.utils import metrics

    eb, vb = 32768, 65536
    edges = int(os.environ.get("GS_TELEMETRY_EDGES", 524288))
    src, dst = make_stream(edges, vb)
    prev = {k: os.environ.get(k)
            for k in ("GS_METRICS", "GS_TELEMETRY")}
    try:
        os.environ["GS_METRICS"] = "0"
        os.environ["GS_TELEMETRY"] = "0"
        kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
        base = kern.count_stream(src, dst)  # warm + baseline counts
        off_s = _timeit(lambda: kern.count_stream(src, dst),
                        reps=7, warmup=2)
        os.environ["GS_METRICS"] = "1"
        metrics.reset()
        armed = kern.count_stream(src, dst)
        if list(armed) != list(base):
            raise AssertionError(
                "armed metrics registry changed the counts — the "
                "zero-overhead contract is broken")
        on_s = _timeit(lambda: kern.count_stream(src, dst),
                       reps=7, warmup=1)
        snap = metrics.health_snapshot()
        prep = metrics.histogram("gs_stage_seconds", stage="prep")
        meta = {
            "engine": "triangle_stream",
            "edge_bucket": eb, "num_edges": edges,
            "parity": True,
            "disarmed_edges_per_s": round(edges / off_s),
            "armed_edges_per_s": round(edges / on_s),
            "overhead_ratio": round(on_s / off_s, 3),
            "health_status": snap["status"],
            "windows_observed": snap["windows_finalized"],
            "stage_prep_observations": (prep or {}).get("count", 0),
            "compiles": {name: c["count"]
                         for name, c in
                         metrics.compile_report().items()},
        }
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        metrics.reset()
    results["metrics"] = meta


def section_latency(results: dict) -> None:
    """Latency-plane evidence (utils/latency): the armed plane on the
    524K/32768 fused-scan row must (a) change NO summary — asserted
    identical to the disarmed run, (b) stay under the 1.05× armed-
    overhead bar, and (c) RECONCILE — every window's stage waterfall
    sums to its measured ingest→deliver end-to-end within 5% (the
    conservation contract tools/latency_report.py re-checks from
    ledgers). The committed meta is the schema-validated `latency`
    section (tools/perf_schema.py) the acceptance bar reads; its
    e2e_p{50,95,99}_s fields feed bench_compare's lower-is-better
    comparisons."""
    from bench import make_stream
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)
    from gelly_streaming_tpu.utils import latency

    eb, vb = 32768, 65536
    edges = int(os.environ.get("GS_TELEMETRY_EDGES", 524288))
    src, dst = make_stream(edges, vb)
    prev = {k: os.environ.get(k)
            for k in ("GS_LATENCY", "GS_METRICS", "GS_TELEMETRY")}
    try:
        os.environ["GS_LATENCY"] = "0"
        os.environ["GS_METRICS"] = "0"
        os.environ["GS_TELEMETRY"] = "0"
        eng = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)

        def run():
            eng.reset()
            return eng.process(src, dst)

        base = run()  # warm + baseline summaries
        off_s = _timeit(run, reps=5, warmup=1)
        os.environ["GS_LATENCY"] = "1"
        latency.reset()
        armed = run()
        if armed != base:
            raise AssertionError(
                "armed latency plane changed the summaries — the "
                "zero-overhead contract is broken")
        on_s = _timeit(run, reps=5, warmup=1)
        recs = latency.recent()
        if not recs:
            raise AssertionError("armed run recorded no windows")
        worst = 0.0
        for rec in recs:
            ok, gap = latency.reconcile(rec)
            if not ok:
                raise AssertionError(
                    "waterfall does not reconcile: window %s gap "
                    "%.6fs of %.6fs e2e" % (rec["window"], gap,
                                            rec["e2e_s"]))
            if rec["e2e_s"] > 0:
                worst = max(worst, gap / rec["e2e_s"])
        stage_totals = {}
        for rec in recs:
            for name, dur in rec["stages"].items():
                stage_totals[name] = stage_totals.get(name, 0) + dur
        meta = {
            "engine": "fused_scan",
            "edge_bucket": eb, "num_edges": edges,
            "parity": True,
            "disarmed_edges_per_s": round(edges / off_s),
            "armed_edges_per_s": round(edges / on_s),
            "overhead_ratio": round(on_s / off_s, 3),
            "reconciled_windows": len(recs),
            "max_unaccounted_frac": round(worst, 6),
            "stages_total_s": {k: round(v, 6) for k, v in
                               sorted(stage_totals.items())},
            **latency.percentile_fields("e2e"),
        }
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        latency.reset()
    results["latency"] = meta


def section_sanitize(results: dict) -> None:
    """Admission-sanitizer evidence (utils/sanitize): the armed
    sanitizer on the 524K/32768 fused-scan row must (a) change NO
    summary on a clean stream — asserted identical to the disarmed
    run, and (b) stay under the 1.02× armed-overhead bar (the
    sanitizer is a handful of vectorized numpy passes against seconds
    of scan work). The committed meta is the schema-validated
    `sanitize` section (tools/perf_schema.py); its dlq_records /
    quarantines counters feed bench_compare's not-worse checks — a
    clean row must commit both at 0."""
    from bench import make_stream
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)
    from gelly_streaming_tpu.utils import resilience as _resilience
    from gelly_streaming_tpu.utils import sanitize as _sanitize

    eb, vb = 32768, 65536
    edges = int(os.environ.get("GS_TELEMETRY_EDGES", 524288))
    src, dst = make_stream(edges, vb)
    prev = {k: os.environ.get(k)
            for k in ("GS_SANITIZE", "GS_DLQ_DIR")}
    try:
        os.environ["GS_SANITIZE"] = "off"
        os.environ.pop("GS_DLQ_DIR", None)
        eng = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)

        def run():
            eng.reset()
            return eng.process(src, dst)

        base = run()  # warm + baseline summaries
        off_s = _timeit(run, reps=5, warmup=1)
        # mode `on` (structural checks): inert on a clean in-range
        # stream by construction. `strict` is a POLICY change (it
        # rejects self-loops, which a random stream contains), so
        # parity is only a contract for `on`.
        os.environ["GS_SANITIZE"] = "on"
        armed = run()
        if armed != base:
            raise AssertionError(
                "armed sanitizer changed a clean stream's summaries "
                "— the inert-on-clean contract is broken")
        on_s = _timeit(run, reps=5, warmup=1)
        dlq = _sanitize.dlq_status()
        meta = {
            "engine": "fused_scan",
            "edge_bucket": eb, "num_edges": edges,
            "mode": "on",
            "parity": True,
            "disarmed_edges_per_s": round(edges / off_s),
            "armed_edges_per_s": round(edges / on_s),
            "overhead_ratio": round(on_s / off_s, 3),
            "dlq_records": 0 if dlq is None else int(dlq["records"]),
            "quarantines": sum(
                1 for e in _resilience.demotion_events()
                if e.get("to") == "quarantined"),
        }
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    results["sanitize"] = meta


def section_provenance(results: dict) -> None:
    """Provenance-ledger evidence (utils/provenance): arming the
    per-window ledger on the 524K/32768 fused-scan row must (a)
    change NO summary — asserted identical to the disarmed run, (b)
    stay under the 1.02× armed-overhead bar (one canonical-JSON
    record + CRC frame + fsync per 32768-edge window against seconds
    of scan work), and (c) record the TRUTH — every armed window's
    ledger digest is asserted equal to the sha256 of the disarmed
    baseline's summary, so the committed row proves the audit trail
    describes the windows it claims to. Also commits the per-tenant
    attribution evidence rows (utils/metrics.attribute_dispatch): a
    fixed 4-row dispatch split whose tenant seconds reconcile to the
    span total bit-for-bit, pad row attributing zero."""
    import tempfile

    from bench import make_stream
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)
    from gelly_streaming_tpu.utils import metrics as _metrics
    from gelly_streaming_tpu.utils import provenance as _prov

    eb, vb = 32768, 65536
    edges = int(os.environ.get("GS_TELEMETRY_EDGES", 524288))
    src, dst = make_stream(edges, vb)
    prev = {k: os.environ.get(k)
            for k in ("GS_PROVENANCE", "GS_PROVENANCE_DIR",
                      "GS_METRICS", "GS_LATENCY", "GS_TELEMETRY")}
    prov_dir = tempfile.mkdtemp(prefix="gs_prov_perf_")
    try:
        os.environ["GS_PROVENANCE"] = "0"
        os.environ.pop("GS_PROVENANCE_DIR", None)
        os.environ["GS_METRICS"] = "0"
        os.environ["GS_LATENCY"] = "0"
        os.environ["GS_TELEMETRY"] = "0"
        eng = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)

        def run():
            eng.reset()
            return eng.process(src, dst)

        base = run()  # warm + baseline summaries
        off_s = _timeit(run, reps=5, warmup=1)
        os.environ["GS_PROVENANCE"] = "1"
        os.environ["GS_PROVENANCE_DIR"] = prov_dir
        armed = run()
        if armed != base:
            raise AssertionError(
                "armed provenance ledger changed the summaries — the "
                "zero-overhead contract is broken")
        on_s = _timeit(run, reps=5, warmup=1)
        _prov.reset()  # flush + close before auditing the segments
        sc = _prov.scan(prov_dir)
        if sc["torn"] is not None:
            raise AssertionError("armed run left a torn ledger tail "
                                 "in a clean shutdown: %r" % sc["torn"])
        # every rep re-emits windows 0..N-1 (reset() rewinds the
        # cursor): at-least-once duplicates must collapse cleanly
        keyed = {}
        for rec in sc["records"]:
            keyed[(rec["tenant"], rec["window"], rec["tier"])] = rec
        if len(keyed) != len(base):
            raise AssertionError(
                "armed run finalized %d windows but the ledger holds "
                "%d distinct records" % (len(base), len(keyed)))
        for (t, w, _tier), rec in sorted(keyed.items()):
            want = _prov.summary_digest(base[w])
            if rec["digest"] != want:
                raise AssertionError(
                    "ledger digest for window %d (%s != %s) does not "
                    "match the disarmed baseline summary"
                    % (w, rec["digest"], want))
        # attribution evidence (DESIGN.md §24): one armed dispatch
        # split across 4 tenant rows by valid edges — deterministic
        # fixed span so the committed rows are comparable run-to-run
        os.environ["GS_METRICS"] = "1"
        _metrics.reset()
        span_s = 0.25
        shares = _metrics.attribute_dispatch(
            span_s, [("hot", eb), ("warm", eb // 2),
                     ("pad", 0), ("cold", eb // 4)])
        _metrics.reset()
        attr_sum = sum(s for _t, s, _b in shares)
        if attr_sum != span_s:
            raise AssertionError(
                "attributed tenant seconds (%.17g) do not reconcile "
                "to the dispatch span (%.17g)" % (attr_sum, span_s))
        meta = {
            "engine": "fused_scan",
            "edge_bucket": eb, "num_edges": edges,
            "parity": True,
            "disarmed_edges_per_s": round(edges / off_s),
            "armed_edges_per_s": round(edges / on_s),
            "overhead_ratio": round(on_s / off_s, 3),
            "records": len(sc["records"]),
            "windows_verified": len(keyed),
            "segments": int(sc["segments"]),
            "knob_fingerprint": _prov.knob_fingerprint(),
            "attribution": {
                "span_s": span_s,
                "reconciles": True,
                "rows": [{"tenant": t, "device_s": round(s, 9),
                          "share": round(s / span_s, 6)}
                         for t, s, _b in shares],
            },
        }
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        _prov.reset()
    results["provenance"] = meta


def section_cost_model(results: dict) -> None:
    """Program cost observatory evidence (utils/costmodel): capture
    XLA cost_analysis-derived FLOPs/bytes for the three hot stream
    programs — the triangle stream executable, the fused scan, and
    the resident super-batch — on the 524K/32768 row, joined with the
    measured dispatch spans of an armed flight-recorder run whose
    ledger is COMMITTED (logs/costmodel_ledger_cpu.jsonl) so
    tools/explain_perf.py has a real attribution substrate in tier-1.
    Results are asserted digest-identical armed vs disarmed (the
    observatory observes, never participates)."""
    import hashlib
    import shutil
    import tempfile

    from bench import make_stream
    from gelly_streaming_tpu.ops.resident_engine import (
        ResidentSummaryEngine)
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel
    from gelly_streaming_tpu.utils import costmodel, knobs, telemetry

    eb, vb = 32768, 65536
    edges = int(os.environ.get("GS_TELEMETRY_EDGES", 524288))
    src, dst = make_stream(edges, vb)

    def digest(obj):
        return hashlib.sha256(json.dumps(
            obj, sort_keys=True, default=int).encode()).hexdigest()

    prev = {k: os.environ.get(k)
            for k in ("GS_COSTMODEL", "GS_TELEMETRY", "GS_TRACE_DIR")}
    try:
        os.environ["GS_COSTMODEL"] = "0"
        os.environ["GS_TELEMETRY"] = "0"
        kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
        eng = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
        res = ResidentSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
        base = {
            "triangle_stream": list(kern._count_stream_device(src,
                                                              dst)),
            "fused_scan": eng.process(src, dst),
            "resident": res.process(src, dst),
        }
        with tempfile.TemporaryDirectory(prefix="gs-costmodel-") as td:
            os.environ["GS_COSTMODEL"] = "1"
            os.environ["GS_TELEMETRY"] = "1"
            os.environ["GS_TRACE_DIR"] = td
            telemetry.reset()
            costmodel.reset()
            eng.reset()
            res.reset()
            armed = {
                "triangle_stream": list(
                    kern._count_stream_device(src, dst)),
                "fused_scan": eng.process(src, dst),
                "resident": res.process(src, dst),
            }
            for leg in base:
                if digest(base[leg]) != digest(armed[leg]):
                    raise AssertionError(
                        "armed cost observatory changed the %s "
                        "results — the zero-overhead contract is "
                        "broken" % leg)
            rows = costmodel.report()
            trace = telemetry.trace_id()
            telemetry.flush()
            ledger_src = telemetry.ledger_path()
            ledger_rel = "logs/costmodel_ledger_cpu.jsonl"
            os.makedirs(os.path.join(REPO, "logs"), exist_ok=True)
            shutil.copyfile(ledger_src,
                            os.path.join(REPO, ledger_rel))
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        telemetry.reset()
        costmodel.reset()
    results["cost_model"] = {
        "engine": "triangle_stream+fused_scan+resident",
        "edge_bucket": eb,
        "num_edges": edges,
        "parity": True,
        "trace": trace,
        "ledger": ledger_rel,
        "device_kind": costmodel.device_kind(),
        "programs": rows,
    }


def section_gnn(results: dict) -> None:
    """The windowed-GNN cost observatory (ops/gnn_window): the same
    armed/disarmed evidence tools/gnn_ab.py --commit writes — digest
    parity asserted (armed ≡ disarmed ≡ numpy twin, slab AND
    summaries) before the analytic slab-model rows are kept. One
    shared helper so the profiler and the A/B tool can never commit
    divergent shapes for the same section."""
    from tools.gnn_ab import gnn_cost_section

    results["gnn"] = gnn_cost_section()


def section_host_snapshot(results: dict) -> None:
    """Batched snapshot-analytics tiers: the driver's device scan vs
    the C++ carried union-find (native.snapshot_windows), the
    demotion ladder's native rung. Window-by-window parity asserted before timing; rates are whole
    run_arrays batches (intern + snapshot + materialize), reset
    between reps so carried state restarts identically."""
    import numpy as np

    from gelly_streaming_tpu import native
    from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver

    rows = []
    for eb in (8_192, 65_536):
        vb = 4 * eb
        num_w = 16
        src, dst = _stream(num_w * eb, vb)
        kw = dict(window_ms=0, edge_bucket=eb, vertex_bucket=vb,
                  analytics=("degrees", "cc", "bipartite"))
        a = StreamingAnalyticsDriver(snapshot_tier="scan", **kw)
        dev = a.run_arrays(src, dst)
        row = {"edge_bucket": eb, "windows": num_w}
        if native.snapshot_available():
            b = StreamingAnalyticsDriver(snapshot_tier="native", **kw)
            nat = b.run_arrays(src, dst)
            row["parity"] = all(
                np.array_equal(x.degrees, y.degrees)
                and np.array_equal(x.cc_labels, y.cc_labels)
                and np.array_equal(x.bipartite_odd, y.bipartite_odd)
                for x, y in zip(dev, nat))

            def run(drv):
                drv.reset()
                drv.run_arrays(src, dst)

            t_dev = _timeit(lambda: run(a), reps=3, warmup=0)
            t_nat = _timeit(lambda: run(b), reps=3, warmup=0)
            row["scan_edges_per_s"] = round(num_w * eb / t_dev)
            row["native_edges_per_s"] = round(num_w * eb / t_nat)
            row["native_vs_scan"] = round(t_dev / t_nat, 2)
        rows.append(row)
    results["host_snapshot"] = rows


PROBE_TIMEOUT_S = int(os.environ.get("GS_PROBE_TIMEOUT", "420"))

# Candidate stream programs for the compile cap
# (ops/triangles.COMPILE_CAP). Triangle candidates try to RAISE the
# 2^19 default (the chip chunk sweep was still climbing at the cap);
# scan candidates BISECT the fused/snapshot wedge (both programs
# stalled the remote compiler >2400s at sizes the triangle program
# compiles cleanly).
PROBE_CANDIDATES = {
    "compile_probe": [
        ("triangle_stream", 32_768, 32),   # 2^20
        ("triangle_stream", 8_192, 128),   # 2^20
    ],
    "compile_probe_scan": [
        ("fused_scan", 8_192, 16),         # 2^17
        ("fused_scan", 32_768, 16),        # 2^19 (the wedged shape?)
        ("snapshot_scan", 8_192, 16),      # 2^17
        ("snapshot_scan", 8_192, 32),      # 2^18 (the r04 driver shape)
        # structural bisection of the scan wedge (VERDICT r4 weak-7:
        # the caps are a tourniquet, not a diagnosis): the same 2^19
        # slot budget that wedges the 3-analytic snapshot scan, with
        # FEWER carried analytics. A clean deg-only row at a size the
        # full scan wedges pins the predicate to the multi-analytic
        # carry, not scan length; deg+cc in between splits the carry
        # axis. Diagnostic program keys — they never move the real
        # snapshot_scan cap.
        ("snapshot_scan_deg", 32_768, 16),     # 2^19, 1 analytic
        ("snapshot_scan_degcc", 32_768, 16),   # 2^19, 2 analytics
    ],
}


def run_compile_probe_child(program: str, eb: int, wb: int) -> None:
    """Compile (and for the scan programs, run once on a trivial
    stream) ONE candidate shape, overriding the compile cap so the
    shape under test is actually built. Prints a single probe row;
    the orchestrating section's subprocess timeout converts a wedged
    remote compile into an ok=false row instead of a lost stage."""
    import jax

    import numpy as np

    from gelly_streaming_tpu.ops import triangles as tri

    t0 = time.perf_counter()
    tri.COMPILE_CAP = 1 << 30
    if program == "triangle_stream":
        k = tri.TriangleWindowKernel(edge_bucket=eb, vertex_bucket=2 * eb)
        k.MAX_STREAM_WINDOWS = wb
        k._stream_exec(wb)   # AOT compile only
    elif program == "fused_scan":
        from gelly_streaming_tpu.ops.scan_analytics import (
            StreamSummaryEngine)

        eng = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=2 * eb)
        eng.MAX_WINDOWS = wb
        z = np.zeros(wb * eb, np.int32)
        eng.process(z, np.ones(wb * eb, np.int32))
    elif program.startswith("snapshot_scan"):
        from gelly_streaming_tpu.core.driver import (
            StreamingAnalyticsDriver)

        analytics = {"snapshot_scan": ("degrees", "cc", "bipartite"),
                     "snapshot_scan_deg": ("degrees",),
                     "snapshot_scan_degcc": ("degrees", "cc")}.get(program)
        if analytics is None:
            raise SystemExit("unknown probe program %r" % program)
        drv = StreamingAnalyticsDriver(
            window_ms=0, edge_bucket=eb, vertex_bucket=2 * eb,
            analytics=analytics)
        drv._SCAN_CHUNK = wb
        z = np.zeros(wb * eb, np.int32)
        drv.run_arrays(z, np.ones(wb * eb, np.int32))
    else:
        raise SystemExit("unknown probe program %r" % program)
    print(json.dumps({
        "program": program, "eb": eb, "wb": wb, "slots": eb * wb,
        "ok": True, "compile_s": round(time.perf_counter() - t0, 1),
        "backend": jax.default_backend(),
    }), flush=True)


def _section_compile_probe(key: str, results: dict) -> None:
    """One child per candidate shape, launched from the JAX-free parent
    (SPAWNING): each child holds the chip alone."""
    from bench import run_json_child

    backend = results["backend"]
    rows = []
    for program, eb, wb in PROBE_CANDIDATES[key]:
        got = run_json_child(
            [sys.executable, os.path.abspath(__file__), "--probe",
             program, str(eb), str(wb)], PROBE_TIMEOUT_S)
        row = {"program": program, "eb": eb, "wb": wb,
               "slots": eb * wb}
        err = str(got.get("error") or "")
        if got.get("ok") and got.get("backend") == backend:
            row.update(ok=True, compile_s=got.get("compile_s"))
        elif "timeout" in err.lower():
            # a timed-out compile: the shape did not compile in time
            row.update(ok=False, reason=err[:200])
        else:
            # crash mid-probe: inconclusive (ok stays non-boolean)
            row.update(ok=None,
                       reason=(err or "backend %s"
                               % got.get("backend"))[:200])
        rows.append(row)
        print(json.dumps(row), flush=True)
    results[key] = rows


def section_chunk_deep(results: dict) -> None:
    """Chunk sweep ABOVE the pre-probe compile cap. Runs after the
    compile_probe section in the same window: this child re-reads the
    just-flushed PERF.json, and measures windows-per-dispatch depths
    up to the compile cap that the window section's anchor-bounded
    sweep did not reach (r04: the chip sweep was still climbing —
    962K edges/s at 16 — when it hit the 2^19 cap). Rows land under
    `chunk_deep`."""
    from gelly_streaming_tpu.ops import triangles as tri

    try:
        with open(os.path.join(REPO, "PERF.json")) as f:
            perf = json.load(f)
    except (OSError, ValueError):
        perf = {}
    out = []
    for eb in (32_768, 8_192):
        vb = 2 * eb
        cap_c = tri.capped_chunk(eb)
        measured = [
            int(s["windows_per_dispatch"])
            for key in ("window", "chunk_deep")
            for row in perf.get(key, []) or []
            if row.get("edge_bucket") == eb
            for s in row.get("chunk_sweep", []) or []
            if s.get("windows_per_dispatch")]
        hi = max(measured, default=0)
        cands = sorted({c for c in (cap_c, cap_c // 2) if c > hi})
        row = {"edge_bucket": eb, "cap_chunk": cap_c,
               "measured_max": hi, "chunk_sweep": []}
        if not cands:
            row["note"] = "no candidates above measured depth"
            out.append(row)
            continue
        kern = tri.TriangleWindowKernel(edge_bucket=eb, vertex_bucket=vb)
        num_w = max(cands)
        src, dst = _stream(num_w * eb, vb, seed=8)
        row.update(k_bucket=kern.kb, windows=num_w)
        for cs in cands:
            kern.MAX_STREAM_WINDOWS = cs
            kern._count_stream_device(src, dst)  # compile + warm
            t = _timeit(lambda: kern._count_stream_device(src, dst),
                        reps=3, warmup=0)
            row["chunk_sweep"].append({
                "windows_per_dispatch": cs,
                "per_window_ms": round(t / num_w * 1e3, 3),
                "edges_per_s": round(num_w * eb / t),
            })
        out.append(row)
    results["chunk_deep"] = out


def section_compile_probe(results: dict) -> None:
    """Triangle-program cap-raise candidates (one subprocess each)."""
    _section_compile_probe("compile_probe", results)


def section_compile_probe_scan(results: dict) -> None:
    """Fused/snapshot scan wedge bisection (one subprocess each)."""
    _section_compile_probe("compile_probe_scan", results)


# Order = run order: the long compiles (cap-raise probes, scan-class
# programs) run last, so a hang there costs only the sections after
# it; fused/driver run after the probes and re-read the just-flushed
# caps.
SECTIONS = {
    "intersect": section_intersect,
    "ingress_ab": section_ingress_ab,
    "egress_ab": section_egress_ab,
    "autotune": section_autotune,
    "telemetry": section_telemetry,
    "metrics": section_metrics,
    "latency": section_latency,
    "sanitize": section_sanitize,
    "provenance": section_provenance,
    "window": section_window,
    "host_stream": section_host_stream,
    "pipeline_stages": section_pipeline,
    "host_reduce": section_host_reduce,
    "host_snapshot": section_host_snapshot,
    "compile_probe": section_compile_probe,
    "compile_probe_scan": section_compile_probe_scan,
    "chunk_deep": section_chunk_deep,
    "dense": section_dense,
    "roofline": section_roofline,
    "trace": section_trace,
    # resident_ab compiles snapshot-scan-family programs (the donated
    # super-batch form): scan-class compiles, END of the order
    "resident_ab": section_resident_ab,
    # pallas_ab compiles the megakernel-bodied scan programs (Mosaic
    # kernels inside a scan): scan-class compiles, END of the order
    # beside resident_ab
    "pallas_ab": section_pallas_ab,
    # cost_model AOT-compiles the fused-scan/resident programs once
    # more for their analyses: scan-class compiles, END of the order
    "cost_model": section_cost_model,
    # gnn compiles the windowed-GNN scan on the acceptance shape:
    # scan-class compile, END of the order beside cost_model
    "gnn": section_gnn,
    "fused": section_fused,
    "driver": section_driver,
}


# sections that launch one chip child per shape: run from the JAX-free
# parent, never from a section child (which would hold the chip)
SPAWNING = {"compile_probe": section_compile_probe,
            "compile_probe_scan": section_compile_probe_scan,
            "dense": section_dense}


def run_section_child(name: str) -> None:
    """Child mode: run ONE chip section in-process and print its JSON
    line — the FULL results dict, so auxiliary keys a section records
    next to its own (e.g. ingress_ab's `ingress_probes`) reach the
    orchestrator instead of vanishing with the child."""
    if name != "autotune":
        # measurement sections pin the STATIC configuration: the online
        # tuner (ops/autotune) changing dispatch knobs mid-rep would
        # make sweep/A-B rows measure a moving target. The `autotune`
        # section measures the tuner itself and re-enables it.
        os.environ["GS_AUTOTUNE"] = "0"
    import jax

    from gelly_streaming_tpu.utils import resilience

    results = {"backend": jax.default_backend(),
               "device": str(jax.devices()[0])}
    SECTIONS[name](results)
    # tier demotions during the section (core/driver._maybe_demote →
    # utils/resilience registry): a run that silently fell off the
    # device tier mid-measurement must be LABELED — the orchestrator
    # accumulates these into PERF.json's `degradations` section, so a
    # demoted chip run can never masquerade as a device-tier row
    events = resilience.demotion_events()
    if events:
        results["degradations"] = [dict(e, section=name)
                                   for e in events]
    print(json.dumps(results), flush=True)


def run_section_subprocess(name: str, timeout_s: int) -> dict:
    """Run one chip section in its own process group with a hard
    timeout: a hung compile or dispatch then costs ONE section, not
    the whole profile."""
    from bench import run_json_child

    return run_json_child(
        [sys.executable, os.path.abspath(__file__), "--section", name],
        timeout_s)


def main():
    if len(sys.argv) >= 3 and sys.argv[1] == "--section":
        run_section_child(sys.argv[2])
        return
    if len(sys.argv) >= 5 and sys.argv[1] == "--probe":
        run_compile_probe_child(sys.argv[2], int(sys.argv[3]),
                                int(sys.argv[4]))
        return
    if len(sys.argv) >= 4 and sys.argv[1] == "--dense":
        run_dense_child(int(sys.argv[2]), sys.argv[3])
        return

    args = sys.argv[1:]
    cpu = "--cpu" in args
    args = [a for a in args if a != "--cpu"]
    unknown = [a for a in args if a not in SECTIONS and a != "sharded"]
    if unknown:
        sys.exit("unknown section(s) %s; valid: %s"
                 % (unknown, list(SECTIONS) + ["sharded"]))
    want = [s for s in list(SECTIONS) + ["sharded"]
            if not args or s in args]
    timeout_s = int(os.environ.get("GS_PROFILE_SECTION_TIMEOUT", "2400"))
    perf_path = os.path.join(REPO, "PERF.json")
    results = {}
    ok_sections = []
    wrote = [None]

    try:
        with open(perf_path) as f:
            prior = json.load(f)
    except (OSError, ValueError):
        prior = None

    def flush():
        # a profiling RUN must never degrade the committed record:
        #  - no successful section yet -> write PERF.json.partial only;
        #  - same backend as the existing file -> merge this run's
        #    successful sections over it (a subset or interrupted run
        #    keeps the other sections' committed measurements);
        #  - different backend -> replace only when THIS run is the
        #    chip ('tpu'); a CPU-fallback run never overwrites a
        #    TPU-labeled file.
        backend = results.get("backend")
        # A failed section NEVER lands under its section key (readers
        # iterate section rows and would crash/mislead on an
        # {"error": ...} stub) — it is recorded under <name>_error,
        # keeping any prior measurement. A same-backend prior file
        # seeds the merge; any other prior is ignored here (the usable
        # check below decides
        # whether this run may replace it at all).
        merged = (dict(prior) if prior is not None
                  and prior.get("backend") == backend else {})
        for k, v in results.items():
            if isinstance(v, dict) and "error" in v:
                merged[k + "_error"] = v
            else:
                merged[k] = v
                merged.pop(k + "_error", None)
        replacing_other_backend = (
            prior is not None and prior.get("backend") != backend)
        usable = bool(ok_sections) and not (
            replacing_other_backend and prior.get("backend") == "tpu"
            and backend != "tpu")
        path = perf_path if usable else perf_path + ".partial"
        with open(path, "w") as f:
            json.dump(merged, f, indent=2)
        if ok_sections and backend:
            # per-backend archive: this backend's rows survive the
            # OTHER backend's profile run taking over PERF.json.
            # Seeded from the EXISTING archive so a subset run (e.g.
            # host_stream only) keeps the other archived sections.
            arch_path = os.path.join(REPO, "PERF_%s.json" % backend)
            try:
                with open(arch_path) as f:
                    arch = json.load(f)
                if arch.get("backend") != backend:
                    arch = {}
            except (OSError, ValueError):
                arch = {}
            arch.update(merged)
            # a section that succeeded THIS run clears its stale
            # failure stub from the archive too — the PERF.json merge
            # above already does; without this the archive keeps a
            # dead <name>_error beside the good rows forever
            for k in list(merged):
                if not k.endswith("_error"):
                    arch.pop(k + "_error", None)
            with open(arch_path, "w") as f:
                json.dump(arch, f, indent=2)
        wrote[0] = path

    chip_sections = [s for s in want if s != "sharded"]
    # the parent never imports JAX: each section runs in its own child
    # (and the compile-probe/dense sections, which launch one child per
    # shape, run their loop here), so one process at a time holds the
    # chip. --cpu is an explicitly labelled CPU rehearsal; otherwise a
    # section whose child found no TPU records an error row.
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if chip_sections:
        results["backend"] = "cpu" if cpu else "tpu"
        flush()
    elif prior is not None:
        # sharded-only run: keep the existing file's chip identity
        results["backend"] = prior.get("backend")
        results["device"] = prior.get("device")
    for name in chip_sections:
        if name in SPAWNING:
            got = {"backend": results["backend"]}
            SPAWNING[name](got)
        else:
            got = run_section_subprocess(name, timeout_s)
        # Trust the backend the CHILD measured on: a section that ran
        # anywhere but the requested backend is an error row, never a
        # CPU timing under a chip's name.
        child_backend = got.get("backend")
        if "error" not in got and child_backend != results["backend"]:
            got = {"error": "backend mismatch: wanted %s, section ran "
                            "on %s" % (results["backend"], child_backend)}
        if got.get("device"):
            results.setdefault("device", got["device"])
        # a child that demoted tiers mid-measurement reports it even
        # when its section row also landed: accumulate across sections
        # (the `degradations` key in PERF.json is the honesty label —
        # update_perf_md/consumers can flag the affected rows)
        if got.get("degradations"):
            results.setdefault("degradations", []).extend(
                got["degradations"])
        results[name] = got.get(name, got if "error" in got else
                                {"error": "missing section key"})
        if "error" not in results[name]:
            ok_sections.append(name)
            # auxiliary keys a section recorded beside its own (e.g.
            # ingress_ab's `ingress_probes`) ride along into PERF.json
            for k, v in got.items():
                if k not in ("backend", "device", name, "degradations") \
                        and k not in SECTIONS:
                    results[k] = v
        print(json.dumps({name: results[name]}), flush=True)
        flush()
    if "sharded" in want:
        results["sharded"] = section_sharded(REPO)
        if "error" not in results["sharded"]:
            ok_sections.append("sharded")
            # hoist the table-mode comparison to the top level
            if "sharded_table" in results["sharded"]:
                results["sharded_table"] = results["sharded"].pop(
                    "sharded_table")
        print(json.dumps({"sharded": results["sharded"]}), flush=True)
        flush()
    print("wrote %s" % wrote[0], file=sys.stderr)


if __name__ == "__main__":
    main()

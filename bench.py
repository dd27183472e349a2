#!/usr/bin/env python
"""North-star benchmark: edges/sec on exact Window Triangle Count.

Streams a synthetic power-law edge stream (a stand-in for the Twitter
slice named in BASELINE.json — zero-egress environment, no external
datasets) through tumbling count-windows and measures end-to-end
throughput of the streaming device pipeline
(ops/triangles.TriangleWindowKernel: ONE compiled program for all
windows; the host ships only raw COO arrays).

Baseline (BASELINE.md: "run the Flink reference or a faithful CPU
port"): faithful CPU ports of the reference's candidate-pair pipeline
(GenerateCandidateEdges + CountTriangles, WindowTriangles.java:83-140)
on the same stream. The PRIMARY baseline is a numpy-vectorized port
(same O(d²) candidate algorithm, compiled inner loops — a fair proxy
for the JVM comparator) timed at the device's own window size; the
pure-Python dict/set port is kept as a secondary row (it measures
CPython interpreter overhead as much as the algorithm).

Exact-count parity between all paths is asserted on the shared sample
windows (and the leading device-size windows) before anything is
reported.

Prints one JSON line per completed scale (smallest first), so an
external timeout still leaves the best completed number; the LAST line
is the headline result:
  {"metric": ..., "value": N, "unit": "edges/s", "vs_baseline": N}
"""

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# Exceptions that mean "the device ran out of room at this scale" — the
# only ones worth stopping the scale ladder for. Matched narrowly (the
# XLA status code / canonical OOM phrasing) so arbitrary compiler bugs
# whose text happens to mention allocation are NOT masked as capacity.
def _is_resource_error(e: Exception) -> bool:
    s = str(e)
    return "RESOURCE_EXHAUSTED" in s or "out of memory" in s.lower()


def run_with_hard_timeout(argv, timeout_s: int, env=None):
    """Run argv in its own process GROUP with a hard timeout; returns
    (rc, stdout, stderr) with rc=None on timeout. Output goes to temp
    FILES, not pipes, and the child gets its own session: a helper the
    child forks that inherits the descriptors cannot keep a post-kill
    communicate() stuck, and killpg reaps it. The child runner of
    tools/profile_kernels.py sections and tools/scale_run.py legs,
    whose parents never import JAX (one process holds the chip)."""
    import signal
    import tempfile

    with tempfile.TemporaryFile("w+") as out, \
            tempfile.TemporaryFile("w+") as err:
        p = subprocess.Popen(argv, stdout=out, stderr=err, text=True,
                             env=env, start_new_session=True)
        try:
            rc = p.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            rc = None
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except OSError:
                pass
            p.wait()
        out.seek(0)
        err.seek(0)
        return rc, out.read(), err.read()


def run_json_child(argv, timeout_s: int, env=None, require_key=None):
    """run_with_hard_timeout + parse the LAST JSON object line of the
    child's stdout (optionally requiring a key, to skip progress
    lines). Returns {'error': ...} on timeout/nonzero-rc/no-JSON — the
    shared child contract of tools/profile_kernels.py sections and
    tools/scale_run.py legs."""
    rc, stdout, stderr = run_with_hard_timeout(argv, timeout_s, env=env)
    if rc is None:
        return {"error": "timeout after %ds" % timeout_s}
    if rc != 0:
        return {"error": "rc=%d: %s" % (rc, stderr.strip()[-800:])}
    for line in reversed(stdout.strip().splitlines()):
        try:
            got = json.loads(line)
        except ValueError:
            continue
        if isinstance(got, dict) and (require_key is None
                                      or got.get(require_key)):
            return got
    return {"error": "no JSON line in child output"}


def _device() -> dict:
    """The device every row ran on, as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def make_stream(num_edges: int, num_vertices: int, seed: int = 7):
    """Power-law-ish edge stream: endpoints drawn from a Zipf-like
    distribution over the vertex space (heavy hitters like a social
    stream), timestamps strictly increasing."""
    rng = np.random.default_rng(seed)
    # exponent ~1.1 keeps candidate counts representative but bounded
    weights = 1.0 / np.arange(1, num_vertices + 1) ** 1.1
    weights /= weights.sum()
    src = rng.choice(num_vertices, size=num_edges, p=weights)
    dst = rng.choice(num_vertices, size=num_edges, p=weights)
    # no self-loops (match real graph datasets): redraw collisions
    loops = src == dst
    while loops.any():
        dst[loops] = rng.choice(num_vertices, size=int(loops.sum()), p=weights)
        loops = src == dst
    # remap so hot vertices are scattered over the id space
    perm = rng.permutation(num_vertices)
    return perm[src], perm[dst]


def device_window_counts(kernel, src, dst, window_edges):
    """Streaming device path: the whole stream's windows batched into
    lax.map dispatches (kernel.count_stream) — one h2d per chunk, one
    d2h of the counts, zero per-window round-trips."""
    assert window_edges == kernel.eb, "stream windows must match the bucket"
    return kernel.count_stream(src, dst)


def warmup_stream_shapes(kernel, num_edges):
    """Compile the (at most two) chunk shapes the timed run will use:
    a full MAX_STREAM_WINDOWS chunk and the ragged final chunk."""
    num_w = -(-num_edges // kernel.eb)
    first = min(num_w, kernel.MAX_STREAM_WINDOWS)
    zeros = np.zeros(first * kernel.eb, np.int32)
    kernel.count_stream(zeros, zeros)
    tail = num_w % kernel.MAX_STREAM_WINDOWS
    if tail and tail != first:
        zeros = zeros[: tail * kernel.eb]
        kernel.count_stream(zeros, zeros)


def cpu_reference_window_counts(src, dst, window_edges):
    """Faithful CPU port of the reference pipeline: per-vertex ALL-window
    neighborhoods → candidate pairs (ids > vertex) → per-pair groups →
    count candidates where a real edge exists. On self-looped input its
    self-pair candidates mirror the reference's HashSet-order-dependent
    emission (see _numpy_window_count), so parity across ports is
    asserted only on loop-free streams — which every bench stream is."""
    counts = []
    for start in range(0, len(src), window_edges):
        s = src[start:start + window_edges]
        d = dst[start:start + window_edges]
        neighborhoods = {}
        for u, v in zip(s.tolist(), d.tolist()):
            neighborhoods.setdefault(u, []).append(v)
            neighborhoods.setdefault(v, []).append(u)
        real = set()
        candidates = {}
        for vertex, nbrs in neighborhoods.items():
            distinct = list(dict.fromkeys(nbrs))
            for n in nbrs:
                real.add((vertex, n))
            for i in range(len(distinct) - 1):
                if distinct[i] <= vertex:
                    continue
                for j in range(i, len(distinct)):
                    if distinct[j] > vertex:
                        pair = (distinct[i], distinct[j])
                        candidates[pair] = candidates.get(pair, 0) + 1
        total = sum(c for pair, c in candidates.items() if pair in real)
        counts.append(total)
    return counts


def _numpy_window_count(s: np.ndarray, d: np.ndarray) -> int:
    """One window of the faithful candidate-pair algorithm
    (WindowTriangles.java:83-140), numpy-vectorized: same O(d²)
    candidate generation per vertex, but with compiled inner loops so
    the baseline is the ALGORITHM's cost, not CPython interpreter
    overhead. Semantics match cpu_reference_window_counts on
    SELF-LOOP-FREE streams (asserted at bench time; every bench stream
    is loop-free by construction): for each center vertex, every
    unordered pair of distinct neighbors both > center is a candidate,
    counted once per center; candidates that are real edges sum to the
    window's triangle count. Self-loops are stripped here — the
    reference's own i==j self-pair emission depends on Java HashSet
    iteration order (GenerateCandidateEdges skips the LAST-iterated
    neighbor's self-pair), so its looped-input count is
    nondeterministic and parity there is undefined; the device kernels
    strip self-loops for the same reason."""
    keep_e = s != d
    s, d = s[keep_e], d[keep_e]
    if len(s) == 0:
        return 0
    V = int(max(s.max(), d.max())) + 1
    center = np.concatenate([s, d]).astype(np.int64)
    nbr = np.concatenate([d, s]).astype(np.int64)
    # distinct (center, neighbor) incidences, both directions = the
    # port's `real` set and its deduped neighborhoods in one array
    enc_u = np.unique(center * V + nbr)
    c = enc_u // V
    n = enc_u % V
    keep = n > c
    ck, nk = c[keep], n[keep]
    if len(ck) == 0:
        return 0
    # per-center segments (ck is sorted because enc_u is)
    change = np.flatnonzero(np.diff(ck)) + 1
    offs = np.concatenate(([0], change, [len(ck)]))
    k = np.diff(offs)
    pairs_per_seg = k * (k - 1) // 2
    cum = np.cumsum(pairs_per_seg)
    total = 0
    # batch segments so the pair arrays stay bounded in memory; hub
    # vertices at 32K-edge windows generate tens of millions of pairs
    MAX_PAIRS = 8_000_000
    start_seg = 0
    while start_seg < len(k):
        base = int(cum[start_seg - 1]) if start_seg else 0
        end_seg = int(np.searchsorted(cum, base + MAX_PAIRS,
                                      side="right"))
        end_seg = min(max(end_seg, start_seg + 1), len(k))
        kb = k[start_seg:end_seg]
        nb = nk[offs[start_seg]:offs[end_seg]]
        kb_offs = np.concatenate(([0], np.cumsum(kb)))
        # position of each element within its segment; element at
        # position p is the SECOND member of p pairs (one per earlier
        # element), which unrolls every i<j pair without a Python loop
        pos = np.arange(len(nb)) - np.repeat(kb_offs[:-1], kb)
        P = int(pos.sum())
        if P:
            j_idx = np.repeat(np.arange(len(nb)), pos)
            blk = np.concatenate(([0], np.cumsum(pos)[:-1]))
            i_off = np.arange(P) - np.repeat(blk, pos)
            i_idx = np.repeat(kb_offs[:-1], kb)[j_idx] + i_off
            pe = nb[i_idx] * V + nb[j_idx]
            loc = np.searchsorted(enc_u, pe)
            loc[loc >= len(enc_u)] = len(enc_u) - 1
            total += int((enc_u[loc] == pe).sum())
        start_seg = end_seg
    return total


def cpu_reference_window_counts_numpy(src, dst, window_edges):
    """Numpy-vectorized faithful port (primary CPU baseline; the
    pure-Python dict/set port above is kept as the secondary row —
    VERDICT r2 weak-2: an interpreted baseline softens the ≥10× bar
    because the real comparator is Flink's JVM, not CPython)."""
    return [
        _numpy_window_count(np.asarray(src[s:s + window_edges]),
                            np.asarray(dst[s:s + window_edges]))
        for s in range(0, len(src), window_edges)
    ]


def run_at_scale(scale: float, metric_suffix: str = "") -> None:
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    num_edges = int(2_097_152 * scale)
    # The window is CAPPED at 32768 edges: scaling up grows the STREAM
    # (more windows through the same compiled program — the north-star
    # metric is edges/sec over a 10M-edge stream slice), not the window.
    # The per-edge triangle work grows superlinearly with window
    # length, so bigger windows would only make the reported rate
    # conservative, not comparable.
    window_edges = min(int(131_072 * scale), 32_768)
    num_vertices = min(int(262_144 * scale), 65_536)
    src, dst = make_stream(num_edges, num_vertices)

    kernel = TriangleWindowKernel(
        edge_bucket=window_edges, vertex_bucket=num_vertices)
    # count_stream slices windows of exactly the kernel's edge bucket,
    # so align the stream's window length to it (scales whose raw
    # window_edges is not a power of two round up)
    window_edges = kernel.eb

    # correctness cross-check + CPU baselines on shared sample windows
    # (small enough for the O(d²) interpreted pipeline to finish; four
    # windows — the ports' per-window time swings with host load and
    # sits in the denominator of the ratio, so averaging steadies it)
    sample_window = min(window_edges, 8_192)
    sample = 4 * sample_window
    reps = int(os.environ.get("GS_BENCH_REPS", "3"))
    t0 = time.perf_counter()
    ref_counts = cpu_reference_window_counts(
        src[:sample], dst[:sample], sample_window)
    cpu_py_rate = sample / (time.perf_counter() - t0)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np_counts = cpu_reference_window_counts_numpy(
            src[:sample], dst[:sample], sample_window)
        ts.append(time.perf_counter() - t0)
    cpu_np_sample_rate = sample / float(np.median(ts))
    assert np_counts == ref_counts, (np_counts, ref_counts)
    # parity of BOTH device paths: the per-window escalating kernel and
    # the batched lax.map streaming path the timed run uses
    dev_counts = [
        kernel.count(src[s:s + sample_window], dst[s:s + sample_window])
        for s in range(0, sample, sample_window)
    ]
    assert dev_counts == ref_counts, (dev_counts, ref_counts)
    sample_kernel = TriangleWindowKernel(
        edge_bucket=sample_window, vertex_bucket=num_vertices)
    stream_counts = sample_kernel.count_stream(src[:sample], dst[:sample])
    assert stream_counts == ref_counts, (stream_counts, ref_counts)

    # PRIMARY baseline: the numpy-vectorized faithful port timed at the
    # DEVICE's window size, so the headline ratio compares like against
    # like (the old sample-window/device-window asymmetry was argued
    # conservative but never measured). Median of 3 on BOTH sides of
    # the ratio: single samples on this shared host swing 30-45% with
    # load, and the headline must not ride one lucky/unlucky draw.
    if window_edges == sample_window:
        # the sample windows ARE device-size windows: reuse that
        # measurement instead of timing the identical work twice
        nfull, full_counts, cpu_rate = 4, np_counts, cpu_np_sample_rate
    else:
        nfull = max(1, min(4, num_edges // window_edges))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            full_counts = cpu_reference_window_counts_numpy(
                src[:nfull * window_edges], dst[:nfull * window_edges],
                window_edges)
            ts.append(time.perf_counter() - t0)
        cpu_rate = nfull * window_edges / float(np.median(ts))

    # warmup at the exact chunk shapes of the timed run (compile here)
    warmup_stream_shapes(kernel, num_edges)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        timed_counts = device_window_counts(kernel, src, dst,
                                            window_edges)
        ts.append(time.perf_counter() - t0)
    rate = num_edges / float(np.median(ts))
    # full-window-size parity: the timed device counts vs the primary
    # baseline's counts on the shared leading windows
    assert list(timed_counts[:nfull]) == full_counts, (
        list(timed_counts[:nfull]), full_counts)

    # the same device path with the ingress pipeline FORCED
    # SYNCHRONOUS (single-threaded prep, no worker pool): the A/B the
    # pipelined-host-ingress work is accountable to, with exact
    # window-by-window parity asserted — identical counts are part of
    # the pipeline's contract, not a sampling check
    from gelly_streaming_tpu.ops import ingress_pipeline

    ts = []
    for _ in range(reps):
        with ingress_pipeline.forced_sync():
            t0 = time.perf_counter()
            sync_counts = device_window_counts(kernel, src, dst,
                                               window_edges)
            ts.append(time.perf_counter() - t0)
    sync_rate = num_edges / float(np.median(ts))
    assert list(sync_counts) == list(timed_counts), \
        "pipelined path diverged from sync host-prep path"

    row = {
        "metric": "edges/sec/chip, exact window triangle count "
                  "(power-law stream, %d-edge windows)%s"
                  % (window_edges, metric_suffix),
        "value": round(rate),
        "unit": "edges/s",
        "device": _device(),
        "tier": "device",
        "vs_baseline": round(rate / cpu_rate, 2),
        # the measured baselines, persisted (BASELINE.md milestone:
        # faithful CPU ports of WindowTriangles.java:83-140 on the same
        # stream; the reference publishes no numbers of its own).
        # PRIMARY: numpy-vectorized port at the device's window size.
        "baseline_cpu_edges_per_s": round(cpu_rate),
        # secondary rows: the same vectorized port on the sample
        # windows, and the pure-Python dict/set port (interpreter-bound;
        # kept for continuity with rounds 1-2)
        "baseline_cpu_numpy_sample_edges_per_s":
            round(cpu_np_sample_rate),
        "baseline_cpu_python_edges_per_s": round(cpu_py_rate),
        "vs_python_baseline": round(rate / cpu_py_rate, 2),
        # the ingress-pipeline A/B: the device path with parallel
        # window prep + overlapped h2d/dispatch (the headline `value`)
        # vs the same path forced single-threaded-synchronous,
        # identical counts asserted window-by-window above
        "sync_prep_edges_per_s": round(sync_rate),
        "pipeline_speedup": round(rate / sync_rate, 2),
        "pipeline_workers": ingress_pipeline.worker_count(),
        "num_edges": num_edges,
    }
    # chosen-knob provenance: every row says what dispatch
    # configuration it actually ran — the static gates, and (when the
    # online tuner was live on the device path) the tuner's chosen arm
    # plus its decision timeline tail (ops/autotune.py)
    from gelly_streaming_tpu.ops import autotune as _autotune

    row["knobs"] = {"k_bucket": kernel.kb,
                    "windows_per_dispatch": kernel.MAX_STREAM_WINDOWS,
                    "ingress": kernel.ingress}
    tuner = getattr(kernel, "tuner", None)
    if tuner is not None:
        ts = tuner.summary()
        row["autotune"] = {
            "enabled": True,
            "chosen": ts["chosen"],
            "rounds": ts["rounds"],
            "promotions": ts["promotions"],
            "edges_per_s_ema": ts["edges_per_s_ema"],
            "timeline": ts["timeline"][-8:],
        }
    else:
        row["autotune"] = {"enabled": _autotune.enabled()}
    # flight-recorder provenance (utils/telemetry): the A/B
    # measurement sections above run DISARMED by default
    # (GS_TELEMETRY=0 — the zero-overhead contract keeps the headline
    # honest); an operator who arms it gets the armed row labeled,
    # with its trace ID and the top span aggregates riding along
    from gelly_streaming_tpu.utils import telemetry as _telemetry

    # the run trace ID rides EVERY row (armed or not — the recorder
    # mints one per process regardless), so a bench_compare regression
    # against this row correlates straight to its ledger
    # (tools/explain_perf.py --regression)
    row["trace"] = _telemetry.trace_id()
    if _telemetry.enabled():
        row["telemetry"] = {"armed": True,
                            "trace": _telemetry.trace_id(),
                            "spans": _telemetry.summary(top=8)}
    else:
        row["telemetry"] = {"armed": False}
    print(json.dumps(row), flush=True)


def run_reduce_leg(metric_suffix: str = "") -> None:
    """BASELINE.json config #2: `reduceOnEdges` sum-of-weights over
    tumbling count windows (reference hot loop
    GraphWindowStream.java:101-121), on the columnar engine
    (ops/windowed_reduce.py). Baseline: a vectorized faithful numpy
    port of the per-window fold (np.bincount(weights) groupby-sum —
    the stiffest single-core form of the reference's per-record
    accumulate), parity-asserted before timing."""
    from gelly_streaming_tpu.ops.windowed_reduce import WindowedEdgeReduce

    num_edges, window_edges = 2_097_152, 8_192
    num_vertices = 1 << 14
    src, dst = make_stream(num_edges, num_vertices)
    val = (1 + (src + 3 * dst) % 97).astype(np.int32)
    reps = int(os.environ.get("GS_BENCH_REPS", "3"))

    def np_port():
        out = []
        for lo in range(0, num_edges, window_edges):
            out.append(np.bincount(
                src[lo:lo + window_edges], val[lo:lo + window_edges],
                minlength=num_vertices).astype(np.int64))
        return out

    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        base = np_port()
        ts.append(time.perf_counter() - t0)
    cpu_rate = num_edges / float(np.median(ts))

    def np_port_with_counts():
        """The same port ALSO producing per-vertex counts — the part
        of the engine's contract (absence detection for non-sum
        monoids, delta consumers) the values-only port omits. Reported
        as a secondary baseline so the primary stays the strictest
        one."""
        out = []
        for lo in range(0, num_edges, window_edges):
            s = src[lo:lo + window_edges]
            out.append((np.bincount(s, val[lo:lo + window_edges],
                                    minlength=num_vertices),
                        np.bincount(s, minlength=num_vertices)))
        return out

    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np_port_with_counts()
        ts.append(time.perf_counter() - t0)
    cpu_rate_counts = num_edges / float(np.median(ts))

    eng = WindowedEdgeReduce(vertex_bucket=num_vertices,
                             edge_bucket=window_edges, name="sum",
                             direction="out")
    got = eng.process_stream(src, dst, val)   # warm + parity material
    assert len(got) == len(base)
    for (cells, _cnt), want in zip(got, base):
        np.testing.assert_array_equal(
            cells[:num_vertices].astype(np.int64), want)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        eng.process_stream(src, dst, val)
        ts.append(time.perf_counter() - t0)
    rate = num_edges / float(np.median(ts))
    from gelly_streaming_tpu.utils import telemetry as _telemetry

    print(json.dumps({
        "metric": "edges/sec/chip, windowed reduceOnEdges "
                  "sum-of-weights (power-law stream, %d-edge "
                  "windows)%s" % (window_edges, metric_suffix),
        "value": round(rate),
        "unit": "edges/s",
        "device": _device(),
        "tier": "device",
        "vs_baseline": round(rate / cpu_rate, 2),
        "baseline_cpu_edges_per_s": round(cpu_rate),
        # secondary: the port made contract-equal (values AND counts)
        "baseline_cpu_with_counts_edges_per_s": round(cpu_rate_counts),
        "vs_baseline_with_counts": round(rate / cpu_rate_counts, 2),
        "num_edges": num_edges,
        # trace-ID correlation (see the triangles leg's row)
        "trace": _telemetry.trace_id(),
    }), flush=True)


def run_cohort_leg(metric_suffix: str = "") -> None:
    """Multi-tenant cohort serving scenario (core/tenancy.py): N
    small tenant streams fed window by window, the cohort's ONE
    vmapped dispatch per round vs N sequential single-tenant engines
    — the 'thousands of small streams' serving shape the ROADMAP
    north star names. Per-tenant sha256 parity asserted before any
    speedup is claimed (tools/tenancy_ab.py owns the deeper
    median-of-3 committed evidence; this leg keeps the regression
    sentry's eye on it every bench run)."""
    from tools.tenancy_ab import (cohort_run, digest_summaries,
                                  make_tenant_streams,
                                  sequential_oracle)

    tenants, windows, eb, vb = 8, 8, 512, 1024
    streams = make_tenant_streams(tenants, windows, eb, vb)
    total_edges = sum(len(s) for s, _d in streams.values())
    want = sequential_oracle(streams, eb, vb, True)
    got = cohort_run(streams, eb, vb, True)
    for tid in streams:
        assert digest_summaries(got[tid]) == digest_summaries(
            want[tid]), "cohort diverged from the sequential " \
            "oracle for tenant %s" % tid
    reps = int(os.environ.get("GS_BENCH_REPS", "3"))
    seq_ts, coh_ts = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        sequential_oracle(streams, eb, vb, True)
        seq_ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        cohort_run(streams, eb, vb, True)
        coh_ts.append(time.perf_counter() - t0)
    seq_s = float(np.median(seq_ts))
    coh_s = float(np.median(coh_ts))

    from gelly_streaming_tpu.ops import autotune as _autotune
    from gelly_streaming_tpu.utils import knobs as _knobs
    from gelly_streaming_tpu.utils import latency as _latency
    from gelly_streaming_tpu.utils import resilience as _resilience
    from gelly_streaming_tpu.utils import sanitize as _sanitize
    from gelly_streaming_tpu.utils import telemetry as _telemetry

    # robustness counters for the regression sentry: rejected-record
    # depth of the (possibly disarmed → 0) dead-letter journal, and
    # bulkhead quarantines recorded this process
    _dlq = _sanitize.dlq_status()
    _dlq_records = 0 if _dlq is None else int(_dlq["records"])
    _quarantines = sum(1 for e in _resilience.demotion_events()
                       if e.get("to") == "quarantined")

    # latency identities of the serving shape: one extra ARMED rep
    # (outside the timed medians — the ≤1.05x overhead must not skew
    # the speedup measurement) emits serve_e2e_p{50,95,99}_s, the
    # fields bench_compare checks lower-is-better; armed summaries
    # are asserted digest-identical first (the observe-only contract)
    lat_prev = os.environ.get("GS_LATENCY")
    os.environ["GS_LATENCY"] = "1"
    _latency.reset()
    try:
        armed = cohort_run(streams, eb, vb, True)
        for tid in streams:
            assert digest_summaries(armed[tid]) == digest_summaries(
                want[tid]), "ARMED latency plane changed tenant %s's " \
                "summaries — the zero-overhead contract is broken" % tid
        lat_fields = _latency.percentile_fields("serve_e2e")
    finally:
        if lat_prev is None:
            os.environ.pop("GS_LATENCY", None)
        else:
            os.environ["GS_LATENCY"] = lat_prev
        _latency.reset()

    print(json.dumps({
        "metric": "edges/sec/chip, multi-tenant cohort serving "
                  "(%d tenants, %d-edge windows, one vmapped "
                  "dispatch per round)%s"
                  % (tenants, eb, metric_suffix),
        "value": round(total_edges / coh_s),
        "unit": "edges/s",
        "device": _device(),
        "tenants": tenants,
        "num_edges": total_edges,
        "tenant_edges_per_s": round(total_edges / coh_s),
        "sequential_edges_per_s": round(total_edges / seq_s),
        "cohort_speedup": round(seq_s / coh_s, 2),
        # ingest→deliver latency identities (utils/latency, armed
        # parity rep above): lower-is-better in bench_compare
        **lat_fields,
        # robustness counters (utils/sanitize + the tenancy
        # bulkhead): a clean serving run rejects nothing and
        # quarantines no one — bench_compare flags ANY non-zero turn
        # of either (lower-is-better, zero-baseline strict)
        "dlq_records": _dlq_records,
        "quarantines": _quarantines,
        # chosen-knob provenance, like every bench row: what dispatch
        # configuration the cohort actually ran
        "knobs": {"eb": eb, "vb": vb,
                  "tenants_per_dispatch": _knobs.get_int(
                      "GS_TENANT_TPD") or "auto",
                  "queue_windows": _knobs.get_int(
                      "GS_TENANT_QUEUE_WINDOWS"),
                  "admission": _knobs.get_str("GS_TENANT_ADMISSION")},
        "autotune": {"enabled": _autotune.enabled()},
        # trace-ID correlation (see the triangles leg's row)
        "trace": _telemetry.trace_id(),
    }), flush=True)


def run_gnn_leg(metric_suffix: str = "") -> None:
    """Windowed-GNN message-passing scenario (ops/gnn_window): the
    fused per-window GNN round (segment-sum aggregation + the dense
    MXU update) over a power-law stream. Parity vs the numpy lattice
    twin is asserted — summary stream AND final feature slab — before
    any rate is reported; the metric unit is edge-features/s (edges ×
    feature_dim per second), the axis the dense update actually
    scales on. tools/gnn_ab.py owns the deeper committed evidence;
    this leg keeps the regression sentry's eye on the workload every
    bench run."""
    from gelly_streaming_tpu.ops import gnn_window as gw
    from gelly_streaming_tpu.utils import knobs as _knobs
    from gelly_streaming_tpu.utils import telemetry as _telemetry
    from tools.gnn_ab import (digest_slab, digest_summaries,
                              run_engine)

    eb, vb, F, windows = 512, 1024, 16, 16
    n = windows * eb - eb // 3  # ragged tail: the partial-window path
    src, dst = make_stream(n, vb, seed=7)
    src, dst = src.astype(np.int32), dst.astype(np.int32)

    got, slab = run_engine(gw.GnnSummaryEngine, eb, vb, F, src, dst)
    want, wslab = run_engine(gw.GnnHostEngine, eb, vb, F, src, dst)
    assert digest_summaries(got) == digest_summaries(want) \
        and digest_slab(slab) == digest_slab(wslab), \
        "GNN round diverged from the numpy lattice twin"

    reps = int(os.environ.get("GS_BENCH_REPS", "3"))
    dev_ts, host_ts = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        run_engine(gw.GnnSummaryEngine, eb, vb, F, src, dst)
        dev_ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_engine(gw.GnnHostEngine, eb, vb, F, src, dst)
        host_ts.append(time.perf_counter() - t0)
    dev_s = float(np.median(dev_ts))
    host_s = float(np.median(host_ts))

    print(json.dumps({
        "metric": "edge-features/sec/chip, windowed GNN round "
                  "(%d-edge windows, F=%d, fused scan vs numpy "
                  "twin)%s" % (eb, F, metric_suffix),
        "value": round(n * F / dev_s),
        "unit": "edge-features/s",
        "device": _device(),
        "num_edges": n,
        "feature_dim": F,
        "gnn_edge_features_per_s": round(n * F / dev_s),
        "edges_per_s": round(n / dev_s),
        "host_edges_per_s": round(n / host_s),
        "parity": True,
        "knobs": {"eb": eb, "vb": vb, "feature_dim": F,
                  "act": _knobs.get_str("GS_GNN_ACT") or "relu",
                  "pallas": _knobs.get_str("GS_GNN_PALLAS")
                  or "off"},
        "trace": _telemetry.trace_id(),
    }), flush=True)


EXIT_CAPACITY = 3
EXIT_TIMEOUT = 4
EXIT_NO_CHIP = 5

# child legs: env var the parent sets -> the leg that child runs
LEGS = {"GS_BENCH_REDUCE": run_reduce_leg,
        "GS_BENCH_COHORT": run_cohort_leg,
        "GS_BENCH_GNN": run_gnn_leg}


def _child_setup() -> None:
    """Every measuring child: the CPU backend under --cpu (a labelled
    rehearsal), the chip otherwise — a child that finds no TPU exits
    EXIT_NO_CHIP instead of measuring the CPU under a chip's name."""
    from gelly_streaming_tpu.core.platform import (enable_compile_cache,
                                                   use_cpu)

    if "--cpu" in sys.argv:
        use_cpu()
    enable_compile_cache()
    import jax

    platform = jax.devices()[0].platform
    if "--cpu" not in sys.argv and platform != "tpu":
        print("bench: no TPU (JAX found %r); pass --cpu for a CPU "
              "rehearsal" % platform, file=sys.stderr)
        sys.exit(EXIT_NO_CHIP)


def _run_child_leg(name: str, fn, *args) -> None:
    _child_setup()
    try:
        fn(*args)
    except AssertionError:
        raise  # parity failure: NEVER mask a correctness regression
    except Exception as e:
        if _is_resource_error(e):
            print("%s: %s: %s" % (name, type(e).__name__, e),
                  file=sys.stderr)
            sys.exit(EXIT_CAPACITY)
        raise


def main():
    suffix = os.environ.get("GS_BENCH_SUFFIX", "")
    for var, leg in LEGS.items():
        if os.environ.get(var):
            _run_child_leg(var, leg, suffix)
            return
    if os.environ.get("GS_BENCH_CHILD"):
        # a child must never re-enter the scale ladder
        attempt = float(os.environ["GS_BENCH_CHILD"])
        _run_child_leg("scale %g" % attempt, run_at_scale, attempt,
                       suffix)
        return
    # the parent never imports JAX: each leg runs in its own watchdogged
    # child, and one process at a time holds the chip
    metric_suffix = " [CPU rehearsal, --cpu]" if "--cpu" in sys.argv \
        else ""

    # Smallest scale first, one JSON line per completed scale: an
    # external timeout at a larger scale still leaves the best completed
    # number on stdout (the driver keeps the last line).
    # top scale = a 10.5M-edge stream (≥ the north star's 10M-edge
    # slice) through the capped 32768-edge window program
    scale = float(os.environ.get("BENCH_SCALE", "5.0"))
    done = 0
    for attempt in (scale / 80, scale / 20, scale):
        rc = run_scale_watchdogged(attempt, metric_suffix)
        if rc == 0:
            done += 1
            continue
        if rc in (EXIT_CAPACITY, EXIT_TIMEOUT) and done:
            # device limit or the watchdog at this scale: keep the
            # completed smaller-scale results on stdout
            print("bench stopped at scale %g (rc=%d); keeping "
                  "completed scales" % (attempt, rc), file=sys.stderr)
            break
        # nothing completed, no chip, or a genuine bug (incl. parity):
        # a green exit with no metric lines must be impossible
        sys.exit(rc or 1)

    # the other legs (BASELINE config #2's columnar reduceOnEdges, the
    # multi-tenant cohort, the windowed GNN): capacity/timeout keeps
    # the completed lines, a parity failure still fails the bench
    for var in LEGS:
        rc = run_scale_watchdogged(0.0, metric_suffix,
                                   extra_env={var: "1"})
        if rc not in (0, EXIT_CAPACITY, EXIT_TIMEOUT):
            sys.exit(rc)
        if rc:
            print("%s rc=%d (capacity/timeout); other lines kept"
                  % (var, rc), file=sys.stderr)


def run_scale_watchdogged(attempt: float, metric_suffix: str,
                          extra_env: dict = None) -> int:
    """Run one scale (or, with extra_env, another bench leg) in a
    subprocess with a hard timeout, streaming its stdout through. A
    hung compile or dispatch gets SIGKILLed (process group) instead of
    stalling the whole bench."""
    import signal

    timeout_s = int(os.environ.get("GS_BENCH_SCALE_TIMEOUT", "1500"))
    env = dict(os.environ, GS_BENCH_SUFFIX=metric_suffix)
    if extra_env:
        env.update(extra_env)
    else:
        env["GS_BENCH_CHILD"] = repr(attempt)
    p = subprocess.Popen([sys.executable] + sys.argv, env=env,
                         stdout=subprocess.PIPE, text=True,
                         start_new_session=True)
    import threading

    def pump():
        for line in p.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        rc = p.wait(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except OSError:
            pass
        p.wait()
        rc = EXIT_TIMEOUT
    t.join(timeout=5)
    return rc


if __name__ == "__main__":
    main()

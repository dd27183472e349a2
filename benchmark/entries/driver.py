"""Bulk analytics over one stream through `StreamingAnalyticsDriver.run_arrays`.

Set-up makes a pool of `pool_calls` calls of the configuration's stream
from the seed, folds the first `warm_calls` of them (set-up, and the
start of the stream the reference follows), compiles every program the
online tuner can pick, and feeds on until the tuners' incumbents have
held for `settle_calls` calls (at most twice that). The window then
feeds call after call, as a file or log source would (a closed loop),
until `--seconds` have passed; past the pool the stream starts over
from its first call. `edges_per_s` is every edge of the window's calls
over the window.

Afterwards a sample of windows drawn from the seed (the last window of
every call and `check_per_call` more) is compared with the reference:
the touched vertex set, each touched vertex's degree, component and
odd-cycle flag, and the window's triangles.
"""

import itertools
import time

import numpy as np

from benchmark import reference, streams

FAULTS = ("state_unchanged", "half_batch", "no_exchange", "altered",
          "control")


def _warm_arms(drv) -> dict:
    """Compile every program the tuners can choose, so that exploring
    an arm inside the window builds nothing. The driver keeps no public
    hook for this (PERF.md, Open questions)."""
    from gelly_streaming_tpu.ops import autotune

    arms = {}
    if not autotune.enabled():
        return arms
    tuner = drv._ensure_scan_tuner() if drv.mesh is None else None
    if tuner is not None:
        arms["snapshot_scan.wb"] = tuner.space["wb"]
        for wb in tuner.space["wb"]:
            drv._warm_scan_arm(wb)
    kern = drv._tri_kern()
    if hasattr(kern, "_tuner_space"):
        space = kern._tuner_space()
        arms["triangles"] = space
        for wb, kb, ing in itertools.product(space["wb"], space["kb"],
                                             space["ingress"]):
            kern._warm_arm({"wb": wb, "kb": kb, "ingress": ing})
    return arms


def _chosen_arms(drv) -> dict:
    out = {}
    tuner = getattr(drv, "_scan_tuner", None)
    if tuner is not None:
        out["snapshot_scan"] = dict(tuner.incumbent)
    tri = getattr(drv._tri_kernel, "tuner", None) if drv._tri_kernel else None
    if tri is not None:
        out["triangles"] = dict(tri.incumbent)
    return out


def _feed(src, dst, eb: int, fault):
    """The edges one call feeds, with the input faults applied."""
    if fault not in ("half_batch", "no_exchange", "control"):
        return src, dst
    s = src.reshape(-1, eb).copy()
    d = dst.reshape(-1, eb).copy()
    if fault == "half_batch":          # half of each window left out
        h = eb // 2
        s[:, h:], d[:, h:] = s[:, :h], d[:, :h]
    elif fault == "no_exchange":       # a quarter of each window only
        q = eb // 4
        s[:] = np.tile(s[:, :q], 4)
        d[:] = np.tile(d[:, :q], 4)
    else:                              # at-least-once: an edge re-sent
        s[:, -1], d[:, -1] = np.roll(s[:, -2], 1), np.roll(d[:, -2], 1)
    return s.ravel(), d.ravel()


def _compare(res, ref) -> dict:
    """Mismatch counts of one delivered window against the reference."""
    bad = {"ids": 0, "deg": 0, "comp": 0, "odd": 0, "tri": 0}
    ids = np.asarray(res.vertex_ids, np.int64)
    order = np.argsort(ids)
    if not np.array_equal(ids[order], ref["ids"]):
        bad["ids"] = 1
        return bad
    n = len(ids)
    got = {"deg": np.asarray(res.degrees)[:n][order],
           "comp": reference._min_member(np.asarray(res.cc_labels)[:n],
                                         ids)[order],
           "odd": np.asarray(res.bipartite_odd)[:n][order].astype(bool)}
    for k, v in got.items():
        bad[k] = int(np.sum(v != ref[k]))
    bad["tri"] = int(res.triangles != ref["triangles"])
    return bad


def run(r) -> None:
    import jax

    from gelly_streaming_tpu import StreamingAnalyticsDriver
    from gelly_streaming_tpu.core.driver import resolve_snapshot_tier
    from gelly_streaming_tpu.ops import triangles as tri_ops

    cfg, tr = r.cell.config, r.cell.traffic
    vb, eb = int(cfg["vertex_bucket"]), int(tr["window_edges"])
    call = int(tr["call_edges"])
    per_call = call // eb
    pool = int(tr["pool_calls"])
    fault = r.fault
    if fault is not None and fault not in FAULTS:
        raise SystemExit("unknown fault %r" % fault)
    t_setup = time.monotonic()
    src, dst = streams.make_stream(call * pool, vb,
                                   seed=streams.sub_seed(r.seed, 0))
    t_stream = time.monotonic()
    src = src.astype(np.int32)
    dst = dst.astype(np.int32)

    mesh = None
    if r.cell.chips > 1:
        from gelly_streaming_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(r.cell.chips)
    drv = StreamingAnalyticsDriver(window_ms=0, analytics=cfg["analytics"],
                                   vertex_bucket=vb, edge_bucket=eb,
                                   mesh=mesh)

    def chunk(k):
        i = (k % pool) * call
        return _feed(src[i:i + call], dst[i:i + call], eb, fault)

    k = 0
    for _ in range(int(tr["warm_calls"])):
        drv.run_arrays(*chunk(k))
        k += 1
    t_warm = time.monotonic()
    arms = _warm_arms(drv)
    t_arms = time.monotonic()
    # the tuners start from an empty cache every run: feed on until
    # their incumbents have held for `settle_calls` calls in a row (at
    # most twice that many calls), so the window does not open on an
    # early promotion
    need = int(tr.get("settle_calls", 0))
    held = settled = 0
    while held < need and settled < 2 * need:
        before = _chosen_arms(drv)
        drv.run_arrays(*chunk(k))
        k += 1
        settled += 1
        held = held + 1 if _chosen_arms(drv) == before else 0
    r.note(setup={"before_stream_s": t_setup - r.t_start,
                  "stream_s": t_stream - t_setup,
                  "warm_calls_s": t_warm - t_stream,
                  "arms_s": t_arms - t_warm,
                  "settle_calls": settled,
                  "settle_s": time.monotonic() - t_arms})
    r.note(tier={"triangles": tri_ops._resolve_stream_impl(eb),
                 "snapshot": ("sharded" if mesh is not None
                              else resolve_snapshot_tier())},
           arms_warmed=arms)

    first_call = k
    rng = np.random.default_rng(streams.sub_seed(r.seed, 3))
    kept = {}           # global window index -> WindowResult
    prev_last = None
    r.first_edge()
    with r.window():
        t0 = time.monotonic()
        while True:
            with jax.profiler.TraceAnnotation("bench.run_arrays"):
                res = drv.run_arrays(*chunk(k))
                np.asarray(res[-1].degrees).sum()
            if fault == "state_unchanged" and prev_last is not None:
                for w in res:
                    for f in ("vertex_ids", "degrees", "cc_labels",
                              "bipartite_odd"):
                        setattr(w, f, getattr(prev_last, f))
            if fault == "altered":
                res[-1].triangles += 1
            prev_last = res[-1]
            base = k * per_call
            pick = {per_call - 1, *rng.choice(
                per_call, int(tr["check_per_call"]), replace=False).tolist()}
            for j in pick:
                kept[base + j] = res[j]
            del res
            k += 1
            if time.monotonic() - t0 >= r.seconds:
                break
        elapsed = time.monotonic() - t0
    calls = k - first_call
    r.values["edges_per_s"] = calls * call / elapsed
    r.counters.update(windows=calls * per_call, calls=calls, eb=eb, vb=vb,
                      analytics=list(cfg["analytics"]))
    r.note(window={"calls": calls, "seconds": elapsed,
                   "pool_wrapped": k > pool, "chosen_arms": _chosen_arms(drv)})
    r.read_memory()
    del drv

    # the reference follows the same stream from its first edge
    t_ref = time.monotonic()
    reps = -(-k // pool)
    full_s = np.tile(src, reps)[:k * call]
    full_d = np.tile(dst, reps)[:k * call]
    refs = reference.fold_windows(full_s, full_d, eb, vb, kept)
    if fault == "control":
        # the control: the reference with a guarantee broken, in the
        # program's place
        ctl = reference.fold_windows(*_feed(full_s, full_d, eb, "control"),
                                     eb, vb, kept)
        kept = {w: _AsResult(ctl[w]) for w in kept}
    tot = {"ids": 0, "deg": 0, "comp": 0, "odd": 0, "tri": 0}
    wrong = 0
    for w, res in kept.items():
        bad = _compare(res, refs[w])
        wrong += any(bad.values())
        for key, v in bad.items():
            tot[key] += v
    r.note(reference={"windows_checked": len(kept),
                      "seconds": time.monotonic() - t_ref})
    r.attempted = calls * per_call
    r.failed = wrong
    for key, v in tot.items():
        r.checks[key + "_bad"] = (v, 0)


class _AsResult:
    """A reference record dressed as a delivered window (the control)."""

    def __init__(self, rec):
        self.vertex_ids = rec["ids"]
        self.degrees = rec["deg"]
        self.cc_labels = rec["comp"]
        self.bipartite_odd = rec["odd"]
        self.triangles = rec["triangles"]

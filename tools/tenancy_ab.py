#!/usr/bin/env python
"""Multi-tenant cohort A/B: does ONE vmapped cohort dispatch over N
streams (core/tenancy.TenantCohort) beat N sequential single-tenant
engines — with EXACT per-tenant parity?

Four probes, each a JSON row:

  cohort_serving — the serving shape ("millions of users = thousands
              of small streams"): N tenants fed window by window in
              arrival order, both sides pumping every round. The
              cohort folds the round's N windows in ONE vmapped
              dispatch; the sequential oracle runs N StreamSummary-
              Engine.process() calls of one window each — the
              per-dispatch wall the ROADMAP names, paid N times per
              round. Per-tenant sha256 over the summary stream must
              match the oracle exactly before any speedup is claimed.
  cohort_batch — the drain shape: deep queues, the cohort catching up
              at its windows-per-dispatch ceiling vs each sequential
              engine folding its whole stream at the chunked scan's
              normal 64-window dispatches. This is the UNFAVORABLE
              baseline for the cohort (the oracle amortizes its own
              dispatches) — committed beside the serving row so the
              evidence shows both economics.
  cohort_resident — the resident-cohort tier (GS_COHORT_RESIDENT=on):
              the donated [N, ...] stacked-carry super-batch program
              vs the same N-sequential per-window oracle, one row per
              N in {1, 3, 8} at the serving shape; the N=1 row shows
              where one tenant gains nothing.
  cohort_pallas — the tenant-axis Pallas megakernel
              (GS_COHORT_PALLAS=on). Off-TPU this runs in interpret
              mode and the row carries `interpret: true`: these
              rows are PARITY evidence (per-tenant sha256 vs the
              oracle), not speed evidence.

Timing is median-of-3 with min/max dispersion in the row (the ingress
A/B's flip-flop taught us a single draw is load noise). GS_AUTOTUNE
is pinned OFF inside the probes so the cross-tenant batching lever is
measured in isolation; GS_TENANT_TPD=0 then dispatches all ready
tenants in one slab.

The committed `tenancy_ab` rows are the cohort's adoption evidence
(the acceptance bar: serving-row speedup ≥1.5x at N=8 with exact
parity; if the bar is missed the rows are committed anyway and the
cohort path stays an explicit opt-in — report honestly, like the
resident tier). Commit policy identical to tools/resident_ab.py.

`--smoke` is the CI parity gate (tools/ci_check.sh): a 1-tenant
cohort must produce the BYTE-IDENTICAL summary digest of a single
StreamSummaryEngine fed the same stream — the cohort path can never
silently drift from the single-stream semantics. `--resident-smoke`
is the resident-tier twin: a 2-tenant cohort pinned to
GS_COHORT_RESIDENT=on must match two single-stream engines AND must
have actually taken the resident path (resident_dispatches > 0) — a
silent decline to the scan tier fails the gate rather than passing
vacuously.
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


def digest_summaries(summaries) -> str:
    """sha256 over the summary-dict stream (every field, in window
    order) — the per-tenant parity identity."""
    h = hashlib.sha256()
    for s in summaries:
        h.update(json.dumps(s, sort_keys=True).encode())
    return h.hexdigest()[:16]


def make_tenant_streams(n_tenants: int, windows: int, eb: int,
                        vb: int, ragged: bool = True):
    """One deterministic power-law stream per tenant; ragged lengths
    (a short partial tail on some tenants) exercise the right-padding
    path the slab exists for."""
    streams = {}
    for i in range(n_tenants):
        n = windows * eb
        if ragged and i % 3 == 2:
            n -= eb // 3  # partial final window
        s, d = make_stream(n, vb, seed=100 + i)
        streams["t%02d" % i] = (s.astype(np.int32), d.astype(np.int32))
    return streams


def sequential_oracle(streams, eb, vb, per_window: bool):
    """N single-tenant engines. per_window=True replays the serving
    shape (one process() call per arrived window, round-robin);
    False folds each stream in one chunked call."""
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)

    out = {}
    engines = {tid: StreamSummaryEngine(edge_bucket=eb,
                                        vertex_bucket=vb)
               for tid in streams}
    if not per_window:
        for tid, (s, d) in streams.items():
            out[tid] = engines[tid].process(s, d)
        return out
    out = {tid: [] for tid in streams}
    cursors = {tid: 0 for tid in streams}
    live = True
    while live:
        live = False
        for tid, (s, d) in streams.items():
            c = cursors[tid]
            if c >= len(s):
                continue
            hi = min(c + eb, len(s))
            # a trailing partial window is the stream's FINAL call —
            # exactly the count-based tumbling contract
            out[tid].extend(engines[tid].process(s[c:hi], d[c:hi]))
            cursors[tid] = hi
            live = True
    return out


_ORACLE_CACHE = {}


def oracle_cached(streams, eb, vb, per_window: bool):
    """Per-(N, shape) memo of the N-sequential oracle within one run:
    cohort_serving, cohort_resident and cohort_pallas all compare
    against the SAME oracle at the same (N, eb, vb) shape, so compute
    it once. The timed reps still recompute it live (that's the
    baseline being measured) and _probe asserts the recomputation's
    per-tenant digests are identical to the cached ones — the cache
    can never mask oracle drift."""
    key = (tuple(sorted(streams)), eb, vb, per_window,
           sum(len(s) for s, _d in streams.values()))
    hit = _ORACLE_CACHE.get(key)
    if hit is None:
        hit = sequential_oracle(streams, eb, vb, per_window)
        _ORACLE_CACHE[key] = hit
    return hit


class scoped_env:
    """Pin GS_* knobs for one probe side and restore afterwards,
    forgetting the Pallas probe verdicts so the pin is re-probed."""

    def __init__(self, **pins):
        self.pins = pins
        self._old = {}

    def _reset(self):
        from gelly_streaming_tpu.ops import pallas_window
        pallas_window._reset_pallas_window()

    def __enter__(self):
        for k, v in self.pins.items():
            self._old[k] = os.environ.get(k)
            os.environ[k] = v
        self._reset()
        return self

    def __exit__(self, *exc):
        for k, old in self._old.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old
        self._reset()
        return False


def cohort_run(streams, eb, vb, per_window: bool):
    """The cohort side: admit everyone, feed in arrival order, pump.
    per_window=True feeds one window per tenant per round (the
    serving shape — every round is one vmapped dispatch); False
    preloads the queues and lets pump() catch up at its
    windows-per-dispatch ceiling."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort

    co = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    for tid in streams:
        co.admit(tid)
    out = {tid: [] for tid in streams}
    cursors = {tid: 0 for tid in streams}
    live = True
    while live:
        live = False
        for tid, (s, d) in streams.items():
            c = cursors[tid]
            if c >= len(s):
                continue
            hi = min(c + eb, len(s)) if per_window \
                else min(c + 4 * eb, len(s))
            co.feed(tid, s[c:hi], d[c:hi])
            cursors[tid] = hi
            live = True
        for tid, res in co.pump().items():
            out[tid].extend(res)
    for tid in streams:
        out[tid].extend(co.close(tid))
    return out


def _probe(name: str, jax, streams, eb, vb, per_window: bool,
           results: list, pins=None, extra=None) -> None:
    """One probe row. `pins` are GS_* knobs applied around the COHORT
    side only (the oracle is always the plain N-sequential baseline);
    `extra` keys are merged into the row verbatim."""
    total_edges = sum(len(s) for s, _d in streams.values())
    want = oracle_cached(streams, eb, vb, per_window)
    want_digests = {t: digest_summaries(want[t]) for t in streams}
    with scoped_env(**(pins or {})):
        got = cohort_run(streams, eb, vb, per_window)
        coh = timed_stats(
            lambda: cohort_run(streams, eb, vb, per_window),
            reps=3, warmup=0)
    parity = all(digest_summaries(got[t]) == want_digests[t]
                 for t in streams)

    relive = {}
    seq = timed_stats(
        lambda: relive.update(
            out=sequential_oracle(streams, eb, vb, per_window)),
        reps=3, warmup=0)
    # the oracle-cache identity: a live recomputation (the timed
    # baseline) must reproduce the cached oracle's digests exactly
    assert all(digest_summaries(relive["out"][t]) == want_digests[t]
               for t in streams), \
        "oracle cache drift: recomputed digests differ (%s)" % name

    row = {
        "probe": name,
        "backend": jax.default_backend(),
        "tenants": len(streams),
        "eb": eb, "vb": vb,
        "num_edges": total_edges,
        "windows": sum(-(-len(s) // eb)
                       for s, _d in streams.values()),
        "tenant_edges_per_s": round(total_edges / coh[0]),
        "sequential_edges_per_s": round(total_edges / seq[0]),
        "parity": bool(parity),
        "tenant_digests": {t: digest_summaries(got[t])
                           for t in sorted(streams)},
    }
    row.update(extra or {})
    _dispersion(row, "cohort", coh)
    _dispersion(row, "sequential", seq)
    if parity:
        row["speedup"] = round(seq[0] / coh[0], 3)
        row["speedup_worst"] = round(seq[1] / coh[2], 3)
        row["speedup_best"] = round(seq[2] / coh[1], 3)
    else:
        bad = [t for t in streams
               if digest_summaries(got[t]) != want_digests[t]]
        print("PARITY FAILURE (%s): tenants %s diverged from the "
              "sequential oracle" % (name, bad), file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


def smoke() -> int:
    """The ci_check gate: a 1-tenant cohort's digest must be
    byte-identical to a single StreamSummaryEngine's on the same
    stream (full + partial windows), in seconds not minutes."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)

    eb, vb = 512, 1024
    n = 5 * eb + eb // 4  # 5 full windows + a partial tail
    s, d = make_stream(n, vb, seed=11)
    s, d = s.astype(np.int32), d.astype(np.int32)
    want = StreamSummaryEngine(edge_bucket=eb,
                               vertex_bucket=vb).process(s, d)
    co = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    co.admit("solo")
    got = []
    for lo in range(0, n, 2 * eb):
        co.feed("solo", s[lo:lo + 2 * eb], d[lo:lo + 2 * eb])
        got.extend(co.pump().get("solo", []))
    got.extend(co.close("solo"))
    if digest_summaries(got) != digest_summaries(want) \
            or len(got) != len(want):
        print("tenancy smoke FAILED: 1-tenant cohort digest %s != "
              "single-stream digest %s (%d vs %d windows)"
              % (digest_summaries(got), digest_summaries(want),
                 len(got), len(want)), file=sys.stderr)
        return 1
    print("tenancy smoke ok: 1-tenant cohort ≡ single stream (%s, "
          "%d windows)" % (digest_summaries(got), len(got)),
          flush=True)
    return 0


def resident_smoke() -> int:
    """The ci_check resident gate: a 2-tenant cohort pinned to the
    resident tier must (a) match two single-stream engines per-tenant
    byte-for-byte AND (b) have actually dispatched through the
    resident super-batch program — a silent decline to the scan tier
    (resident_dispatches == 0) FAILS instead of passing vacuously."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)

    eb, vb = 512, 1024
    streams = make_tenant_streams(2, 5, eb, vb, ragged=True)
    want = {tid: StreamSummaryEngine(edge_bucket=eb,
                                     vertex_bucket=vb).process(s, d)
            for tid, (s, d) in streams.items()}
    with scoped_env(GS_COHORT_RESIDENT="on"):
        co = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
        for tid in streams:
            co.admit(tid)
        got = {tid: [] for tid in streams}
        cursors = {tid: 0 for tid in streams}
        live = True
        while live:
            live = False
            for tid, (s, d) in streams.items():
                c = cursors[tid]
                if c >= len(s):
                    continue
                hi = min(c + eb, len(s))
                co.feed(tid, s[c:hi], d[c:hi])
                cursors[tid] = hi
                live = True
            for tid, res in co.pump().items():
                got[tid].extend(res)
        for tid in streams:
            got[tid].extend(co.close(tid))
        dispatches = co.resident_dispatches
    if dispatches == 0:
        print("resident smoke FAILED: GS_COHORT_RESIDENT=on but the "
              "cohort never took the resident super-batch path "
              "(resident_dispatches=0) — silent decline",
              file=sys.stderr)
        return 1
    bad = [t for t in streams
           if digest_summaries(got[t]) != digest_summaries(want[t])]
    if bad:
        print("resident smoke FAILED: tenants %s diverged from the "
              "single-stream engines" % bad, file=sys.stderr)
        return 1
    print("resident smoke ok: 2-tenant resident cohort ≡ single "
          "streams (%d resident dispatches)" % dispatches, flush=True)
    return 0


PROBE_NAMES = ("cohort_serving", "cohort_batch", "cohort_resident",
               "cohort_pallas")


def commit_results(results, backend: str) -> None:
    """Merge this run's `tenancy_ab` rows into the committed evidence
    — the same policy as tools/resident_ab.py: PERF.json only when
    its backend label matches the live backend, the per-backend
    archive PERF_<backend>.json always. Merge is BY PROBE: only the
    probes this run produced are replaced, so a cohort_resident-only
    run can't evict the committed cohort_serving/cohort_batch rows."""
    ran = {r["probe"] for r in results}
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
        kept = [r for r in cur.get("tenancy_ab", [])
                if r.get("probe") not in ran]
        cur["tenancy_ab"] = kept + results
        with open(path, "w") as f:
            json.dump(cur, f, indent=2)
        print("committed %s row(s) to %s (%d prior row(s) kept)"
              % (len(results), os.path.basename(path), len(kept)),
              flush=True)


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("probes", nargs="*",
                    help="subset of %s to run (default: all)"
                         % (PROBE_NAMES,))
    ap.add_argument("--tenants", type=int,
                    default=int(os.environ.get("GS_AB_TENANTS", 8)))
    ap.add_argument("--windows", type=int,
                    default=int(os.environ.get("GS_AB_WINDOWS", 16)),
                    help="windows per tenant")
    ap.add_argument("--eb", type=int,
                    default=int(os.environ.get("GS_AB_EB", 512)))
    ap.add_argument("--vb", type=int,
                    default=int(os.environ.get("GS_AB_VB", 1024)))
    ap.add_argument("--smoke", action="store_true",
                    help="CI parity gate only: 1-tenant cohort must "
                         "equal the single-stream digest")
    ap.add_argument("--resident-smoke", action="store_true",
                    help="CI resident gate: 2-tenant cohort pinned "
                         "GS_COHORT_RESIDENT=on must equal the "
                         "single-stream digests AND have taken the "
                         "resident path")
    ap.add_argument("--commit", action="store_true",
                    help="merge rows into PERF.json (backend-matched) "
                         "and PERF_<backend>.json")
    args = ap.parse_args()
    bad = [p for p in args.probes if p not in PROBE_NAMES]
    if bad:
        ap.error("unknown probe(s) %s; valid: %s"
                 % (bad, list(PROBE_NAMES)))
    want = args.probes or list(PROBE_NAMES)

    # measure the cross-tenant batching lever in isolation: the online
    # tuner changing dispatch knobs between reps would be noise here
    os.environ["GS_AUTOTUNE"] = "0"

    if args.smoke:
        sys.exit(smoke())
    if args.resident_smoke:
        sys.exit(resident_smoke())

    import jax

    streams = make_tenant_streams(args.tenants, args.windows,
                                  args.eb, args.vb)
    results = []
    if "cohort_serving" in want:
        _probe("cohort_serving", jax, streams, args.eb, args.vb,
               True, results)
    if "cohort_batch" in want:
        _probe("cohort_batch", jax, streams, args.eb, args.vb,
               False, results)
    if "cohort_resident" in want:
        # one row per cohort size: N=1 (the honest no-gain floor),
        # N=3 (mixed), N=args.tenants (the serving acceptance shape)
        for n in sorted({1, 3, args.tenants}):
            sub = make_tenant_streams(n, args.windows, args.eb,
                                      args.vb)
            _probe("cohort_resident", jax, sub, args.eb, args.vb,
                   True, results,
                   pins={"GS_COHORT_RESIDENT": "on"})
    if "cohort_pallas" in want:
        on_tpu = jax.default_backend() == "tpu"
        for n in sorted({1, 3, args.tenants}):
            sub = make_tenant_streams(n, args.windows, args.eb,
                                      args.vb)
            _probe("cohort_pallas", jax, sub, args.eb, args.vb,
                   True, results,
                   pins={"GS_COHORT_PALLAS": "on"},
                   extra={} if on_tpu else {"interpret": True})
    out = os.path.join(REPO, "logs",
                       "tenancy_ab_%s.json" % jax.default_backend())
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote %s" % out, flush=True)
    if args.commit:
        commit_results(results, jax.default_backend())


if __name__ == "__main__":
    main()

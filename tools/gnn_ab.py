#!/usr/bin/env python
"""Windowed-GNN A/B: is the device GNN round (ops/gnn_window) worth
its dispatches — at EXACT feature-slab parity with the numpy twin?

Three probes, each a JSON row:

  gnn_engine — GnnSummaryEngine (fused lax.scan over chunked windows)
              vs GnnHostEngine (the numpy bit-exactness oracle) on
              the same stream: sha256 over the summary stream AND the
              final [vb, F] feature slab must match before any
              speedup is claimed. The lattice exactness argument
              (module docstring of ops/gnn_window) is what makes this
              an equality, not a tolerance.
  gnn_cohort — core/tenancy.GnnTenantCohort folding N tenants'
              windows in ONE vmapped dispatch vs N sequential
              GnnSummaryEngine runs, per-tenant slab + summary
              parity, one row per N — the acceptance evidence at
              N ∈ {1, 8} (the N=1 row is the honest no-gain floor).
  gnn_pallas — the fused Pallas GNN kernel (GS_GNN_PALLAS=on) vs the
              XLA gather/segment-sum round (pinned off). Off-TPU this
              runs in interpret mode and the row carries
              `interpret: true`: those rows are PARITY evidence,
              not speed evidence.

Timing is median-of-3 with min/max dispersion in the row (the ingress
A/B's flip-flop taught us a single draw is load noise). GS_AUTOTUNE
is pinned OFF inside the probes.

`--commit` merges the rows into PERF.json (backend-matched) and
PERF_<backend>.json under `gnn_ab`, AND commits the `gnn` cost
section (gnn_cost_section — the same helper tools/profile_kernels.py
section_gnn runs): the armed cost-observatory rows for the GNN
programs with the stated arithmetic intensity beside the measured
throughput. The intensity claim is the point of the workload — the
dense update's 2·(vb+1)·F² FLOPs put these programs past every
existing gather program's 0.25–0.28 FLOPs/byte — and it is stated
honestly: on CPU the measured rate stays far below the model's bound
either way, and the row says which bound the MODEL predicts, not
what the backend achieved.
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
    order) — the per-stream parity identity."""
    h = hashlib.sha256()
    for s in summaries:
        h.update(json.dumps(s, sort_keys=True).encode())
    return h.hexdigest()[:16]


def digest_slab(slab: np.ndarray) -> str:
    """sha256 over the exact bytes of the [vb, F] feature slab — the
    carry-state parity identity (summaries alone can't see a slab
    divergence that happens to preserve the checksum)."""
    return hashlib.sha256(
        np.ascontiguousarray(slab, np.float32).tobytes()
    ).hexdigest()[:16]


def make_tenant_streams(n_tenants: int, windows: int, eb: int,
                        vb: int, ragged: bool = True):
    """One deterministic power-law stream per tenant; ragged lengths
    (a short partial tail on some tenants) exercise the window-axis
    padding the empty-window-holds rule exists for."""
    streams = {}
    for i in range(n_tenants):
        n = windows * eb
        if ragged and i % 3 == 2:
            n -= eb // 3  # partial final window
        s, d = make_stream(n, vb, seed=100 + i)
        streams["t%02d" % i] = (s.astype(np.int32), d.astype(np.int32))
    return streams


def _weights(F: int):
    """Deterministic non-trivial dense layer (snapped by the engines):
    a mixing matrix, not the identity default — parity on the
    identity would not exercise the matmul at all."""
    rng = np.random.RandomState(42)
    return rng.randn(F, F) * 0.3, rng.randn(F) * 0.1


def run_engine(cls, eb, vb, F, s, d):
    """One engine-tier run: seed deterministic features + weights,
    fold the stream, return (summaries, final slab)."""
    from gelly_streaming_tpu.ops import gnn_window as gw

    eng = cls(eb, vb, feature_dim=F)
    eng.set_weights(*_weights(F))
    eng.load_feature_units(gw.default_features(vb, F, seed=1))
    out = eng.process(s, d)
    return out, eng.state()


def run_cohort(streams, eb, vb, F):
    """The cohort side: admit everyone with per-tenant seeds, feed in
    arrival order, pump each round, close. Returns per-tenant
    (summaries, final slab)."""
    from gelly_streaming_tpu.core.tenancy import GnnTenantCohort
    from gelly_streaming_tpu.ops import gnn_window as gw

    co = GnnTenantCohort(eb, vb, feature_dim=F)
    co.set_weights(*_weights(F))
    out = {tid: [] for tid in streams}
    for i, tid in enumerate(sorted(streams)):
        co.admit(tid, feature_units=gw.default_features(vb, F,
                                                        seed=i))
    cursors = {tid: 0 for tid in streams}
    live = True
    while live:
        live = False
        for tid, (s, d) in streams.items():
            c = cursors[tid]
            if c >= len(s):
                continue
            hi = min(c + 2 * eb, len(s))
            co.feed(tid, s[c:hi], d[c:hi])
            cursors[tid] = hi
            live = True
        for tid, res in co.pump().items():
            out[tid].extend(res)
    slabs = {}
    for tid in streams:
        slabs[tid] = co.state(tid) if not co.queued_edges(tid) \
            else None
        out[tid].extend(co.close(tid))
    return out, slabs


def cohort_oracle(streams, eb, vb, F):
    """N sequential GnnSummaryEngine runs with the cohort's
    per-tenant seeds — the baseline being measured AND the parity
    oracle."""
    from gelly_streaming_tpu.ops import gnn_window as gw

    out, slabs = {}, {}
    for i, tid in enumerate(sorted(streams)):
        eng = gw.GnnSummaryEngine(eb, vb, feature_dim=F)
        eng.set_weights(*_weights(F))
        eng.load_feature_units(gw.default_features(vb, F, seed=i))
        s, d = streams[tid]
        out[tid] = eng.process(s, d)
        slabs[tid] = eng.state()
    return out, slabs


class scoped_env:
    """Pin GS_* knobs for one probe side and restore afterwards,
    resetting the Pallas probe verdicts so each side builds anew."""

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


def probe_engine(jax, eb, vb, F, windows, results) -> None:
    """gnn_engine: device scan vs the numpy twin."""
    from gelly_streaming_tpu.ops import gnn_window as gw

    n = windows * eb - eb // 3  # ragged tail on purpose
    s, d = make_stream(n, vb, seed=7)
    s, d = s.astype(np.int32), d.astype(np.int32)
    got, slab = run_engine(gw.GnnSummaryEngine, eb, vb, F, s, d)
    want, wslab = run_engine(gw.GnnHostEngine, eb, vb, F, s, d)
    parity = (digest_summaries(got) == digest_summaries(want)
              and digest_slab(slab) == digest_slab(wslab))
    dev = timed_stats(
        lambda: run_engine(gw.GnnSummaryEngine, eb, vb, F, s, d),
        reps=3, warmup=0)
    host = timed_stats(
        lambda: run_engine(gw.GnnHostEngine, eb, vb, F, s, d),
        reps=3, warmup=0)
    ef = n * F  # edge-features: the workload's throughput unit
    row = {
        "probe": "gnn_engine",
        "backend": jax.default_backend(),
        "eb": eb, "vb": vb, "feature_dim": F,
        "num_edges": n, "windows": -(-n // eb),
        "engine_edges_per_s": round(n / dev[0]),
        "host_edges_per_s": round(n / host[0]),
        "gnn_edge_features_per_s": round(ef / dev[0]),
        "parity": bool(parity),
        "slab_digest": digest_slab(slab),
        "summary_digest": digest_summaries(got),
    }
    _dispersion(row, "engine", dev)
    _dispersion(row, "host", host)
    if parity:
        row["speedup"] = round(host[0] / dev[0], 3)
        row["speedup_worst"] = round(host[1] / dev[2], 3)
        row["speedup_best"] = round(host[2] / dev[1], 3)
    else:
        print("PARITY FAILURE (gnn_engine): device slab/summaries "
              "diverged from the numpy twin", file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


def probe_cohort(jax, eb, vb, F, windows, n_tenants,
                 results) -> None:
    """gnn_cohort: one vmapped N-tenant dispatch vs N sequential
    engines, per-tenant slab + summary parity."""
    streams = make_tenant_streams(n_tenants, windows, eb, vb)
    got, gslabs = run_cohort(streams, eb, vb, F)
    want, wslabs = cohort_oracle(streams, eb, vb, F)
    parity = all(
        digest_summaries(got[t]) == digest_summaries(want[t])
        and (gslabs[t] is None
             or digest_slab(gslabs[t]) == digest_slab(wslabs[t]))
        for t in streams)
    coh = timed_stats(lambda: run_cohort(streams, eb, vb, F),
                      reps=3, warmup=0)
    seq = timed_stats(lambda: cohort_oracle(streams, eb, vb, F),
                      reps=3, warmup=0)
    total = sum(len(s) for s, _d in streams.values())
    row = {
        "probe": "gnn_cohort",
        "backend": jax.default_backend(),
        "tenants": n_tenants,
        "eb": eb, "vb": vb, "feature_dim": F,
        "num_edges": total,
        "windows": sum(-(-len(s) // eb)
                       for s, _d in streams.values()),
        "cohort_edges_per_s": round(total / coh[0]),
        "sequential_edges_per_s": round(total / seq[0]),
        "gnn_edge_features_per_s": round(total * F / coh[0]),
        "parity": bool(parity),
        "tenant_digests": {t: digest_summaries(got[t])
                           for t in sorted(streams)},
    }
    _dispersion(row, "cohort", coh)
    _dispersion(row, "sequential", seq)
    if parity:
        row["speedup"] = round(seq[0] / coh[0], 3)
        row["speedup_worst"] = round(seq[1] / coh[2], 3)
        row["speedup_best"] = round(seq[2] / coh[1], 3)
    else:
        bad = [t for t in streams
               if digest_summaries(got[t]) != digest_summaries(want[t])]
        print("PARITY FAILURE (gnn_cohort N=%d): tenants %s diverged"
              % (n_tenants, bad), file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


def probe_pallas(jax, eb, vb, F, windows, results) -> None:
    """gnn_pallas: the fused kernel (pinned on) vs the XLA round
    (pinned off), slab + summary parity. The kernel must actually
    have been selected — a silent gate decline fails the probe
    instead of measuring XLA against itself."""
    from gelly_streaming_tpu.ops import gnn_window as gw

    n = windows * eb - eb // 3
    s, d = make_stream(n, vb, seed=7)
    s, d = s.astype(np.int32), d.astype(np.int32)
    on_tpu = jax.default_backend() == "tpu"

    with scoped_env(GS_GNN_PALLAS="off"):
        want, wslab = run_engine(gw.GnnSummaryEngine, eb, vb, F,
                                 s, d)
        xla = timed_stats(
            lambda: run_engine(gw.GnnSummaryEngine, eb, vb, F, s, d),
            reps=3, warmup=0)
    with scoped_env(GS_GNN_PALLAS="on"):
        eng = gw.GnnSummaryEngine(eb, vb, feature_dim=F)
        if not eng._pallas:
            print("PROBE FAILURE (gnn_pallas): GS_GNN_PALLAS=on but "
                  "the kernel was not selected (silent gate decline)",
                  file=sys.stderr)
            results.append({"probe": "gnn_pallas",
                            "backend": jax.default_backend(),
                            "eb": eb, "vb": vb, "feature_dim": F,
                            "parity": False, "selected": False})
            return
        got, slab = run_engine(gw.GnnSummaryEngine, eb, vb, F, s, d)
        pal = timed_stats(
            lambda: run_engine(gw.GnnSummaryEngine, eb, vb, F, s, d),
            reps=3, warmup=0)
    parity = (digest_summaries(got) == digest_summaries(want)
              and digest_slab(slab) == digest_slab(wslab))
    row = {
        "probe": "gnn_pallas",
        "backend": jax.default_backend(),
        "eb": eb, "vb": vb, "feature_dim": F,
        "num_edges": n, "windows": -(-n // eb),
        "pallas_edges_per_s": round(n / pal[0]),
        "xla_edges_per_s": round(n / xla[0]),
        "parity": bool(parity),
        "selected": True,
        "slab_digest": digest_slab(slab),
    }
    if not on_tpu:
        row["interpret"] = True
    _dispersion(row, "pallas", pal)
    _dispersion(row, "xla", xla)
    if parity:
        row["speedup"] = round(xla[0] / pal[0], 3)
        row["speedup_worst"] = round(xla[1] / pal[2], 3)
        row["speedup_best"] = round(xla[2] / pal[1], 3)
    else:
        print("PARITY FAILURE (gnn_pallas): fused kernel diverged "
              "from the XLA round", file=sys.stderr)
    results.append(row)
    print(json.dumps(row), flush=True)


def gnn_cost_section(eb: int = 32768, vb: int = 65536,
                     F: int = None, edges: int = None) -> dict:
    """The `gnn` cost-observatory section (shared by --commit here
    and tools/profile_kernels.py section_gnn): run the GNN engine
    armed on the acceptance shape, assert digest parity against a
    disarmed run AND the host twin, and return the per-program
    analytic rows — each stating its arithmetic intensity — beside
    the measured throughput. The honesty clause: the intensity is the
    STATED model's (flops/bytes of the analytic slab model, computed
    by utils/costmodel.classify), not a measured counter; on CPU the
    achieved rate stays bytes-bound regardless, and the row carries
    both numbers so PERF.md can say so."""
    import jax

    from gelly_streaming_tpu.ops import gnn_window as gw
    from gelly_streaming_tpu.utils import costmodel, telemetry

    from bench import make_stream as _mk

    if F is None:
        F = 16
    if edges is None:
        edges = int(os.environ.get("GS_TELEMETRY_EDGES", 524288))
    s, d = _mk(edges, vb)
    s, d = s.astype(np.int32), d.astype(np.int32)

    prev = {k: os.environ.get(k)
            for k in ("GS_COSTMODEL", "GS_TELEMETRY")}
    try:
        os.environ["GS_COSTMODEL"] = "0"
        os.environ["GS_TELEMETRY"] = "0"
        base, base_slab = run_engine(gw.GnnSummaryEngine, eb, vb, F,
                                     s, d)
        twin, twin_slab = run_engine(gw.GnnHostEngine, eb, vb, F,
                                     s, d)
        os.environ["GS_COSTMODEL"] = "1"
        os.environ["GS_TELEMETRY"] = "1"
        telemetry.reset()
        costmodel.reset()
        t = timed_stats(lambda: run_engine(gw.GnnSummaryEngine, eb,
                                           vb, F, s, d),
                        reps=1, warmup=0)
        armed, armed_slab = run_engine(gw.GnnSummaryEngine, eb, vb,
                                       F, s, d)
        parity = (digest_summaries(base) == digest_summaries(armed)
                  == digest_summaries(twin)
                  and digest_slab(base_slab) == digest_slab(armed_slab)
                  == digest_slab(twin_slab))
        if not parity:
            raise AssertionError(
                "gnn cost section: armed/disarmed/host digests "
                "diverged — the observatory must observe, never "
                "participate")
        rows = [r for r in costmodel.report()
                if (r.get("program") or "").startswith("gnn")]
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        telemetry.reset()
        costmodel.reset()
    return {
        "engine": "gnn_scan",
        "backend": jax.default_backend(),
        "edge_bucket": eb,
        "vertex_bucket": vb,
        "feature_dim": F,
        "num_edges": edges,
        "parity": True,
        "edges_per_s": round(edges / t[0]),
        "gnn_edge_features_per_s": round(edges * F / t[0]),
        "programs": rows,
    }


PROBE_NAMES = ("gnn_engine", "gnn_cohort", "gnn_pallas")


def commit_results(results, backend: str, gnn_section=None) -> None:
    """Merge this run's `gnn_ab` rows (and the `gnn` cost section)
    into the committed evidence — the same policy as
    tools/tenancy_ab.py: PERF.json only when its backend label
    matches the live backend, the per-backend archive
    PERF_<backend>.json always. Merge is BY PROBE."""
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
        kept = [r for r in cur.get("gnn_ab", [])
                if r.get("probe") not in ran]
        cur["gnn_ab"] = kept + results
        if gnn_section is not None:
            cur["gnn"] = gnn_section
        with open(path, "w") as f:
            json.dump(cur, f, indent=2)
        print("committed %d gnn_ab row(s)%s to %s (%d prior row(s) "
              "kept)" % (len(results),
                         " + gnn section" if gnn_section else "",
                         os.path.basename(path), len(kept)),
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
                    default=int(os.environ.get("GS_AB_WINDOWS", 8)),
                    help="windows per stream")
    ap.add_argument("--eb", type=int,
                    default=int(os.environ.get("GS_AB_EB", 512)))
    ap.add_argument("--vb", type=int,
                    default=int(os.environ.get("GS_AB_VB", 1024)))
    ap.add_argument("--feature-dim", type=int,
                    default=int(os.environ.get("GS_AB_F", 16)))
    ap.add_argument("--commit", action="store_true",
                    help="merge rows into PERF.json (backend-matched) "
                         "and PERF_<backend>.json, plus the `gnn` "
                         "cost section")
    args = ap.parse_args()
    bad = [p for p in args.probes if p not in PROBE_NAMES]
    if bad:
        ap.error("unknown probe(s) %s; valid: %s"
                 % (bad, list(PROBE_NAMES)))
    want = args.probes or list(PROBE_NAMES)

    os.environ["GS_AUTOTUNE"] = "0"

    import jax

    eb, vb, F = args.eb, args.vb, args.feature_dim
    results = []
    if "gnn_engine" in want:
        probe_engine(jax, eb, vb, F, args.windows, results)
    if "gnn_cohort" in want:
        for n in sorted({1, 3, args.tenants}):
            probe_cohort(jax, eb, vb, F, args.windows, n, results)
    if "gnn_pallas" in want:
        probe_pallas(jax, eb, vb, F, args.windows, results)
    out = os.path.join(REPO, "logs",
                       "gnn_ab_%s.json" % jax.default_backend())
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote %s" % out, flush=True)
    if args.commit:
        section = gnn_cost_section()
        commit_results(results, jax.default_backend(), section)


if __name__ == "__main__":
    main()

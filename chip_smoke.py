#!/usr/bin/env python
"""Bring-up smoke: the main path once on the attached TPU, checked
against plain numpy references written here.

Two phases, each on data made from ``--seed``:

- driver: ``StreamingAnalyticsDriver`` with all four analytics at the
  bench's 524K/32768 rung (vertex_bucket=65536, edge_bucket=32768,
  16 count windows of ``bench.make_stream``'s power-law stream)
  through ``run_arrays``. A cold call (compiles) and a warm
  call after ``reset()`` are timed separately; every window of both
  must match the reference exactly, and the warm call must compile
  nothing.
- serving: ``TenantCohort(edge_bucket=4096, vertex_bucket=8192)`` as in
  the README quickstart, 8 tenants fed, pumped and closed; every
  tenant's summaries must match the reference.

``--chips 4`` runs only the driver phase, twice on the same stream:
once over ``make_mesh(4)`` and once single-chip, both against the
reference, and checks that the mesh state lives on all four devices.

The references (docs/PARITY.md semantics) import nothing from
``gelly_streaming_tpu``: cumulative ``np.bincount`` degrees, a
union-find with parity for components and two-colouring, and exact
per-window triangles over the distinct, loop-free edges of the window.

The last line of stdout is the JSON verdict, printed only when every
check passed on a TPU. No TPU, a mismatch, an exception, a tier
demotion or a ``selection.fallback`` event exits nonzero without it.
One process holds the chip; no child process is started.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


# ----------------------------------------------------------------------
# numpy references
# ----------------------------------------------------------------------
class _ParityUnionFind:
    """Union-find over vertex ids with the parity of each vertex to its
    parent; a root's `odd` flag marks a component holding an odd
    cycle."""

    def __init__(self, n: int):
        self.parent = list(range(n))
        self.parity = [0] * n
        self.odd = [False] * n

    def _find(self, v: int):
        path = []
        while self.parent[v] != v:
            path.append(v)
            v = self.parent[v]
        root, acc = v, 0
        for u in reversed(path):  # compress, parities relative to root
            acc ^= self.parity[u]
            self.parity[u] = acc
            self.parent[u] = root
        return root

    def union(self, a: int, b: int) -> None:
        ra, rb = self._find(a), self._find(b)
        pa = self.parity[a] if a != ra else 0
        pb = self.parity[b] if b != rb else 0
        if ra == rb:
            if pa == pb:
                self.odd[ra] = True
            return
        self.parent[rb] = ra
        self.parity[rb] = pa ^ pb ^ 1
        self.odd[ra] = self.odd[ra] or self.odd[rb]

    def roots(self) -> np.ndarray:
        p = np.asarray(self.parent, np.int64)
        while True:
            pp = p[p]
            if np.array_equal(pp, p):
                return p
            p = pp


def ref_triangles(s: np.ndarray, d: np.ndarray) -> int:
    """Exact triangles of the window's simple undirected graph:
    distinct loop-free edges oriented by (degree, id), each triangle
    found once as a wedge u→v, u→w closed by the edge {v, w}."""
    s = np.asarray(s, np.int64)
    d = np.asarray(d, np.int64)
    keep = s != d
    s, d = s[keep], d[keep]
    if not len(s):
        return 0
    n = int(max(s.max(), d.max())) + 1
    und = np.unique(np.minimum(s, d) * n + np.maximum(s, d))
    lo, hi = und // n, und % n
    deg = np.bincount(lo, minlength=n) + np.bincount(hi, minlength=n)
    rank = deg * n + np.arange(n)
    fwd = rank[lo] < rank[hi]
    a = np.where(fwd, lo, hi)
    b = np.where(fwd, hi, lo)
    order = np.lexsort((b, a))
    a, b = a[order], b[order]
    starts = np.flatnonzero(np.r_[True, a[1:] != a[:-1]])
    sizes = np.diff(np.r_[starts, len(a)])
    total = 0
    for k in np.unique(sizes[sizes > 1]):
        seg = starts[sizes == k]
        i, j = np.triu_indices(int(k), 1)
        v = b[seg[:, None] + i[None, :]].ravel()
        w = b[seg[:, None] + j[None, :]].ravel()
        key = np.minimum(v, w) * n + np.maximum(v, w)
        total += int(np.isin(key, und, assume_unique=False).sum())
    return total


def _min_member(labels: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Each vertex's component named by its smallest member id, so two
    labelings compare as partitions, not as label values."""
    _, inv = np.unique(labels, return_inverse=True)
    low = np.full(inv.max() + 1, np.iinfo(np.int64).max, np.int64)
    np.minimum.at(low, inv, ids)
    return low[inv]


def ref_driver_windows(src, dst, eb: int, nv: int) -> list:
    """Per count window: touched ids, cumulative degrees, component
    (min member id) and odd-cycle flag of each touched id, and the
    window's own triangles."""
    uf = _ParityUnionFind(nv)
    deg = np.zeros(nv, np.int64)
    out = []
    for lo in range(0, len(src), eb):
        s, d = src[lo:lo + eb], dst[lo:lo + eb]
        deg += np.bincount(s, minlength=nv) + np.bincount(d, minlength=nv)
        for a, b in zip(s.tolist(), d.tolist()):
            uf.union(a, b)
        ids = np.flatnonzero(deg)
        roots = uf.roots()[ids]
        odd = np.asarray(uf.odd, bool)[roots]
        out.append({"ids": ids, "deg": deg[ids],
                    "comp": _min_member(roots, ids), "odd": odd,
                    "triangles": ref_triangles(s, d)})
    return out


def ref_summaries(src, dst, eb: int, nv: int) -> list:
    """The cohort's per-window summaries: max cumulative degree,
    components and odd cycle among touched vertices, and the window's
    triangles."""
    out = []
    for w in ref_driver_windows(src, dst, eb, nv):
        out.append({"max_degree": int(w["deg"].max()),
                    "num_components": len(np.unique(w["comp"])),
                    "odd_cycle": bool(w["odd"].any()),
                    "triangles": w["triangles"]})
    return out


# ----------------------------------------------------------------------
# checks
# ----------------------------------------------------------------------
def check_driver_results(results, refs, label: str) -> None:
    if len(results) != len(refs):
        raise AssertionError("%s: %d windows, reference has %d"
                             % (label, len(results), len(refs)))
    for w, (res, ref) in enumerate(zip(results, refs)):
        ids = np.asarray(res.vertex_ids, np.int64)
        order = np.argsort(ids)
        if not np.array_equal(ids[order], ref["ids"]):
            raise AssertionError("%s window %d: touched vertex set "
                                 "differs" % (label, w))
        got = {
            "deg": np.asarray(res.degrees)[:len(ids)][order],
            "comp": _min_member(np.asarray(res.cc_labels)[:len(ids)],
                                ids)[order],
            "odd": np.asarray(res.bipartite_odd)[:len(ids)][order]
            .astype(bool),
        }
        for key, val in got.items():
            if not np.array_equal(val, ref[key]):
                bad = int(np.sum(val != ref[key]))
                raise AssertionError("%s window %d: %s differs at %d "
                                     "vertices" % (label, w, key, bad))
        if res.triangles != ref["triangles"]:
            raise AssertionError("%s window %d: triangles %s, reference "
                                 "%d" % (label, w, res.triangles,
                                         ref["triangles"]))


class _Guards:
    """Counts compiles and captures the events that mean the path did
    not run as resolved: `selection.fallback` and tier demotions."""

    def __init__(self):
        import jax

        from gelly_streaming_tpu.utils import resilience, telemetry

        self.compiles = 0
        self.fallbacks = []
        self._resilience = resilience

        def on_duration(name, *_a, **_k):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_record(rec):
            if rec.get("name") == "selection.fallback":
                self.fallbacks.append(rec)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        telemetry.register_sink(on_record, lambda: True)

    def check(self) -> None:
        demoted = self._resilience.demotion_events()
        if demoted or self.fallbacks:
            raise AssertionError("path did not run as resolved: "
                                 "demotions=%s fallbacks=%s"
                                 % (demoted, self.fallbacks))


def _block(results) -> None:
    """Results are host arrays already; reading the last one is the
    host read that ends the timed call."""
    if results:
        np.asarray(results[-1].degrees).sum()


def run_driver(src, dst, refs, guards, eb: int, vb: int, mesh=None,
               label="driver"):
    from gelly_streaming_tpu import StreamingAnalyticsDriver
    from gelly_streaming_tpu.core.driver import resolve_snapshot_tier

    drv = StreamingAnalyticsDriver(window_ms=0, mesh=mesh,
                                   vertex_bucket=vb, edge_bucket=eb)
    t0 = time.perf_counter()
    cold = drv.run_arrays(src, dst)
    _block(cold)
    cold_s = time.perf_counter() - t0
    check_driver_results(cold, refs, label + " cold")
    drv.reset()
    before = guards.compiles
    t0 = time.perf_counter()
    warm = drv.run_arrays(src, dst)
    _block(warm)
    warm_s = time.perf_counter() - t0
    check_driver_results(warm, refs, label + " warm")
    warm_compiles = guards.compiles - before
    row = {"phase": label, "windows": len(warm),
           "edges": int(len(src)), "cold_s": cold_s, "warm_s": warm_s,
           "warm_compiles": warm_compiles,
           "snapshot_tier": ("sharded" if mesh is not None
                             else resolve_snapshot_tier())}
    print(json.dumps(row), flush=True)
    if warm_compiles:
        raise AssertionError("%s: the warm call compiled %d programs"
                             % (label, warm_compiles))
    return drv


def run_serving(seed: int, windows: int, eb: int = 4096, vb: int = 8192,
                tenants: int = 8):
    from gelly_streaming_tpu.core.tenancy import TenantCohort

    from bench import make_stream

    co = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    streams = {}
    for i in range(tenants):
        # a ragged tail on every other tenant: close() cuts a partial
        n = windows * eb + (eb // 3 if i % 2 else 0)
        s, d = make_stream(n, vb, seed=seed + 1 + i)
        streams["tenant-%d" % i] = (s.astype(np.int32), d.astype(np.int32))
        co.admit("tenant-%d" % i)
    t0 = time.perf_counter()
    got = {tid: [] for tid in streams}
    for tid, (s, d) in streams.items():
        co.feed(tid, s, d)
    for tid, rows in co.pump().items():
        got[tid].extend(rows)
    for tid in streams:
        got[tid].extend(co.close(tid))
    serve_s = time.perf_counter() - t0
    for tid, (s, d) in streams.items():
        want = ref_summaries(s, d, eb, vb)
        if got[tid] != want:
            raise AssertionError("serving: tenant %s summaries differ "
                                 "from the reference: %s vs %s"
                                 % (tid, got[tid][:2], want[:2]))
    print(json.dumps({"phase": "serving", "tenants": tenants,
                      "windows": {t: len(r) for t, r in got.items()},
                      "wall_s": serve_s,
                      "tier": co.tenant_tier("tenant-0")}), flush=True)


def mesh_peaks(devices) -> list:
    """Peak bytes each mesh device held: the mesh run must have put
    real work on every one of them, not all on device 0."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    if min(peaks) < 1 << 20:
        raise AssertionError("a mesh device held under 1 MiB: %s"
                             % peaks)
    return peaks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    # a demotion must fail the smoke, not quietly pass on a lower tier
    os.environ["GS_TIER_DEMOTE"] = "0"
    sys.path.insert(0, REPO)
    from gelly_streaming_tpu.core.platform import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print("chip_smoke: no TPU (JAX found %r); nothing run"
              % dev.platform, file=sys.stderr)
        return 2
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices), "compile_cache": cache}),
          flush=True)
    if len(devices) < args.chips:
        print("chip_smoke: --chips %d but JAX sees %d devices"
              % (args.chips, len(devices)), file=sys.stderr)
        return 2

    from bench import make_stream

    guards = _Guards()
    eb, vb = 32768, 65536
    src, dst = make_stream(16 * eb, vb, seed=args.seed)
    t0 = time.perf_counter()
    refs = ref_driver_windows(src, dst, eb, vb)
    print(json.dumps({"reference": "driver", "windows": len(refs),
                      "triangles": [r["triangles"] for r in refs],
                      "seconds": time.perf_counter() - t0}), flush=True)

    if args.chips == 4:
        from gelly_streaming_tpu.parallel.mesh import make_mesh

        mesh = make_mesh(4)
        run_driver(src, dst, refs, guards, eb, vb, mesh=mesh,
                   label="driver_mesh4")
        print(json.dumps({"mesh_devices": [d.id for d in devices[:4]],
                          "peak_bytes_in_use": mesh_peaks(devices[:4])}),
              flush=True)
        run_driver(src, dst, refs, guards, eb, vb, label="driver_1chip")
    else:
        run_driver(src, dst, refs, guards, eb, vb)
        run_serving(args.seed, windows=4)
    guards.check()
    stats = dev.memory_stats() or {}
    print(json.dumps({"peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                      "compiles": guards.compiles}), flush=True)
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

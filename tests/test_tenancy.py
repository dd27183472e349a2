"""Multi-tenant cohort scheduler suite (core/tenancy.py):

- padded-cohort parity: N ragged tenant streams (full + partial
  windows, different lengths) through the vmapped cohort equal N
  sequential StreamSummaryEngine runs window by window;
- the 1-tenant digest pin: a cohort of one IS the single-stream
  engine, bit for bit (the ci_check smoke's in-suite twin);
- admission semantics: GS_TENANT_MAX typed rejection, duplicate and
  unknown ids, closed-tenant feeds;
- backpressure: bounded queue overflow → typed TenantBackpressure
  (`reject`) or counted shedding (`drop`), capacity = queue windows
  x edge bucket;
- per-tenant demotion: one sick tenant falls to its own single-tenant
  engine (tenant-labeled demotion event) while the cohort keeps
  dispatching — results unchanged;
- per-tenant vertex buckets: mixed-bucket cohorts dispatch per bucket
  group with exact parity;
- tenants-per-dispatch: a pinned GS_TENANT_TPD splits rounds into
  several vmapped dispatches (ingest-ring lookahead path) with
  identical results;
- the windowed-reduce cohort leg: WindowedEdgeReduce.cohort_step over
  N tenant windows equals each tenant's own single-window reduce.
"""

import numpy as np
import pytest

from bench import make_stream
from gelly_streaming_tpu.core import tenancy
from gelly_streaming_tpu.core.tenancy import (
    TenantBackpressure, TenantCohort, TenantRejected)
from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine
from gelly_streaming_tpu.utils import resilience

EB, VB = 128, 256


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    from gelly_streaming_tpu.ops import pallas_window

    for k in ("GS_TENANT_MAX", "GS_TENANT_QUEUE_WINDOWS",
              "GS_TENANT_ADMISSION", "GS_TENANT_TPD", "GS_AUTOTUNE",
              "GS_COHORT_RESIDENT", "GS_COHORT_PALLAS"):
        monkeypatch.delenv(k, raising=False)
    monkeypatch.setenv("GS_AUTOTUNE", "0")
    resilience.reset_demotions()
    pallas_window._reset_pallas_window()
    yield
    resilience.reset_demotions()
    pallas_window._reset_pallas_window()


def streams_for(n, windows=4, eb=EB, vb=VB, ragged=True):
    out = {}
    for i in range(n):
        edges = windows * eb
        if ragged and i % 2 == 1:
            edges -= eb // 3  # partial final window
        s, d = make_stream(edges, vb, seed=60 + i)
        out["t%d" % i] = (s.astype(np.int32), d.astype(np.int32))
    return out


def oracle(streams, eb=EB, vb=VB):
    return {tid: StreamSummaryEngine(edge_bucket=eb,
                                     vertex_bucket=vb).process(s, d)
            for tid, (s, d) in streams.items()}


def run_cohort(streams, eb=EB, vb=VB, piece=None, co=None,
               admit_vb=None):
    co = co or TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    for tid in streams:
        if tid not in co.tenants:
            co.admit(tid, vertex_bucket=(admit_vb or {}).get(tid))
    out = {tid: [] for tid in streams}
    cursors = {tid: 0 for tid in streams}
    piece = piece or 2 * eb
    live = True
    while live:
        live = False
        for tid, (s, d) in streams.items():
            c = cursors[tid]
            if c >= len(s):
                continue
            co.feed(tid, s[c:c + piece], d[c:c + piece])
            cursors[tid] = min(len(s), c + piece)
            live = True
        for tid, res in co.pump().items():
            out[tid].extend(res)
    for tid in streams:
        out[tid].extend(co.close(tid))
    return out, co


# ----------------------------------------------------------------------
# parity
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_tenants", [1, 3, 8])
def test_cohort_parity_vs_sequential_oracle(n_tenants):
    """Ragged cohorts (different stream lengths, partial tails, pad
    rows on non-power-of-two populations) reproduce N sequential
    single-tenant engines exactly, window by window."""
    streams = streams_for(n_tenants)
    want = oracle(streams)
    got, _co = run_cohort(streams)
    assert got == want


def test_one_tenant_cohort_is_the_single_stream_engine():
    """The digest pin the ci_check smoke enforces: a 1-tenant cohort
    must be indistinguishable from StreamSummaryEngine on the same
    stream, including the partial final window."""
    n = 3 * EB + EB // 4
    s, d = make_stream(n, VB, seed=7)
    s, d = s.astype(np.int32), d.astype(np.int32)
    want = StreamSummaryEngine(edge_bucket=EB,
                               vertex_bucket=VB).process(s, d)
    got, _co = run_cohort({"solo": (s, d)})
    assert got["solo"] == want


def test_ragged_window_counts_within_one_pump():
    """Tenants with unequal queue depths in ONE pump: the slab pads
    the window axis per tenant and drops padded summaries."""
    streams = streams_for(2, ragged=False)
    s0, d0 = streams["t0"]
    s1, d1 = streams["t1"]
    want = oracle(streams)
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("t0")
    co.admit("t1")
    co.feed("t0", s0, d0)           # 4 windows deep
    co.feed("t1", s1[:EB], d1[:EB])  # 1 window deep
    out = co.pump()
    assert len(out["t0"]) == 4 and len(out["t1"]) == 1
    co.feed("t1", s1[EB:], d1[EB:])
    out2 = co.pump()
    assert out["t0"] + out2.get("t0", []) == want["t0"]
    assert out["t1"] + out2["t1"] == want["t1"]


def test_per_tenant_vertex_buckets_group_dispatch():
    """Tenants declaring different vertex buckets land in separate
    bucket groups (one slab per group) with exact per-tenant parity."""
    small = streams_for(2, vb=VB, ragged=True)
    big_s, big_d = make_stream(3 * EB, 2 * VB, seed=91)
    streams = dict(small, big=(big_s.astype(np.int32),
                               big_d.astype(np.int32)))
    want = oracle(small)
    want["big"] = StreamSummaryEngine(
        edge_bucket=EB, vertex_bucket=2 * VB).process(*streams["big"])
    got, co = run_cohort(streams, admit_vb={"big": 2 * VB})
    assert got == want
    assert co.tenants["big"].vb == 2 * VB


def test_pinned_tenants_per_dispatch_batches(monkeypatch):
    """GS_TENANT_TPD=2 over 5 tenants: every round splits into three
    vmapped dispatches (ingest-ring lookahead prep) — identical
    results, and the ring actually saw work."""
    monkeypatch.setenv("GS_TENANT_TPD", "2")
    streams = streams_for(5)
    want = oracle(streams)
    got, _co = run_cohort(streams)
    assert got == want


# ----------------------------------------------------------------------
# admission / backpressure
# ----------------------------------------------------------------------
def test_admission_cap_typed_rejection(monkeypatch):
    monkeypatch.setenv("GS_TENANT_MAX", "2")
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("a")
    co.admit("b")
    with pytest.raises(TenantRejected) as ei:
        co.admit("c")
    assert ei.value.tenant == "c"
    assert "GS_TENANT_MAX" in str(ei.value)


def test_duplicate_unknown_and_closed_are_typed():
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("a")
    with pytest.raises(TenantRejected):
        co.admit("a")
    with pytest.raises(TenantRejected):
        co.feed("ghost", [0], [1])
    co.close("a")
    with pytest.raises(TenantRejected):
        co.feed("a", [0], [1])


def test_backpressure_reject_is_atomic(monkeypatch):
    """Overflow under the default `reject` policy raises typed
    TenantBackpressure carrying queued/capacity and accepts NOTHING
    (a half-accepted feed could split a window across a retry)."""
    monkeypatch.setenv("GS_TENANT_QUEUE_WINDOWS", "2")
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("a")
    s, d = make_stream(2 * EB, VB, seed=1)
    assert co.feed("a", s, d) == 2 * EB
    with pytest.raises(TenantBackpressure) as ei:
        co.feed("a", s[:1], d[:1])
    assert ei.value.queued == 2 * EB
    assert ei.value.capacity == 2 * EB
    assert co.queued_edges("a") == 2 * EB  # nothing was accepted
    co.pump()  # draining the queue reopens the tenant
    assert co.feed("a", s[:1], d[:1]) == 1


def test_backpressure_drop_sheds_and_counts(monkeypatch):
    monkeypatch.setenv("GS_TENANT_QUEUE_WINDOWS", "1")
    monkeypatch.setenv("GS_TENANT_ADMISSION", "drop")
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("a")
    s, d = make_stream(2 * EB, VB, seed=2)
    assert co.feed("a", s, d) == EB  # capacity = 1 window
    assert co.tenants["a"].dropped_edges == EB
    # the accepted prefix still folds exactly
    want = StreamSummaryEngine(
        edge_bucket=EB, vertex_bucket=VB).process(s[:EB], d[:EB])
    assert co.pump()["a"] == want


def test_closed_partial_resume_refuses_more_stream(tmp_path):
    """The engines' partial-window-must-be-final guard holds across a
    checkpoint: a tenant restored AFTER its short final window was
    cut cannot fold more windows on a misaligned carry — feed()
    raises the same ValueError StreamSummaryEngine does."""
    from gelly_streaming_tpu.utils import checkpoint as ck

    s, d = make_stream(EB + EB // 4, VB, seed=3)
    s, d = s.astype(np.int32), d.astype(np.int32)
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("a")
    co.feed("a", s, d)
    co.close("a")
    path = str(tmp_path / "a.npz")
    ck.save(path, co.tenant_state_dict("a"))

    co2 = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co2.admit("a")
    co2.load_tenant_state_dict("a", ck.restore(path))
    with pytest.raises(ValueError, match="partial window"):
        co2.feed("a", s[:1], d[:1])


def test_close_drains_only_the_closing_tenant():
    """close() must never consume another tenant's queued windows —
    its caller only reads one stream, so a sibling's summaries would
    be silently lost."""
    streams = streams_for(2, ragged=False)
    want = oracle(streams)
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("t0")
    co.admit("t1")
    co.feed("t0", *streams["t0"])
    co.feed("t1", *streams["t1"])
    got0 = co.close("t0")
    assert got0 == want["t0"]
    # t1's windows are still queued, delivered by the next pump
    assert co.queued_edges("t1") == len(streams["t1"][0])
    got1 = co.pump()["t1"] + co.close("t1")
    assert got1 == want["t1"]


def test_backpressure_durable_stamp_once_per_episode(monkeypatch):
    """A producer retry loop against a full queue must not fsync per
    attempt: the first overflow of an episode stamps durable, repeats
    stamp buffered, and a drain opens a new episode."""
    monkeypatch.setenv("GS_TENANT_QUEUE_WINDOWS", "1")
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("a")
    s, d = make_stream(EB, VB, seed=5)
    co.feed("a", s, d)
    for _ in range(3):
        with pytest.raises(TenantBackpressure):
            co.feed("a", s[:1], d[:1])
    assert co.tenants["a"].bp_stamped is True
    co.pump()  # drain resets the episode
    assert co.tenants["a"].bp_stamped is False


def test_unknown_id_introspection_does_not_count_rejections():
    """A typo'd id in read-only introspection raises the typed error
    WITHOUT stamping ledger events or rejection counters (only the
    serving surface — feed — records unknown-tenant refusals)."""
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    with pytest.raises(TenantRejected):
        co.tenant_tier("ghost")


def test_cohort_step_mixed_dtypes_promote():
    """A wider row in the cohort must not be truncated to the first
    row's dtype: the shared value buffer takes the promoted dtype."""
    from gelly_streaming_tpu.ops.windowed_reduce import (
        WindowedEdgeReduce)

    eng = WindowedEdgeReduce(vertex_bucket=VB, edge_bucket=EB,
                             name="sum", direction="out")
    s, d = make_stream(EB, VB, seed=8)
    vi = np.ones(EB, np.int64)
    vf = np.full(EB, 0.5, np.float64)
    got = eng.cohort_step([(s, d, vi), (s, d, vf)])
    want_f = eng.process_stream(s, d, vf)[0]
    touched = np.asarray(want_f[1]) > 0
    np.testing.assert_allclose(
        np.asarray(got[1][0])[touched],
        np.asarray(want_f[0])[touched])


def test_feed_validates_ids_against_the_bucket():
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("a")
    with pytest.raises(ValueError, match="dense in"):
        co.feed("a", [VB], [0])
    with pytest.raises(ValueError, match="dense in"):
        co.feed("a", [0], [-1])


# ----------------------------------------------------------------------
# demotion
# ----------------------------------------------------------------------
def test_demoted_tenant_runs_single_while_cohort_dispatches():
    """Mid-stream demotion of one tenant: its remaining windows run on
    its OWN StreamSummaryEngine (seeded from the live carry — exact),
    the others stay on the vmapped cohort, and every tenant's summary
    stream still equals the sequential oracle. The demotion event
    carries the tenant label."""
    streams = streams_for(3)
    want = oracle(streams)
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    out = {tid: [] for tid in streams}
    for tid in streams:
        co.admit(tid)
    # first half
    for tid, (s, d) in streams.items():
        co.feed(tid, s[:2 * EB], d[:2 * EB])
    for tid, res in co.pump().items():
        out[tid].extend(res)
    co.demote("t1", reason="test drill")
    assert co.tenant_tier("t1") == "single"
    assert co.tenant_tier("t0") == "cohort"
    evs = [e for e in resilience.demotion_events()
           if e.get("tenant") == "t1"]
    assert evs and evs[0]["from"] == "cohort" \
        and evs[0]["to"] == "single"
    # rest of the streams
    for tid, (s, d) in streams.items():
        co.feed(tid, s[2 * EB:], d[2 * EB:])
    for tid, res in co.pump().items():
        out[tid].extend(res)
    for tid in streams:
        out[tid].extend(co.close(tid))
    assert out == want


def test_poisoned_prep_demotes_only_the_sick_tenant():
    """An injected per-tenant prep fault isolates: the poisoned tenant
    demotes (and its queued windows replay on the single tier), the
    other tenants' summaries are untouched — the chaos tenant leg's
    in-suite twin."""
    from gelly_streaming_tpu.utils import faults

    streams = streams_for(3, ragged=False)
    want = oracle(streams)
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    for tid in streams:
        co.admit(tid)
    out = {tid: [] for tid in streams}
    for tid, (s, d) in streams.items():
        co.feed(tid, s, d)
    # round 1 preps tenants in sorted order: call 2 poisons t1
    with faults.inject(faults.FaultSpec(site="tenant_prep",
                                        on_call=2)):
        for tid, res in co.pump().items():
            out[tid].extend(res)
    assert co.tenant_tier("t1") == "single"
    for tid in streams:
        out[tid].extend(co.close(tid))
    assert out == want


# ----------------------------------------------------------------------
# windowed-reduce cohort leg
# ----------------------------------------------------------------------
@pytest.mark.parametrize("name,direction", [("sum", "out"),
                                            ("min", "in"),
                                            ("max", "all")])
def test_windowed_reduce_cohort_step_parity(name, direction):
    """ops/windowed_reduce.cohort_step: N tenants' windows as one
    [N, eb] stack dispatch — counts identical, touched cells value-
    identical to each tenant's own reduce (count-0 cells compare by
    count, the repo-wide convention)."""
    from gelly_streaming_tpu.ops.windowed_reduce import (
        WindowedEdgeReduce)

    eng = WindowedEdgeReduce(vertex_bucket=VB, edge_bucket=EB,
                             name=name, direction=direction)
    rows, singles = [], []
    for i in range(5):
        n = EB if i != 3 else EB - 17
        s, d = make_stream(n, VB, seed=70 + i)
        val = (1 + (s + 3 * d) % 13).astype(np.int64)
        rows.append((s, d, val))
        singles.append(eng.process_stream(s, d, val)[0])
    got = eng.cohort_step(rows)
    assert len(got) == 5
    for (gc, gn), (sc, sn) in zip(got, singles):
        gn, sn = np.asarray(gn), np.asarray(sn)
        np.testing.assert_array_equal(gn, sn)
        touched = sn > 0
        np.testing.assert_array_equal(
            np.asarray(gc)[touched].astype(np.int64),
            np.asarray(sc)[touched].astype(np.int64))


def test_cohort_step_rejects_user_fn_and_oversize():
    from gelly_streaming_tpu.ops.windowed_reduce import (
        WindowedEdgeReduce)

    eng = WindowedEdgeReduce(vertex_bucket=VB, edge_bucket=EB,
                             fn=lambda a, b: a + b)
    with pytest.raises(ValueError, match="monoid"):
        eng.cohort_step([(np.zeros(1, np.int64),) * 3])
    eng2 = WindowedEdgeReduce(vertex_bucket=VB, edge_bucket=EB)
    big = np.zeros(EB + 1, np.int64)
    with pytest.raises(ValueError, match="exceed"):
        eng2.cohort_step([(big, big, big)])


def test_tenants_per_dispatch_tuner_arm(monkeypatch, tmp_path):
    """With the online tuner live, the cohort's tenant_cohort family
    owns a tenants-per-dispatch arm: rounds record measured edges/s,
    summaries stay identical at every arm (hermetic cache)."""
    monkeypatch.setenv("GS_AUTOTUNE", "1")
    monkeypatch.setenv("GS_TUNE_CACHE", str(tmp_path))
    streams = streams_for(4)
    want = oracle(streams)
    got, co = run_cohort(streams, piece=EB)
    assert got == want
    tuner = co._tuner(VB)
    assert tuner is not None
    summary = tuner.summary()
    assert summary["rounds"] >= 1
    assert "tpd" in summary["chosen"]


# ----------------------------------------------------------------------
# cohort-aware event-time guard
# ----------------------------------------------------------------------
def test_event_time_interleaved_disjoint_ranges_ok():
    """The regression the guard exists to avoid regressing INTO: two
    tenants with disjoint, interleaved time ranges share slabs all
    run long — monotonicity is per tenant, never per slab — and the
    results still match the oracle exactly."""
    streams = streams_for(2, ragged=False)
    want = oracle(streams)
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("t0")
    co.admit("t1")
    out = {"t0": [], "t1": []}
    piece = EB
    # t0 lives around epoch 1_000_000, t1 around 500 — every feed
    # round interleaves the two clocks in one admission boundary
    for lo in range(0, 4 * EB, piece):
        for tid, base in (("t0", 1_000_000), ("t1", 500)):
            s, d = streams[tid]
            if lo >= len(s):
                continue
            hi = min(lo + piece, len(s))
            co.feed(tid, s[lo:hi], d[lo:hi],
                    ts=np.arange(base + lo, base + hi, dtype=np.int64))
        for tid, res in co.pump().items():
            out[tid].extend(res)
    for tid in streams:
        out[tid].extend(co.close(tid))
    assert out == want


def test_event_time_regression_refuses_atomically():
    """A per-tenant event-time regression — within a batch or against
    the tenant's newest accepted stamp — refuses the WHOLE batch for
    that tenant only, consuming nothing; the other tenant's clock is
    untouched."""
    streams = streams_for(2, ragged=False)
    s0, d0 = streams["t0"]
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    co.admit("t0")
    co.admit("t1")
    # non-monotone WITHIN one batch
    bad = np.arange(EB, dtype=np.int64)
    bad[EB // 2] = 0
    with pytest.raises(ValueError, match="WITHIN the batch"):
        co.feed("t0", s0[:EB], d0[:EB], ts=bad)
    assert co.queued_edges("t0") == 0  # nothing consumed
    # accept a clean batch ending at ts=EB-1 ...
    co.feed("t0", s0[:EB], d0[:EB],
            ts=np.arange(EB, dtype=np.int64))
    # ... then a batch starting BEFORE it: refused, naming the tenant
    with pytest.raises(ValueError, match="t0.*already reached"):
        co.feed("t0", s0[EB:2 * EB], d0[EB:2 * EB],
                ts=np.arange(EB // 2, EB // 2 + EB, dtype=np.int64))
    assert co.queued_edges("t0") == EB
    # t1's clock is independent: far-past stamps are fine
    s1, d1 = streams["t1"]
    assert co.feed("t1", s1[:EB], d1[:EB],
                   ts=np.arange(EB, dtype=np.int64)) == EB


# ----------------------------------------------------------------------
# resident cohort tier (GS_COHORT_RESIDENT)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n_tenants", [1, 3, 8])
def test_resident_cohort_parity(monkeypatch, n_tenants):
    """Pinned on, the donated stacked-carry super-batch program must
    reproduce the scan-tier cohort (and thus the N sequential
    engines) exactly — and must have actually dispatched through the
    resident path."""

    streams = streams_for(n_tenants)
    want = oracle(streams)
    monkeypatch.setenv("GS_COHORT_RESIDENT", "on")
    got, co = run_cohort(streams, piece=EB)
    assert got == want
    assert co.resident_dispatches > 0, \
        "resident tier pinned on but never dispatched"


def test_resident_cohort_defaults_off_digest_identical(monkeypatch):
    """GS_COHORT_RESIDENT unset: the dispatch plan is the scan-tier
    cohort's, and the pinned resident tier reproduces its results
    bit-identically."""

    streams = streams_for(3)
    base, co0 = run_cohort(streams)
    assert co0.resident_dispatches == 0
    monkeypatch.setenv("GS_COHORT_RESIDENT", "on")
    got, _co = run_cohort(streams)
    assert got == base


def test_resident_stack_replacement_never_strands_a_carry(monkeypatch):
    """Regression pin: a membership-changed dispatch must evict the
    WHOLE stale resident stack before committing its replacement.
    The bug: staggered stream lengths shrink the batch (t0/t1 drain
    first), then close(t1) dispatches a one-tenant batch whose commit
    replaced the stack while t3 still held a res_row into it — t3's
    final partial window then folded onto a pad row's fresh carry
    instead of its own, silently wrong analytics."""

    rng = np.random.default_rng(7)
    streams = {}
    for i in range(4):
        edges = EB * (3 + i) - (EB // 3 if i % 2 else 0)
        streams["t%d" % i] = (
            rng.integers(0, VB, edges).astype(np.int32),
            rng.integers(0, VB, edges).astype(np.int32))
    want = oracle(streams)
    monkeypatch.setenv("GS_COHORT_RESIDENT", "on")
    # piece=2*EB staggers exhaustion so the batch membership churns
    # across rounds before the per-tenant closes cut the tails
    got, co = run_cohort(streams, piece=2 * EB)
    assert co.resident_dispatches > 0
    assert got == want


def test_resolve_resident_cohort_pins_and_gate(monkeypatch):
    from gelly_streaming_tpu.ops import resident_engine

    monkeypatch.setenv("GS_COHORT_RESIDENT", "on")
    assert resident_engine.resolve_resident_cohort() is True
    monkeypatch.setenv("GS_COHORT_RESIDENT", "off")
    assert resident_engine.resolve_resident_cohort() is False
    monkeypatch.delenv("GS_COHORT_RESIDENT")
    assert resident_engine.resolve_resident_cohort() is False


def test_tuner_rekeys_on_cohort_size_bucket(monkeypatch, tmp_path):
    """The Nb bugfix pin: the tuner family key includes the cohort
    size bucket, so a grown cohort gets a fresh family (stale
    tenants-per-dispatch EMAs measured at old N can't steer the new
    population) — and the persisted best re-seeds the new key."""
    monkeypatch.setenv("GS_AUTOTUNE", "1")
    monkeypatch.setenv("GS_TUNE_CACHE", str(tmp_path))
    streams = streams_for(2, ragged=False)
    co = TenantCohort(edge_bucket=EB, vertex_bucket=VB)
    for tid, (s, d) in streams.items():
        co.admit(tid)
        co.feed(tid, s[:EB], d[:EB])
    co.pump()
    t1 = co._tuner(VB)
    assert t1.key.endswith(":N=8")  # bucket floor
    assert max(t1.space["tpd"]) == 8
    # grow the cohort past the bucket (8 → 16): the SAME cohort
    # object must rekey its family rather than keep tuning the N=8
    # arms on the new program shape
    s, d = streams["t0"]
    for i in range(10, 18):
        co.admit("t%d" % i)
        co.feed("t%d" % i, s[:EB], d[:EB])
    co.pump()
    t2 = co._tuner(VB)
    assert t2 is t1, "rekey must mutate the family, not fork it"
    assert t2.key.endswith(":N=16")  # bucket_size(10 live tenants)
    assert t2 is co._tuner(VB)  # stable until the bucket moves again
    # arms on the new family stay within ITS space
    assert set(t2.space) >= {"tpd"}
    assert max(t2.space["tpd"]) == 16


# ----------------------------------------------------------------------
# knob plumbing
# ----------------------------------------------------------------------
def test_tenancy_knob_readers(monkeypatch):
    assert tenancy.max_tenants() == 64
    assert tenancy.queue_windows() == 8
    assert tenancy.admission_policy() == "reject"
    assert tenancy.pinned_tpd() == 0
    monkeypatch.setenv("GS_TENANT_MAX", "3")
    monkeypatch.setenv("GS_TENANT_ADMISSION", "drop")
    assert tenancy.max_tenants() == 3
    assert tenancy.admission_policy() == "drop"

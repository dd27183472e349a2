"""Fused Pallas window megakernel (ops/pallas_window.py): interpret-
mode parity against the host twins across all four analytics (the
524K/32768 acceptance row included), ragged window tails, vb/eb
bucket boundaries, the K-overflow exact-redo handoff, the
GS_PALLAS_WINDOW pin (default off = the XLA body's digests
unchanged), the trace-failure fallback chaos leg (durable
`selection.fallback`, stream survives), the VMEM-budget `supports`
gate, the tile tuner family, and the analytic cost-model
registration (one slab read strictly below the scan-of-gathers
bytes)."""

import hashlib
import json

import numpy as np
import pytest

from gelly_streaming_tpu.ops import pallas_window as pw
from gelly_streaming_tpu.ops import triangles as tri_ops
from gelly_streaming_tpu.ops.resident_engine import (
    ResidentSummaryEngine)
from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine
from gelly_streaming_tpu.parallel.host_twin import HostSummaryEngine
from gelly_streaming_tpu.utils import telemetry


@pytest.fixture
def pallas_on(monkeypatch):
    monkeypatch.setenv("GS_PALLAS_WINDOW", "on")
    monkeypatch.setenv("GS_AUTOTUNE", "0")
    pw._reset_pallas_window()
    yield
    pw._reset_pallas_window()


@pytest.fixture
def pallas_unset(monkeypatch):
    monkeypatch.delenv("GS_PALLAS_WINDOW", raising=False)
    pw._reset_pallas_window()
    yield
    pw._reset_pallas_window()


def _stream(n, v, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, v, n).astype(np.int32),
            rng.integers(0, v, n).astype(np.int32))


def _digest(summaries) -> str:
    h = hashlib.sha256()
    for s in summaries:
        h.update(json.dumps(s, sort_keys=True).encode())
    return h.hexdigest()


# ----------------------------------------------------------------------
# parity: megakernel ≡ XLA scan ≡ host twin
# ----------------------------------------------------------------------
def test_engine_parity_all_analytics_ragged_tail(pallas_on):
    """All four analytics (degrees, CC, bipartiteness, triangles)
    through the engine, with a ragged trailing window."""
    src, dst = _stream(5 * 256 - 37, 200)
    eng = StreamSummaryEngine(edge_bucket=256, vertex_bucket=256)
    assert eng._pallas, "megakernel body not selected under pin"
    out = eng.process(src, dst)
    host = HostSummaryEngine(edge_bucket=256,
                             vertex_bucket=256).process(src, dst)
    assert out == host
    # every analytic actually exercised
    assert any(s["triangles"] for s in out)
    assert any(s["odd_cycle"] for s in out)
    assert out[-1]["max_degree"] >= out[0]["max_degree"]


def test_resident_engine_compact_fused_parity(pallas_on):
    """The resident tier's compact twin decodes uint16 IN-kernel —
    summaries must still match the host twin exactly."""
    src, dst = _stream(2048, 180, seed=2)
    eng = ResidentSummaryEngine(edge_bucket=256, vertex_bucket=256)
    assert eng._pallas and eng.ingress == "compact"
    host = HostSummaryEngine(edge_bucket=256,
                             vertex_bucket=256).process(src, dst)
    assert eng.process(src, dst) == host


def test_stream_counter_parity(pallas_on):
    src, dst = _stream(4 * 256, 150, seed=3)
    on = tri_ops.TriangleWindowKernel(edge_bucket=256,
                                      vertex_bucket=256)
    assert on._pallas_counter
    got = on._count_stream_device(src, dst)
    from gelly_streaming_tpu.ops import host_triangles

    assert got == host_triangles.count_stream(src, dst, 256)


def test_acceptance_524k_row(pallas_on):
    """The acceptance pin: interpret-mode megakernel output is
    sha256-bit-identical to the host twins on the canonical
    524K/32768 row (eb=32768, vb=65536) — all four analytics."""
    src, dst = _stream(524_288, 60_000, seed=7)
    eng = StreamSummaryEngine(edge_bucket=32768, vertex_bucket=65536)
    assert eng._pallas
    got = _digest(eng.process(src, dst))
    host = HostSummaryEngine(edge_bucket=32768, vertex_bucket=65536)
    assert got == _digest(host.process(src, dst))


def test_bucket_boundaries(pallas_on):
    """vb at the uint16 ceiling (compact fused) and past it (standard
    fallback wire), and the minimum edge bucket."""
    src, dst = _stream(512, 60, seed=4)
    for eb, vb in ((8, 65536), (8, 131072), (256, 131072)):
        eng = ResidentSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
        assert eng._pallas
        want = "compact" if vb <= 65536 else "standard"
        assert eng.ingress == want
        host = HostSummaryEngine(edge_bucket=eb, vertex_bucket=vb)
        assert eng.process(src, dst) == host.process(src, dst)


def test_k_overflow_exact_redo_handoff(pallas_on):
    """A hub whose oriented out-degree outruns K must (a) raise the
    kernel's overflow signal and (b) come back EXACT through the call
    site's escalating redo."""
    import jax
    import jax.numpy as jnp

    v, eb, kb = 128, 128, 8
    # complete graph K14: every vertex has equal degree, so the
    # (degree, id) orientation gives vertex 0 an out-degree of 13 >
    # kb=8 (a low-degree hub would orient INWARD and never overflow)
    m = 14
    ks, kd = np.triu_indices(m, k=1)
    extra_s, extra_d = _stream(200, v, seed=5)
    src = np.concatenate([ks.astype(np.int32), extra_s])
    dst = np.concatenate([kd.astype(np.int32), extra_d])
    # the kernel itself must report the overflow (else this test is
    # vacuous and the redo path untested)
    body = pw.maybe_window_body(eb, vb := 128, kb)
    assert body is not None
    carry = (jnp.zeros(vb + 1, jnp.int32),
             jnp.arange(vb + 1, dtype=jnp.int32),
             jnp.arange(2 * (vb + 1), dtype=jnp.int32))
    from gelly_streaming_tpu.ops import segment as seg_ops

    _w, s, d, valid = seg_ops.window_stack(src, dst, eb, sentinel=vb)
    _c, ys = jax.jit(lambda c, a, b, m: jax.lax.scan(
        body, c, (a, b, m)))(carry, jnp.asarray(s), jnp.asarray(d),
                             jnp.asarray(valid))
    assert int(np.asarray(ys[4]).sum()) > 0, "hub did not overflow K"
    eng = StreamSummaryEngine(edge_bucket=eb, vertex_bucket=128,
                              k_bucket=kb)
    assert eng._pallas
    host = HostSummaryEngine(edge_bucket=eb, vertex_bucket=128,
                             k_bucket=kb)
    assert eng.process(src, dst) == host.process(src, dst)


def test_cohort_scan_stays_xla_with_parity(pallas_on, monkeypatch):
    """build_cohort_scan opts out (vmap-of-pallas is its own future
    evidence) ALL the way down — pallas_ok=False must also reach the
    embedded triangle counter, or a pallas_call smuggles into the
    vmapped body anyway — and per-tenant results still match the
    megakernel engine exactly."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort
    from gelly_streaming_tpu.ops import scan_analytics as sa

    # spy: nothing in a pallas_ok=False build may consult the
    # megakernel selectors
    calls = []
    real_body, real_ctr = pw.maybe_window_body, pw.maybe_counter
    monkeypatch.setattr(
        pw, "maybe_window_body",
        lambda *a, **k: calls.append("body") or real_body(*a, **k))
    monkeypatch.setattr(
        pw, "maybe_counter",
        lambda *a, **k: calls.append("ctr") or real_ctr(*a, **k))
    sa._build_scan(256, 256, 16, pallas_ok=False)
    assert calls == [], "pallas selector consulted despite opt-out"

    src, dst = _stream(1024, 100, seed=6)
    cohort = TenantCohort(edge_bucket=256, vertex_bucket=256)
    cohort.admit("t0")
    cohort.feed("t0", src, dst)
    outs = cohort.pump()
    single = StreamSummaryEngine(edge_bucket=256,
                                 vertex_bucket=256)
    assert single._pallas
    assert outs["t0"] == single.process(src, dst)


# ----------------------------------------------------------------------
# the pin
# ----------------------------------------------------------------------
def test_gate_default_off_digests_unchanged(pallas_unset):
    """GS_PALLAS_WINDOW unset: the XLA body is selected, and the
    pinned megakernel reproduces its digests bit-for-bit."""
    src, dst = _stream(1024, 120, seed=8)
    eng = StreamSummaryEngine(edge_bucket=256, vertex_bucket=256)
    assert not eng._pallas
    base = _digest(eng.process(src, dst))
    kern = tri_ops.TriangleWindowKernel(edge_bucket=256,
                                        vertex_bucket=256)
    assert not kern._pallas_counter
    counts = kern._count_stream_device(src, dst)

    import os

    os.environ["GS_PALLAS_WINDOW"] = "on"
    pw._reset_pallas_window()
    try:
        eng2 = StreamSummaryEngine(edge_bucket=256,
                                   vertex_bucket=256)
        assert eng2._pallas
        assert _digest(eng2.process(src, dst)) == base
        kern2 = tri_ops.TriangleWindowKernel(edge_bucket=256,
                                             vertex_bucket=256)
        assert kern2._count_stream_device(src, dst) == counts
    finally:
        os.environ.pop("GS_PALLAS_WINDOW", None)
        pw._reset_pallas_window()


def test_resolve_pins(monkeypatch):
    monkeypatch.setenv("GS_PALLAS_WINDOW", "on")
    assert pw.resolve_pallas_window() is True
    monkeypatch.setenv("GS_PALLAS_WINDOW", "off")
    assert pw.resolve_pallas_window() is False
    monkeypatch.delenv("GS_PALLAS_WINDOW")
    assert pw.resolve_pallas_window() is False


# ----------------------------------------------------------------------
# fallback legs (the chaos contract)
# ----------------------------------------------------------------------
def test_trace_failure_falls_back_with_durable_event(monkeypatch):
    """pallas_call raising at build/trace time must degrade to the
    XLA scan with a durable selection.fallback event — the stream
    keeps running, results stay exact."""
    monkeypatch.setenv("GS_PALLAS_WINDOW", "on")
    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.delenv("GS_TRACE_DIR", raising=False)
    pw._reset_pallas_window()
    telemetry.reset()

    def boom(*a, **k):
        raise RuntimeError("mosaic said no")

    monkeypatch.setattr(pw.pl, "pallas_call", boom)
    pw._CALLS.clear()
    try:
        src, dst = _stream(512, 90, seed=9)
        eng = StreamSummaryEngine(edge_bucket=256, vertex_bucket=256)
        assert not eng._pallas  # fell back to the XLA body
        out = eng.process(src, dst)
        host = HostSummaryEngine(edge_bucket=256, vertex_bucket=256)
        assert out == host.process(src, dst)
        evs = [r for r in telemetry.records()
               if r["name"] == "selection.fallback"
               and r["a"].get("component") == "pallas_window"]
        assert evs, "no durable selection.fallback event"
        assert "mosaic said no" in evs[0]["a"]["error"]
    finally:
        pw._CALLS.clear()
        pw._reset_pallas_window()
        telemetry.reset()


@pytest.mark.parametrize("pin, build", [
    ("GS_PALLAS_WINDOW", lambda: pw.maybe_window_body(256, 256, 16)),
    ("GS_PALLAS_WINDOW",
     lambda: pw.maybe_counter(256, 16, lambda *a: None)),
    ("GS_COHORT_PALLAS", lambda: pw.maybe_cohort_body(256, 256, 16, 4)),
    ("GS_GNN_PALLAS", lambda: pw.maybe_gnn_body(256, 256, 16, "relu")),
], ids=["window", "counter", "cohort", "gnn"])
def test_pinned_kernel_that_cannot_build_raises(monkeypatch, pin, build):
    """Pinned `on` and built for the chip (interpret off), a kernel the
    compiler refuses raises PallasUnavailable — it never quietly runs
    the XLA body instead. Here the refusal is the CPU backend's own:
    it lowers Pallas in interpret mode only."""
    monkeypatch.setenv(pin, "on")
    monkeypatch.setattr(pw, "_need_interpret", lambda: False)
    pw._reset_pallas_window()
    pw._CALLS.clear()
    try:
        with pytest.raises(pw.PallasUnavailable, match=pin + "=on"):
            build()
    finally:
        pw._CALLS.clear()
        pw._reset_pallas_window()


def test_vmem_budget_gate(monkeypatch):
    """supports() enforces the chip VMEM budget on TPU backends only:
    interpret (no VMEM) always passes, a pretend-chip refuses shapes
    whose K-bucket table can't fit — with a durable fallback event
    when the engine build hits the refusal."""
    assert pw.supports(32768, 65536, 128)  # interpret: no budget
    monkeypatch.setattr(pw, "_on_tpu", lambda: True)
    assert pw.supports(8192, 8192, 16)
    assert not pw.supports(32768, 65536, 128)  # 33MB table alone
    monkeypatch.setenv("GS_PALLAS_WINDOW", "on")
    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.delenv("GS_TRACE_DIR", raising=False)
    pw._reset_pallas_window()
    telemetry.reset()
    try:
        assert pw.maybe_window_body(32768, 65536, 128) is None
        evs = [r for r in telemetry.records()
               if r["name"] == "selection.fallback"
               and "vmem budget" in r["a"].get("error", "")]
        assert evs
    finally:
        pw._reset_pallas_window()
        telemetry.reset()


# ----------------------------------------------------------------------
# tiling layer + tuner family
# ----------------------------------------------------------------------
def test_resolve_tiles_pins_and_divisibility(monkeypatch):
    monkeypatch.setenv("GS_PALLAS_TILE", "64")
    monkeypatch.setenv("GS_PALLAS_CK", "16")
    tile, ck = pw.resolve_tiles(256, 32)
    assert (tile, ck) == (64, 16)
    monkeypatch.setenv("GS_PALLAS_TILE", "96")  # not a divisor
    tile, _ = pw.resolve_tiles(256, 32)
    assert 256 % tile == 0
    monkeypatch.delenv("GS_PALLAS_TILE")
    monkeypatch.delenv("GS_PALLAS_CK")
    tile, ck = pw.resolve_tiles(256, 32)
    assert 256 % tile == 0 and 8 <= ck <= 32


def test_tile_tuner_family(monkeypatch):
    monkeypatch.setenv("GS_TUNE_CACHE", "0")
    tuner = pw.tile_tuner(32768, 65536, 32)
    assert tuner.key == "pallas_window:eb=32768:vb=65536:kb=32"
    assert set(tuner.space) == {"tile_e", "ck"}
    for t in tuner.space["tile_e"]:
        assert 32768 % t == 0
    arm = tuner.next_round()
    tuner.record(arm, 32768, 0.5)
    assert tuner.best() in [dict(zip(tuner.space, v)) for v in
                            __import__("itertools").product(
                                *tuner.space.values())]


def test_explicit_tile_arm_parity(pallas_on):
    """A multi-tile grid (the chip shape) folds tile-by-tile and
    must match the whole-slab default bit-for-bit."""
    import jax
    import jax.numpy as jnp

    from gelly_streaming_tpu.ops import scan_analytics as sa
    from gelly_streaming_tpu.ops import segment as seg_ops

    eb, vb, kb = 64, 64, 8
    src, dst = _stream(3 * eb, 50, seed=10)
    _w, s, d, valid = seg_ops.window_stack(src, dst, eb, sentinel=vb)

    def run(body):
        carry = (jnp.zeros(vb + 1, jnp.int32),
                 jnp.arange(vb + 1, dtype=jnp.int32),
                 jnp.arange(2 * (vb + 1), dtype=jnp.int32))
        c, ys = jax.jit(lambda c0, a, b, m: jax.lax.scan(
            body, c0, (a, b, m)))(carry, jnp.asarray(s),
                                  jnp.asarray(d), jnp.asarray(valid))
        return ([np.asarray(x) for x in c],
                [np.asarray(y) for y in ys])

    cx, yx = run(sa._build_scan(eb, vb, kb, pallas_ok=False))
    for tile in (16, 32, 64):
        ct, yt = run(pw.build_window_body(eb, vb, kb, tile_e=tile,
                                          chunk_k=8))
        assert all(np.array_equal(a, b) for a, b in zip(cx, ct))
        assert all(np.array_equal(a, b) for a, b in zip(yx, yt))


# ----------------------------------------------------------------------
# cost-model registration (the observatory acceptance)
# ----------------------------------------------------------------------
def test_cost_model_registers_single_slab_read(monkeypatch,
                                               pallas_on):
    from gelly_streaming_tpu.utils import costmodel

    monkeypatch.setenv("GS_COSTMODEL", "1")
    costmodel.reset()
    try:
        eng = StreamSummaryEngine(edge_bucket=256, vertex_bucket=256)
        assert eng._pallas
        rows = [r for r in costmodel.report("TPU v5 lite")
                if r["program"] == "pallas_window"
                and r.get("model") == "analytic"]
        assert rows, "analytic megakernel entry not registered"
        # a dispatch must join the STATED model at its own span sig —
        # never a capture of the interpret lowering (review fix)
        eng.process(*_stream(256, 200, seed=1))
        sig_rows = [r for r in costmodel.report("TPU v5 lite")
                    if r["program"] == "pallas_window"
                    and not r["sig"].startswith("eb=")]
        assert sig_rows, \
            "dispatch sig not instantiated from the analytic template"
        assert all(r.get("model") == "analytic" for r in sig_rows)
        row = rows[0]
        # the adoption story in one inequality: ONE slab read,
        # strictly below the scan-of-gathers' summed reads
        assert row["slab_bytes"] == pw.slab_bytes(256)
        assert row["bytes_accessed"] < row["scan_of_gathers_bytes"]
        assert row["scan_of_gathers_bytes"] \
            == pw.scan_of_gathers_bytes(256, 256)
        assert row["flops"] and row["bound"] in ("bytes", "flops")
        # and the summed gathers dominate BY the extra slab reads
        assert (row["scan_of_gathers_bytes"] - row["bytes_accessed"]
                >= 3 * pw.slab_bytes(256))
    finally:
        costmodel.reset()


def test_window_bytes_model_shapes():
    assert pw.slab_bytes(1024, compact=True) < pw.slab_bytes(1024)
    assert pw.window_bytes(1024, 512) \
        < pw.scan_of_gathers_bytes(1024, 512)
    # budget arithmetic is monotone in each dimension
    assert pw.vmem_window_bytes(1024, 512, 16) \
        < pw.vmem_window_bytes(2048, 512, 16) \
        < pw.vmem_window_bytes(2048, 1024, 32)


# ----------------------------------------------------------------------
# tenant-axis cohort megakernel (GS_COHORT_PALLAS)
# ----------------------------------------------------------------------
@pytest.fixture
def cohort_pallas_on(monkeypatch):
    monkeypatch.setenv("GS_COHORT_PALLAS", "on")
    monkeypatch.setenv("GS_AUTOTUNE", "0")
    pw._reset_pallas_window()
    yield
    pw._reset_pallas_window()


def test_cohort_kernel_interpret_parity(cohort_pallas_on):
    """The tier-1 interpret-parity pin: the tenant-axis megakernel
    (tenant axis as a second grid dimension, whole cohort's carries
    VMEM-resident) reproduces N sequential single-stream engines
    exactly — ragged tails and pad rows included."""
    from gelly_streaming_tpu.core.tenancy import TenantCohort
    from gelly_streaming_tpu.ops import scan_analytics as sa

    eb, vb = 256, 256
    # the cohort program the dispatch will build must BE the kernel
    run = sa.build_cohort_scan(eb, vb, 16, nb=4)
    assert getattr(run, "pallas_window", False), \
        "cohort scan did not select the tenant-axis megakernel"

    streams = {}
    for i in range(3):
        n = 3 * eb - (17 if i == 2 else 0)
        streams["t%d" % i] = _stream(n, 200, seed=20 + i)
    want = {tid: StreamSummaryEngine(
                edge_bucket=eb, vertex_bucket=vb).process(s, d)
            for tid, (s, d) in streams.items()}
    co = TenantCohort(edge_bucket=eb, vertex_bucket=vb)
    got = {tid: [] for tid in streams}
    for tid in streams:
        co.admit(tid)
    for tid, (s, d) in streams.items():
        co.feed(tid, s, d)
    for tid, res in co.pump().items():
        got[tid].extend(res)
    for tid in streams:
        got[tid].extend(co.close(tid))
    assert got == want


def test_cohort_resolve_pins(monkeypatch):
    monkeypatch.setenv("GS_COHORT_PALLAS", "on")
    assert pw.resolve_cohort_pallas() is True
    monkeypatch.setenv("GS_COHORT_PALLAS", "off")
    assert pw.resolve_cohort_pallas() is False
    monkeypatch.delenv("GS_COHORT_PALLAS")
    assert pw.resolve_cohort_pallas() is False


def test_cohort_vmem_budget_scales_with_rows(monkeypatch):
    """supports_cohort recomputes the DESIGN.md budget with N carry
    rows in flight: a shape a single tenant affords can refuse at
    cohort width, and refusal surfaces as a durable fallback (the
    dispatch degrades to the vmapped XLA scan, never dies)."""
    # interpret (off-chip): no budget, any width passes
    assert pw.supports_cohort(8192, 8192, 16, 64)
    monkeypatch.setattr(pw, "_on_tpu", lambda: True)
    assert pw.supports(8192, 8192, 16)
    assert pw.supports_cohort(8192, 8192, 16, 1)
    # 2 * 64 * carry_bytes(8192) alone is ~16.8MB > the 12MB budget
    assert not pw.supports_cohort(8192, 8192, 16, 64)
    # the cohort term is exactly N stacked carries over the single row
    assert (pw.cohort_vmem_window_bytes(8192, 8192, 16, 64)
            - pw.cohort_vmem_window_bytes(8192, 8192, 16, 1)
            == 2 * 63 * pw.carry_bytes(8192))
    monkeypatch.setenv("GS_COHORT_PALLAS", "on")
    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.delenv("GS_TRACE_DIR", raising=False)
    pw._reset_pallas_window()
    telemetry.reset()
    try:
        assert pw.maybe_cohort_body(8192, 8192, 16, 64) is None
        evs = [r for r in telemetry.records()
               if r["name"] == "selection.fallback"
               and r["a"].get("component") == "cohort_pallas"]
        assert evs and "vmem budget" in evs[0]["a"].get("error", "")
    finally:
        pw._reset_pallas_window()
        telemetry.reset()


def test_cohort_gate_default_off_is_vmapped_scan(pallas_unset,
                                                 monkeypatch):
    """GS_COHORT_PALLAS unset: build_cohort_scan returns the vmapped XLA
    scan, bit-identical to today's default."""
    monkeypatch.delenv("GS_COHORT_PALLAS", raising=False)
    from gelly_streaming_tpu.ops import scan_analytics as sa

    run = sa.build_cohort_scan(256, 256, 16, nb=4)
    assert not getattr(run, "pallas_window", False)
    assert pw.maybe_cohort_body(256, 256, 16, 4) is None

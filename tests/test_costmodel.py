"""Program cost observatory suite (utils/costmodel):

- signature rendering: metrics.abstract_sig tuples → the compact
  deterministic string the ledger tags and registry keys share;
- roofline classification: bytes- vs FLOPs-bound against the
  GS_COSTMODEL_PEAK_* machine balance, `unknown` without both inputs;
- capture paths: wrap_exec (free, off the existing AOT executable)
  and the wrap_jit on_call hook (one extra AOT compile per new
  signature), idempotent per (program, sig), error-tolerant on
  un-lowerable functions;
- the telemetry-sink join: program/sig-tagged dispatch spans
  accumulate measured seconds, report() serves the joined rows
  (including cost-less rows for programs armed after their compile);
- end-to-end: an armed fused-scan engine run leaves ledger dispatch
  spans carrying program="fused_scan" + sig — the attribution
  substrate tools/explain_perf.py drills into;
- the zero-overhead contract: GS_COSTMODEL=0 (the default) vs 1 on
  the 524K/32768 CPU row is digest-identical (the observatory
  observes, never participates) — the acceptance pin.
"""

import hashlib

import numpy as np
import pytest

from gelly_streaming_tpu.utils import costmodel, metrics, telemetry


@pytest.fixture
def armed(monkeypatch):
    """Observatory armed, registry fresh before AND after."""
    monkeypatch.setenv("GS_COSTMODEL", "1")
    monkeypatch.delenv("GS_TELEMETRY", raising=False)
    costmodel.reset()
    telemetry.reset()
    yield
    costmodel.reset()
    telemetry.reset()


def _stream(num_edges, num_vertices, seed=7):
    from bench import make_stream

    return make_stream(num_edges, num_vertices, seed)


def _toy_exec():
    """A tiny AOT-compiled executable + its abstract signature."""
    import jax
    import jax.numpy as jnp

    def f(x, y):
        return (x * y).sum() + jnp.dot(x, y)

    sds = (jax.ShapeDtypeStruct((64,), jnp.float32),
           jax.ShapeDtypeStruct((64,), jnp.float32))
    return jax.jit(f).lower(*sds).compile(), metrics.abstract_sig(sds)


# ----------------------------------------------------------------------
# signature rendering
# ----------------------------------------------------------------------
def test_sig_key_renders_abstract_sigs():
    import jax.numpy as jnp

    sig = metrics.abstract_sig(
        (jnp.zeros((16, 32768), jnp.int32),
         jnp.zeros((16, 32768), jnp.uint16),
         jnp.zeros((16,), jnp.bool_)))
    assert costmodel.sig_key(sig) \
        == "i32[16,32768],u16[16,32768],b1[16]"
    # nested pytree args (the fused-scan carry tuple) render nested
    nested = metrics.abstract_sig(
        ((jnp.zeros(4, jnp.int32), jnp.zeros(8, jnp.float32)),))
    assert costmodel.sig_key(nested) == "(i32[4],f32[8])"
    # deterministic: the same sig twice is the same key
    assert costmodel.sig_key(sig) == costmodel.sig_key(sig)


# ----------------------------------------------------------------------
# roofline classification
# ----------------------------------------------------------------------
def test_classify_bytes_vs_flops_bound(monkeypatch):
    monkeypatch.setitem(costmodel.PEAKS, "test chip", (100e9, 10e9))
    # machine balance = 10 FLOPs/byte
    low = costmodel.classify({"flops": 10, "bytes_accessed": 100},
                             "test chip")
    assert low["bound"] == "bytes"
    assert low["arith_intensity_flops_per_byte"] == 0.1
    # bytes-bound: roofline time is the bandwidth term
    assert low["roofline_s"] == pytest.approx(100 / 10e9)
    high = costmodel.classify({"flops": 10000, "bytes_accessed": 100},
                              "test chip")
    assert high["bound"] == "flops"
    assert high["roofline_s"] == pytest.approx(10000 / 100e9)
    assert high["machine_balance_flops_per_byte"] == 10.0


def test_classify_unknown_without_both_inputs():
    for entry in ({}, {"flops": 10}, {"bytes_accessed": 10},
                  {"flops": None, "bytes_accessed": 10}):
        out = costmodel.classify(dict(entry))
        assert out["bound"] == "unknown"
        assert out["roofline_s"] is None


def test_peaks_table_keyed_by_device_kind():
    """The v5e row is the published one; any other device — the CPU
    this suite runs on included — is an error wherever a roofline
    share is computed, never a silent default."""
    assert costmodel.peaks("TPU v5 lite") == (197e12, 819e9)
    with pytest.raises(ValueError, match="no roofline peaks"):
        costmodel.peaks("cpu")
    with pytest.raises(ValueError, match="no roofline peaks"):
        costmodel.classify({"flops": 1, "bytes_accessed": 1}, "TPU v9")
    # the default is the device this process runs on: the CPU here
    with pytest.raises(ValueError, match="'cpu'"):
        costmodel.classify({"flops": 1, "bytes_accessed": 1})


def test_join_measure_math():
    entry = costmodel.classify(
        {"flops": 2_000_000_000, "bytes_accessed": 4_000_000_000},
        "TPU v5 lite")
    costmodel.join_measure(entry, count=4, total_s=8.0)
    assert entry["dispatches"] == 4
    assert entry["measured_mean_s"] == 2.0
    assert entry["achieved_gflops"] == 1.0     # 2 GF / 2 s
    assert entry["achieved_gbps"] == 2.0       # 4 GB / 2 s
    assert entry["roofline_frac"] == pytest.approx(
        entry["roofline_s"] / 2.0, abs=1e-6)
    # zero measurements: economics fields stay absent
    bare = costmodel.join_measure(costmodel.classify({}), 0, 0.0)
    assert "measured_mean_s" not in bare


# ----------------------------------------------------------------------
# disarmed: guarded no-ops
# ----------------------------------------------------------------------
def test_disarmed_captures_nothing(monkeypatch):
    monkeypatch.setenv("GS_COSTMODEL", "0")
    costmodel.reset()
    try:
        ex, sig = _toy_exec()
        costmodel.record_compiled("toy", ex, sig)
        costmodel.on_call("toy", ex, sig, (), {})
        wrapped = costmodel.wrap_exec("toy", ex, sig)
        wrapped(np.ones(64, np.float32), np.ones(64, np.float32))
        assert costmodel.programs() == {}
        assert costmodel.report() == []
        assert telemetry.pop_dispatch_tags() == {}
    finally:
        costmodel.reset()


# ----------------------------------------------------------------------
# armed capture: wrap_exec (free) and on_call (one extra compile)
# ----------------------------------------------------------------------
def test_wrap_exec_captures_and_tags(armed):
    ex, sig = _toy_exec()
    wrapped = costmodel.wrap_exec("toy_exec", ex, sig)
    assert wrapped.__wrapped__ is ex
    out = wrapped(np.ones(64, np.float32), np.ones(64, np.float32))
    assert float(np.asarray(out)) == pytest.approx(128.0)
    entry = costmodel.programs()[("toy_exec", "f32[64],f32[64]")]
    # the CPU backend reports both analyses on an AOT executable
    assert entry["flops"] > 0
    assert entry["bytes_accessed"] > 0
    assert entry["argument_bytes"] == 512      # 2 × 64 × f32
    assert costmodel.classify(dict(entry), "TPU v5 lite")["bound"] \
        in ("bytes", "flops")
    # the dispatch bound its program/sig tags for the span record site
    assert telemetry.pop_dispatch_tags() \
        == {"program": "toy_exec", "sig": "f32[64],f32[64]"}
    # idempotent per key: a second call re-tags, never re-captures
    before = costmodel.programs()
    wrapped(np.ones(64, np.float32), np.ones(64, np.float32))
    assert costmodel.programs() == before


def test_wrap_exec_armed_mid_stream_still_captures(monkeypatch):
    """Disarmed at wrap time, armed later: the compiled handle rides
    the closure, so the first ARMED call captures."""
    monkeypatch.setenv("GS_COSTMODEL", "0")
    costmodel.reset()
    try:
        ex, sig = _toy_exec()
        wrapped = costmodel.wrap_exec("toy_late", ex, sig)
        wrapped(np.ones(64, np.float32), np.ones(64, np.float32))
        assert costmodel.programs() == {}
        monkeypatch.setenv("GS_COSTMODEL", "1")
        wrapped(np.ones(64, np.float32), np.ones(64, np.float32))
        assert ("toy_late", "f32[64],f32[64]") in costmodel.programs()
        telemetry.pop_dispatch_tags()
    finally:
        costmodel.reset()


def test_on_call_via_wrap_jit_captures_per_signature(armed):
    import jax
    import jax.numpy as jnp

    fn = metrics.wrap_jit("toy_jit", jax.jit(lambda x: x + 1))
    fn(jnp.arange(8))
    fn(jnp.arange(8))                      # same sig: one entry
    fn(jnp.arange(16, dtype=jnp.float32))  # new sig: second entry
    progs = costmodel.programs()
    assert set(progs) == {("toy_jit", "i32[8]"),
                          ("toy_jit", "f32[16]")}
    assert progs[("toy_jit", "i32[8]")]["flops"] is not None
    telemetry.pop_dispatch_tags()


def test_on_call_unlowerable_records_error_entry(armed):
    costmodel.on_call("plain_fn", lambda x: x, ("sig",), (1,), {})
    entry = costmodel.programs()[("plain_fn", "sig")]
    assert "not AOT-lowerable" in entry["error"]
    # the error entry still reports (cost-less) instead of vanishing;
    # error rows still carry the schema-required cost keys (null), so
    # a partially-captured run commits a valid cost_model section
    row = next(r for r in costmodel.report()
               if r.get("program") == "plain_fn")
    assert row["bound"] == "unknown"
    assert row["flops"] is None
    assert row["bytes_accessed"] is None
    telemetry.pop_dispatch_tags()


# ----------------------------------------------------------------------
# the sink join + report
# ----------------------------------------------------------------------
def test_sink_joins_tagged_spans_into_report(armed, monkeypatch):
    ex, sig = _toy_exec()
    costmodel.record_compiled("joined", ex, sig)
    for _ in range(3):
        with telemetry.span("ingress.dispatch", program="joined",
                            sig="f32[64],f32[64]"):
            pass
    # untagged spans never reach the registry
    with telemetry.span("ingress.prep"):
        pass
    rows = {r["program"]: r for r in costmodel.report("TPU v5 lite")}
    assert rows["joined"]["dispatches"] == 3
    assert rows["joined"]["measured_total_s"] >= 0
    assert "roofline_frac" in rows["joined"] \
        or rows["joined"]["measured_total_s"] == 0.0
    # a tagged program that was never captured (armed after compile)
    # still reports, cost-less
    with telemetry.span("ingress.dispatch", program="ghost",
                        sig="i32[4]"):
        pass
    rows = {r["program"]: r for r in costmodel.report("TPU v5 lite")}
    assert rows["ghost"]["dispatches"] == 1
    assert rows["ghost"]["bound"] == "unknown"
    # cost-less rows still carry the schema-required keys as null
    assert rows["ghost"]["flops"] is None
    assert rows["ghost"]["bytes_accessed"] is None


def test_report_sorted_by_measured_time(armed):
    # stated durations, not sleeps: under a loaded host one slept
    # millisecond can outlast four (the order must not hang on it)
    for name, n in (("cold", 1), ("hot", 4)):
        for _ in range(n):
            telemetry.record_span("ingress.dispatch", 0.0, 0.001,
                                  program=name, sig="s")
    order = [r["program"] for r in costmodel.report()]
    assert order.index("hot") < order.index("cold")


# ----------------------------------------------------------------------
# end-to-end: the fused-scan engine leaves an attributable ledger
# ----------------------------------------------------------------------
def test_engine_dispatch_spans_carry_program_tags(
        armed, monkeypatch, tmp_path):
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)

    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.setenv("GS_TRACE_DIR", str(tmp_path))
    telemetry.reset()
    src, dst = _stream(4096, 512)
    eng = StreamSummaryEngine(edge_bucket=1024, vertex_bucket=512)
    eng.process(src, dst)
    spans = [r for r in telemetry.records() if r["t"] == "span"]
    tagged = [r for r in spans
              if (r.get("a") or {}).get("program") == "fused_scan"]
    assert tagged, "no dispatch span carried the fused_scan tag"
    sig = tagged[0]["a"]["sig"]
    assert "i32[" in sig                  # the COO slab is in the key
    assert ("fused_scan", sig) in costmodel.programs()
    # the live join serves the same rows explain_perf computes offline
    row = next(r for r in costmodel.report("TPU v5 lite")
               if r["program"] == "fused_scan")
    assert row["dispatches"] == len(tagged)
    assert row["flops"] is not None


def test_dispatch_tags_survive_armed_stage_watchdog(
        armed, monkeypatch, tmp_path):
    """With GS_STAGE_TIMEOUT_S armed, resilience runs the dispatch on
    the gs-stage-watchdog helper thread — the program/sig tags bind
    in THAT thread's TLS and must still reach the span record (the
    production-debugging configuration: watchdog + observatory)."""
    from gelly_streaming_tpu.ops.scan_analytics import (
        StreamSummaryEngine)

    monkeypatch.setenv("GS_TELEMETRY", "1")
    monkeypatch.setenv("GS_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("GS_STAGE_TIMEOUT_S", "120")
    telemetry.reset()
    src, dst = _stream(4096, 512)
    eng = StreamSummaryEngine(edge_bucket=1024, vertex_bucket=512)
    eng.process(src, dst)
    tagged = [r for r in telemetry.records()
              if r["t"] == "span" and r.get("name") == "ingress.dispatch"
              and (r.get("a") or {}).get("program") == "fused_scan"]
    assert tagged, ("guarded dispatch lost its program tags — the "
                    "watchdog thread's TLS didn't reach the record")


# ----------------------------------------------------------------------
# the zero-overhead contract (acceptance pin)
# ----------------------------------------------------------------------
def test_disarmed_digest_parity_524k_row(monkeypatch):
    """GS_COSTMODEL=0 (default knobs) vs 1 on the 524K/32768 CPU row:
    counts are bit-identical — the observatory observes, never
    participates."""
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    src, dst = _stream(524288, 65536)
    monkeypatch.delenv("GS_COSTMODEL", raising=False)
    monkeypatch.delenv("GS_TELEMETRY", raising=False)
    costmodel.reset()
    telemetry.reset()
    kern = TriangleWindowKernel(edge_bucket=32768,
                                vertex_bucket=65536)
    base = kern.count_stream(src, dst)
    assert costmodel.programs() == {}     # disarmed: nothing captured
    monkeypatch.setenv("GS_COSTMODEL", "1")
    costmodel.reset()
    try:
        armed_counts = kern.count_stream(src, dst)
        # the roofline verdict is attached by report(), classified
        # against a chip's peaks (the CPU has no peaks row)
        rows = [r for r in costmodel.report("TPU v5 lite")
                if r["program"] == "triangle_stream"]
    finally:
        costmodel.reset()
        telemetry.reset()
    digest = lambda c: hashlib.sha256(  # noqa: E731
        np.asarray(c, np.int64).tobytes()).hexdigest()
    assert digest(base) == digest(armed_counts)
    # armed, the device stream program was captured and classified
    assert rows
    assert all(r["bound"] in ("bytes", "flops", "unknown") for r in rows)

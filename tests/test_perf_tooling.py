"""PERF.json contract guards: the committed evidence files validate
against the schema (tools/perf_schema.py), and the PERF.md renderer
(tools/update_perf_md.py) round-trips a full fixture — so a new
profiler section can't silently break the selection gates or the
unattended end-of-window renderer."""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


perf_schema = _load_tool("perf_schema")
update_perf_md = _load_tool("update_perf_md")
trace_report = _load_tool("trace_report")
bench_compare = _load_tool("bench_compare")


# ----------------------------------------------------------------------
# schema: the committed files must stay valid
# ----------------------------------------------------------------------
def test_committed_perf_file_validates():
    with open(os.path.join(REPO, "PERF_cpu.json")) as f:
        perf = json.load(f)
    assert perf_schema.validate(perf) == []


def test_schema_rejects_malformed_sections():
    bad = {
        "backend": "cpu",
        "ingress_ab": {"not": "a list"},
        "egress_ab": [{"probe": "driver_ab", "parity": True}],  # no speedup
        "degradations": [{"from": "scan"},  # missing to/window/mesh
                         {"from": "sharded", "to": "scan", "window": 1,
                          "mesh_shape": "4x1",     # not a list of ints
                          "shard_id": "two"}],     # not an int
        "pipeline_stages": ["not-a-dict"],
        "host_reduce_error": "not-a-dict",
        "telemetry": [{"count": 3}],               # missing span
        "regressions": [{"row": "x"}],             # missing field/...
        "metrics": [{"engine": "t"}],              # dict, not a list
    }
    errors = perf_schema.validate(bad)
    joined = "\n".join(errors)
    assert "ingress_ab" in joined
    assert "egress_ab" in joined and "speedup" in joined
    assert "degradations" in joined
    assert "'mesh_shape'" in joined and "'shard_id'" in joined
    assert "pipeline_stages" in joined
    assert "host_reduce_error" in joined
    assert "telemetry" in joined and "'span'" in joined
    assert "regressions" in joined and "'ratio'" in joined
    assert "metrics: expected a dict section" in joined
    # a dict metrics section missing its required keys is also caught
    errors = perf_schema.validate(
        {"backend": "cpu", "metrics": {"engine": "t"}})
    assert any("metrics" in e and "overhead_ratio" in e
               for e in errors)
    assert perf_schema.validate([]) != []       # top level must be dict
    assert perf_schema.validate({"backend": 3})  # backend must be str


def test_schema_allows_unknown_sections():
    assert perf_schema.validate(
        {"backend": "cpu", "brand_new_section": [{"x": 1}]}) == []


# ----------------------------------------------------------------------
# renderer round-trip on a full fixture
# ----------------------------------------------------------------------
FIXTURE = {
    "backend": "cpu",
    "device": "TFRT_CPU_0",
    "roofline": {
        "peaks": {"hw": "v5e", "bf16_tflops": 197, "hbm_gbps": 819},
        "rows": [{"program": "tri_stream", "ms": 1.5,
                  "gflops_achieved": 10.0, "mfu_vs_bf16_peak": 0.01,
                  "gbps_achieved": 5.0, "hbm_frac_of_peak": 0.01,
                  "bound": "hbm",
                  "arith_intensity_flops_per_byte": 2.0}],
    },
    "trace": {"windows": 16, "edge_bucket": 32768,
              "dispatch_wall_ms": 100.0, "trace_dir": "logs/trace",
              "top_ops": [{"op": "sort", "total_ms": 5.0, "calls": 2}]},
    "host_stream": [{"edge_bucket": 8192, "parity": True,
                     "host_edges_per_s": 2, "device_edges_per_s": 1,
                     "host_vs_device": 2.0}],
    "pipeline_stages": [{"engine": "triangle", "edge_bucket": 32768,
                         "ingress": "standard", "workers": 4,
                         "prep_ms_per_chunk": 1.0,
                         "h2d_ms_per_chunk": 2.0,
                         "compute_ms_per_chunk": 3.0,
                         "pipelined_edges_per_s": 10,
                         "sync_edges_per_s": 5,
                         "pipeline_speedup": 2.0, "parity": True}],
    "ingress_probes": [{"probe": "dispatch_latency",
                        "round_trip_s": 0.2}],
    "ingress_ab": [{"probe": "stream_ab", "parity": True,
                    "num_edges": 100, "std_edges_per_s": 1,
                    "compact_edges_per_s": 2, "speedup": 2.0,
                    "speedup_worst": 1.8, "speedup_best": 2.2}],
    "egress_ab": [{"probe": "driver_ab", "parity": True,
                   "eb": 32768, "vb": 65536,
                   "full_edges_per_s": 1, "delta_edges_per_s": 2,
                   "speedup": 2.0, "speedup_worst": 1.9,
                   "speedup_best": 2.1}],
    "tenancy_ab": [{"probe": "cohort_serving", "parity": True,
                    "tenants": 8, "eb": 512, "vb": 1024,
                    "tenant_edges_per_s": 18476,
                    "sequential_edges_per_s": 12285,
                    "speedup": 1.504, "speedup_worst": 1.346,
                    "speedup_best": 1.584}],
    "autotune": [{"engine": "triangle_stream", "edge_bucket": 32768,
                  "parity": True, "static_edges_per_s": 1,
                  "tuned_cold_edges_per_s": 2,
                  "tuned_seeded_edges_per_s": 3,
                  "seeded_vs_static": 3.0,
                  "chosen": {"wb": 64, "kb": 32,
                             "ingress": "standard"}}],
    "degradations": [{"section": "driver", "from": "scan",
                      "to": "native", "window": 5, "reason": "t",
                      "mesh_shape": None, "shard_id": None},
                     {"section": "driver", "from": "sharded",
                      "to": "scan", "window": 9, "reason": "dead shard",
                      "mesh_shape": [4], "shard_id": 2}],
    "telemetry": [{"span": "ingress.prep", "count": 16,
                   "total_ms": 40.0, "p50_ms": 2.0, "p95_ms": 4.0,
                   "p99_ms": 5.0}],
    "telemetry_meta": {"engine": "triangle_stream+driver",
                       "parity": True, "overhead_ratio": 1.01,
                       "trace": "abc-123"},
    "metrics": {"engine": "triangle_stream", "edge_bucket": 32768,
                "num_edges": 524288, "parity": True,
                "disarmed_edges_per_s": 24000000,
                "armed_edges_per_s": 23500000,
                "overhead_ratio": 1.021, "windows_observed": 16},
    "cost_model": {"engine": "triangle_stream+fused_scan",
                   "edge_bucket": 32768, "num_edges": 524288,
                   "parity": True, "trace": "abc-123",
                   "ledger": "logs/costmodel_ledger_cpu.jsonl",
                   "peaks": {"gflops": 197000.0, "gbps": 819.0},
                   "programs": [
                       {"program": "fused_scan",
                        "sig": "i32[16,32768],b1[16,32768]",
                        "flops": 47352212,
                        "bytes_accessed": 186835344,
                        "arith_intensity_flops_per_byte": 0.2534,
                        "bound": "bytes", "dispatches": 1,
                        "measured_mean_s": 0.2376,
                        "roofline_s": 0.000228,
                        "roofline_frac": 0.00096}]},
    "regressions": [{"row": "bench[triangle]", "field": "value",
                     "baseline": 100, "current": 50, "ratio": 0.5,
                     "tolerance": 0.2}],
    "sharded": {"collectives": {
        "config": {"n": 8, "vb": 65536, "kb": 32, "cap": 4096},
        "backend": "cpu-virtual-mesh", "note": "modeled",
        "rows": [{"collective": "psum",
                  "modeled_ici_bytes_per_chip": 1024,
                  "modeled_ms_v5e_ici": 0.01,
                  "measured_ms_cpu_mesh": 0.5}]}},
}


def test_fixture_passes_schema():
    assert perf_schema.validate(FIXTURE) == []


def test_render_covers_every_new_section():
    block = update_perf_md.render(FIXTURE)
    assert update_perf_md.MARK_BEGIN in block
    assert update_perf_md.MARK_END in block
    for needle in ("d2h egress A/B", "Online dispatch autotuner",
                   "driver_ab", "triangle_stream",
                   "wb=64", "DEGRADED RUN", "Roofline",
                   "Ingress pipeline per-stage timing",
                   "Flight recorder", "ingress.prep", "1.010",
                   "Metrics plane", "1.021",
                   "Program cost observatory", "fused_scan",
                   "explain_perf",
                   "Multi-tenant cohort A/B", "cohort_serving"):
        assert needle in block, needle


def test_update_perf_md_round_trips_idempotently(tmp_path):
    perf_path = str(tmp_path / "PERF.json")
    md_path = str(tmp_path / "PERF.md")
    with open(perf_path, "w") as f:
        json.dump(FIXTURE, f)
    with open(md_path, "w") as f:
        f.write("# PERF\n\nhand-written preamble\n\n%s\nstale\n%s\n"
                "hand-written tail\n" % (update_perf_md.MARK_BEGIN,
                                         update_perf_md.MARK_END))
    update_perf_md.main(perf_path, md_path)
    with open(md_path) as f:
        once = f.read()
    assert "hand-written preamble" in once
    assert "hand-written tail" in once
    assert "stale" not in once
    assert "Online dispatch autotuner" in once
    update_perf_md.main(perf_path, md_path)  # idempotent
    with open(md_path) as f:
        assert f.read() == once


# ----------------------------------------------------------------------
# trace_report round-trips its committed fixture ledger (no network,
# no chip): the tier-1 guard that the flight-recorder toolchain keeps
# reading the ledgers real runs write
# ----------------------------------------------------------------------
LEDGER_FIXTURE = os.path.join(REPO, "tests", "fixtures",
                              "telemetry_ledger.jsonl")


def test_trace_report_loads_fixture_and_skips_torn_tail():
    records = trace_report.load(LEDGER_FIXTURE)
    # the fixture ends with a deliberately torn line (a crash
    # mid-append): skipped, never fatal
    assert not any("torn" in str(r.get("name", "")) for r in records)
    assert trace_report.meta_of(records)["trace"] == "fixture-1"
    kinds = {r["t"] for r in records}
    assert {"meta", "span", "event", "counter"} <= kinds


def test_trace_report_histograms_exact_on_fixture():
    records = trace_report.load(LEDGER_FIXTURE)
    rows = {r["span"]: r for r in trace_report.span_rows(records)}
    prep = rows["ingress.prep"]
    # durations committed in the fixture: 10/20/30/40 ms -> nearest
    # rank p50=20, p95=40, p99=40; total 100
    assert prep["count"] == 4
    assert prep["total_ms"] == 100.0
    assert (prep["p50_ms"], prep["p95_ms"], prep["p99_ms"]) \
        == (20.0, 40.0, 40.0)
    thr = {r["span"]: r
           for r in trace_report.throughput_rows(records)}
    # two triangles.round spans: 131072 edges over 0.2 s
    assert thr["triangles.round"]["edges"] == 131072
    assert thr["triangles.round"]["edges_per_s"] == 655360


def test_trace_report_perfetto_and_render_round_trip(tmp_path):
    records = trace_report.load(LEDGER_FIXTURE)
    trace = json.loads(json.dumps(trace_report.to_perfetto(records)))
    evs = trace["traceEvents"]
    assert all({"name", "ph", "pid", "tid", "ts"} <= set(e)
               for e in evs)
    assert any(e["ph"] == "X" and e["name"] == "ingress.chunk"
               for e in evs)
    assert any(e["ph"] == "i" and e["name"] == "resume" for e in evs)
    assert any(e["ph"] == "C" for e in evs)
    text = trace_report.render(records)
    for needle in ("fixture-1", "ingress.prep", "tier_demotion",
                   "resume", "edges/s"):
        assert needle in text, needle
    # the CLI end-to-end: report + perfetto export, exit 0
    out = str(tmp_path / "trace.json")
    assert trace_report.main([LEDGER_FIXTURE, "--perfetto", out]) == 0
    with open(out) as f:
        assert json.load(f)["traceEvents"]


# ----------------------------------------------------------------------
# bench_compare: the perf regression sentry (tools/bench_compare.py)
# ----------------------------------------------------------------------
BENCH_ROWS = [
    {"metric": "triangle 32768", "value": 9000000, "unit": "edges/s",
     "pipeline_speedup": 3.1, "sync_prep_edges_per_s": 2900000},
    {"metric": "reduce 8192", "value": 170000000, "unit": "edges/s",
     "vs_baseline": 1.19},
]


def _write_jsonl(path, rows):
    with open(path, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")


def test_bench_compare_unchanged_run_exits_zero(tmp_path, capsys):
    base = str(tmp_path / "base.jsonl")
    _write_jsonl(base, BENCH_ROWS)
    assert bench_compare.main(["--baseline", base]) == 0
    report = json.loads(capsys.readouterr().out)
    assert perf_schema.validate(report) == []
    assert report["regressions"] == []
    assert report["rows_compared"] == 2


def test_bench_compare_slowed_row_exits_nonzero(tmp_path, capsys):
    base = str(tmp_path / "base.jsonl")
    cur = str(tmp_path / "cur.jsonl")
    _write_jsonl(base, BENCH_ROWS)
    slowed = [dict(r) for r in BENCH_ROWS]
    slowed[0]["value"] = int(slowed[0]["value"] * 0.5)  # -50%
    _write_jsonl(cur, slowed)
    rc = bench_compare.main(["--baseline", base, "--current", cur,
                             "--out", str(tmp_path / "report.json")])
    assert rc == 1
    report = json.loads((tmp_path / "report.json").read_text())
    assert perf_schema.validate(report) == []
    regs = report["regressions"]
    assert len(regs) == 1
    assert regs[0]["row"] == "triangle 32768"
    assert regs[0]["field"] == "value"
    assert regs[0]["ratio"] == 0.5


def test_bench_compare_ratio_field_and_tolerance(tmp_path):
    base = str(tmp_path / "base.jsonl")
    cur = str(tmp_path / "cur.jsonl")
    _write_jsonl(base, BENCH_ROWS)
    slowed = [dict(r) for r in BENCH_ROWS]
    slowed[0]["pipeline_speedup"] = 2.6  # -16%: inside 0.2, not 0.1
    _write_jsonl(cur, slowed)
    assert bench_compare.main(
        ["--baseline", base, "--current", cur]) == 0
    assert bench_compare.main(
        ["--baseline", base, "--current", cur,
         "--tolerance", "0.1"]) == 1


def test_schema_and_sentry_cover_tenancy_rows(tmp_path):
    """The tenancy_ab section: required keys enforced (probe / parity
    / tenants; parity-true rows need a positive speedup), and
    bench_compare matches tenancy rows by (probe, tenants) identity
    comparing tenant_edges_per_s — the regression sentry covers the
    cohort path."""
    bad = {"backend": "cpu",
           "tenancy_ab": [{"probe": "cohort_serving", "parity": True}]}
    errors = "\n".join(perf_schema.validate(bad))
    assert "tenancy_ab" in errors
    assert "'tenants'" in errors and "speedup" in errors
    good = {"backend": "cpu",
            "tenancy_ab": [{"probe": "cohort_serving", "parity": True,
                            "tenants": 8, "speedup": 1.5,
                            "tenant_edges_per_s": 20000,
                            "sequential_edges_per_s": 13000}]}
    assert perf_schema.validate(good) == []

    base = str(tmp_path / "PERF_base.json")
    cur = str(tmp_path / "PERF_cur.json")
    with open(base, "w") as f:
        json.dump(good, f)
    slowed = json.loads(json.dumps(good))
    slowed["tenancy_ab"][0]["tenant_edges_per_s"] = 9000  # -55%
    with open(cur, "w") as f:
        json.dump(slowed, f)
    assert bench_compare.main(
        ["--baseline", base, "--current", base]) == 0
    rc = bench_compare.main(
        ["--baseline", base, "--current", cur,
         "--out", str(tmp_path / "report.json")])
    assert rc == 1
    report = json.loads((tmp_path / "report.json").read_text())
    regs = report["regressions"]
    assert regs[0]["row"] == "tenancy_ab[cohort_serving,8]"
    assert regs[0]["field"] == "tenant_edges_per_s"


def test_bench_compare_reads_perf_json(tmp_path):
    """PERF*.json baselines compare section rows (host_stream etc.)
    and the metrics/telemetry_meta dict sections."""
    base = str(tmp_path / "PERF_base.json")
    cur = str(tmp_path / "PERF_cur.json")
    with open(base, "w") as f:
        json.dump(FIXTURE, f)
    slowed = json.loads(json.dumps(FIXTURE))
    slowed["metrics"]["armed_edges_per_s"] = 10
    with open(cur, "w") as f:
        json.dump(slowed, f)
    assert bench_compare.main(
        ["--baseline", base, "--current", base]) == 0
    assert bench_compare.main(
        ["--baseline", base, "--current", cur]) == 1


def test_bench_compare_unreadable_inputs_exit_two(tmp_path):
    empty = str(tmp_path / "empty.json")
    with open(empty, "w") as f:
        f.write("{}")
    assert bench_compare.main(["--baseline", empty]) == 2
    assert bench_compare.main(
        ["--baseline", str(tmp_path / "missing.json")]) == 2


# ----------------------------------------------------------------------
# trace_report filters + empty-ledger exits
# ----------------------------------------------------------------------
def test_trace_report_filters(tmp_path):
    records = trace_report.load(LEDGER_FIXTURE)
    only = trace_report.filter_records(records, trace_id="fixture-1")
    assert only and all(r.get("trace") == "fixture-1" for r in only)
    none = trace_report.filter_records(records, trace_id="nope")
    assert none == []
    late = trace_report.filter_records(records, since=1e12)
    assert all(r["t"] == "meta" for r in late)  # meta anchor kept


def test_trace_report_exits_nonzero_on_empty_and_torn(tmp_path,
                                                      capsys):
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert trace_report.main([str(empty)]) == 1
    assert "no usable records" in capsys.readouterr().err
    torn = tmp_path / "torn.jsonl"
    torn.write_text('{"t": "span", "name": "torn')
    assert trace_report.main([str(torn)]) == 1
    assert "torn" in capsys.readouterr().err
    # filters that match nothing are an error, not an empty table
    assert trace_report.main([LEDGER_FIXTURE,
                              "--trace-id", "nope"]) == 1
    assert "nothing to report" in capsys.readouterr().err
    assert trace_report.main([LEDGER_FIXTURE,
                              "--trace-id", "fixture-1"]) == 0


# ----------------------------------------------------------------------
# cost_model schema + the BENCH capture shape (round 13)
# ----------------------------------------------------------------------
def test_schema_rejects_malformed_cost_model():
    bad = {"backend": "cpu",
           "cost_model": {"engine": "t"}}          # missing keys
    joined = "\n".join(perf_schema.validate(bad))
    assert "cost_model" in joined and "'programs'" in joined
    bad = {"backend": "cpu",
           "cost_model": {"programs": {"not": "a list"},
                          "parity": True, "edge_bucket": 1,
                          "trace": "t", "ledger": "l"}}
    assert any("must be a list" in e for e in perf_schema.validate(bad))
    bad = {"backend": "cpu",
           "cost_model": {"programs": [{"program": "p"}],  # bare row
                          "parity": True, "edge_bucket": 1,
                          "trace": "t", "ledger": "l"}}
    joined = "\n".join(perf_schema.validate(bad))
    # flops/bytes may be null but the keys must EXIST (reported-none
    # vs silently-dropped must stay distinguishable)
    for key in ("'sig'", "'flops'", "'bytes_accessed'", "'bound'",
                "'dispatches'"):
        assert key in joined, key
    ok = {"backend": "cpu",
          "cost_model": {"programs": [
              {"program": "p", "sig": "s", "flops": None,
               "bytes_accessed": None, "bound": "unknown",
               "dispatches": 0}],
              "parity": True, "edge_bucket": 1,
              "trace": "t", "ledger": "l"}}
    assert perf_schema.validate(ok) == []


def test_schema_validates_bench_capture_shape():
    cap = {"n": 1, "cmd": "python bench.py", "rc": 0,
           "tail": '{"metric": "x", "value": 1}\n', "parsed": None}
    assert perf_schema.is_capture(cap)
    assert perf_schema.validate_capture(cap) == []
    assert not perf_schema.is_capture({"backend": "cpu"})
    bad = {"cmd": "x", "rc": "zero", "tail": 3, "parsed": []}
    errors = perf_schema.validate_capture(bad)
    joined = "\n".join(errors)
    assert "'tail'" in joined and "'rc'" in joined \
        and "'parsed'" in joined


# ----------------------------------------------------------------------
# bench_compare: null identity fields match missing ones (the
# satellite fix), trace-ID correlation stamps
# ----------------------------------------------------------------------
def test_bench_compare_null_identity_treated_as_missing(tmp_path):
    """A row whose `metric` is present-but-null must behave exactly
    like a row without the key: no phantom `None` identity, so two
    UNRELATED null-identity rows can never be compared against each
    other as if they were the same row."""
    # extract_rows: every supported shape drops the null-identity row
    text = ('{"metric": null, "value": 100}\n'
            '{"metric": "real", "value": 7}\n'
            '{"value": 3}\n')
    rows = bench_compare.extract_rows(text, "stdout")
    assert set(rows) == {"real"}
    cap = {"tail": text, "parsed": {"metric": None, "value": 100}}
    assert set(bench_compare.extract_rows(cap, "cap")) == {"real"}
    assert bench_compare.extract_rows(
        {"metric": None, "value": 100, "tail_": 0}, "dict") == {}
    # end-to-end: baseline and current each carry a DIFFERENT
    # null-identity row (100 vs 10 — a 10× "drop" were they matched);
    # the shared real row is unchanged, so the sentry must exit 0
    base, cur = str(tmp_path / "b.jsonl"), str(tmp_path / "c.jsonl")
    _write_jsonl(base, [{"metric": None, "value": 100},
                        {"metric": "real", "value": 7}])
    _write_jsonl(cur, [{"metric": None, "value": 10},
                       {"metric": "real", "value": 7}])
    assert bench_compare.main(
        ["--baseline", base, "--current", cur]) == 0


def test_bench_compare_stamps_trace_correlation(tmp_path, capsys):
    """Bench rows carry the run trace ID; a regression report must
    stamp baseline/current traces (top level AND per regression row)
    so explain_perf --regression resolves the right ledger."""
    base, cur = str(tmp_path / "b.jsonl"), str(tmp_path / "c.jsonl")
    _write_jsonl(base, [{"metric": "t", "value": 100,
                         "trace": "aaaa-1111"}])
    _write_jsonl(cur, [{"metric": "t", "value": 10,
                        "trace": "bbbb-2222"}])
    out = str(tmp_path / "report.json")
    rc = bench_compare.main(["--baseline", base, "--current", cur,
                             "--out", out])
    assert rc == 1
    report = json.loads((tmp_path / "report.json").read_text())
    assert perf_schema.validate(report) == []
    assert report["baseline_trace"] == "aaaa-1111"
    assert report["current_trace"] == "bbbb-2222"
    reg = report["regressions"][0]
    assert reg["baseline_trace"] == "aaaa-1111"
    assert reg["current_trace"] == "bbbb-2222"
    # the operator is told the drill-down command
    assert "explain_perf.py --regression" in capsys.readouterr().err
    # multi-run files: each regression follows ITS row's trace, not
    # the document's first-seen one
    _write_jsonl(base, [{"metric": "a", "value": 100,
                         "trace": "runA-base"},
                        {"metric": "b", "value": 100,
                         "trace": "runB-base"}])
    _write_jsonl(cur, [{"metric": "a", "value": 100,
                        "trace": "runA-cur"},
                       {"metric": "b", "value": 10,
                        "trace": "runB-cur"}])
    assert bench_compare.main(["--baseline", base, "--current", cur,
                               "--out", out]) == 1
    report = json.loads((tmp_path / "report.json").read_text())
    reg = report["regressions"][0]
    assert reg["row"] == "b"
    assert reg["baseline_trace"] == "runB-base"
    assert reg["current_trace"] == "runB-cur"


# ----------------------------------------------------------------------
# trace_report: cost-registry columns in the span table + Perfetto
# ----------------------------------------------------------------------
def _tagged_ledger_rows():
    return [
        {"t": "meta", "trace": "cost-1", "pid": 1,
         "epoch": 1e9, "mono": 0.0, "ring": 4096},
        {"t": "span", "name": "ingress.dispatch", "trace": "cost-1",
         "tid": 1, "ts": 0.0, "dur": 0.25, "sid": 2,
         "a": {"chunk": 0, "program": "fused_scan",
               "sig": "i32[16,32768],b1[16,32768]"}},
        {"t": "span", "name": "ingress.prep", "trace": "cost-1",
         "tid": 1, "ts": 0.3, "dur": 0.01, "sid": 3,
         "a": {"chunk": 0}},
    ]


def test_trace_report_span_table_carries_cost_columns(tmp_path):
    cost = trace_report.cost_index(FIXTURE)
    assert cost[("fused_scan",
                 "i32[16,32768],b1[16,32768]")]["flops"] == 47352212
    rows = {r["span"]: r
            for r in trace_report.span_rows(_tagged_ledger_rows(),
                                            cost)}
    disp = rows["ingress.dispatch"]
    assert disp["program"] == "fused_scan"
    assert disp["flops"] == 47352212
    assert disp["bytes_accessed"] == 186835344
    assert disp["bound"] == "bytes"
    assert "program" not in rows["ingress.prep"]   # untagged: no cols
    # the rendered table shows the program + FLOPs/bytes annotation
    text = trace_report.render(_tagged_ledger_rows(), cost=cost)
    assert "fused_scan" in text
    assert "GF" in text and "bytes" in text


def test_trace_report_perfetto_args_carry_cost(tmp_path):
    cost = trace_report.cost_index(FIXTURE)
    trace = trace_report.to_perfetto(_tagged_ledger_rows(), cost)
    disp = next(e for e in trace["traceEvents"]
                if e["name"] == "ingress.dispatch")
    assert disp["args"]["flops"] == 47352212
    assert disp["args"]["bound"] == "bytes"
    # the CLI end-to-end: --perf annotates, exports, exits 0
    ledger = tmp_path / "l.jsonl"
    _write_jsonl(str(ledger), _tagged_ledger_rows())
    perf = tmp_path / "PERF.json"
    perf.write_text(json.dumps(FIXTURE))
    out = str(tmp_path / "trace.json")
    assert trace_report.main([str(ledger), "--perf", str(perf),
                              "--perfetto", out]) == 0
    with open(out) as f:
        evs = json.load(f)["traceEvents"]
    assert any(e.get("args", {}).get("flops") for e in evs)


# ----------------------------------------------------------------------
# explain_perf: the attribution drill-down (tools/explain_perf.py)
# ----------------------------------------------------------------------
explain_perf = _load_tool("explain_perf")


def test_explain_perf_committed_row_attributes(capsys):
    """The acceptance pin: run on the committed 524K/32768 CPU row
    (PERF_cpu.json cost_model + its committed ledger) — per-stage and
    per-program attribution, stage totals reconciling with the ledger
    within the default 5%, exit 0."""
    perf = os.path.join(REPO, "PERF_cpu.json")
    if not os.path.exists(perf):
        pytest.skip("PERF_cpu.json not committed")
    assert explain_perf.main(["--perf", perf]) == 0
    out = capsys.readouterr().out
    assert "stage attribution" in out
    assert "reconciled: 100.0% mapped, tolerance 5.0%" in out
    for program in ("fused_scan", "triangle_stream"):
        assert program + "@" in out, program
    assert "ranked suspects" in out


def test_explain_perf_stage_attribution_and_containers():
    """Leaf spans map to their stages; container spans are excluded
    so time is never double-booked — by name (the known envelopes)
    AND structurally (any span that parents another, even under an
    unknown name); the two independent accountings agree."""
    records = _tagged_ledger_rows() + [
        {"t": "span", "name": "ingress.chunk", "trace": "cost-1",
         "tid": 1, "ts": 0.0, "dur": 0.26, "sid": 1,
         "a": {"chunk": 0}},                # known container: excluded
        {"t": "span", "name": "step.triangles", "trace": "cost-1",
         "tid": 1, "ts": 0.4, "dur": 0.51, "sid": 10},  # parents a
        {"t": "span", "name": "ingress.finalize", "trace": "cost-1",
         "tid": 1, "ts": 0.4, "dur": 0.5, "sid": 4, "par": 10,
         "a": {"chunk": 0}},                # ...leaf: envelope excluded
        {"t": "span", "name": "step.snapshot_extract", "trace": "cost-1",
         "tid": 1, "ts": 0.92, "dur": 0.04, "sid": 11},  # host extraction
    ]
    stages, attributed, ledger_total, unmapped = \
        explain_perf.stage_attribution(records)
    by_stage = {r["stage"]: r for r in stages}
    assert by_stage["dispatch"]["total_s"] == 0.25
    assert by_stage["prep"]["total_s"] == 0.01
    # step.triangles maps to a stage but PARENTS the finalize span —
    # only the child leaf counts, never both; the snapshot extraction
    # after the d2h joins the same stage
    assert by_stage["d2h+finalize"]["total_s"] == 0.54
    assert by_stage["d2h+finalize"]["count"] == 2
    assert by_stage["dispatch"]["count"] == 1
    assert attributed == pytest.approx(0.80, abs=1e-6)
    assert attributed == pytest.approx(ledger_total, rel=1e-3)
    assert unmapped == []
    # program attribution: the finalize span's d2h time lands on the
    # program whose chunk it drained
    progs = explain_perf.program_attribution(
        records, FIXTURE["cost_model"]["programs"])
    row = next(r for r in progs if r["program"] == "fused_scan")
    assert row["dispatches"] == 1
    assert row["materialize_s"] == 0.5
    assert row["flops"] == 47352212


def test_explain_perf_suspect_heuristics():
    """A recompile_storm event and a finalize-dominated ledger each
    fire their suspect, ranked by score."""
    records = _tagged_ledger_rows() + [
        {"t": "span", "name": "ingress.finalize", "trace": "cost-1",
         "tid": 1, "ts": 0.4, "dur": 5.0, "sid": 4,
         "a": {"chunk": 0}},
        {"t": "event", "name": "recompile_storm", "trace": "cost-1",
         "tid": 1, "ts": 0.5, "a": {"fn": "fused_scan"}},
    ]
    stages, _att, _led, _un = explain_perf.stage_attribution(records)
    progs = explain_perf.program_attribution(
        records, FIXTURE["cost_model"]["programs"])
    suspects = explain_perf.rank_suspects(stages, progs, records)
    names = [s["suspect"] for s in suspects]
    assert "recompile_storm" in names
    assert "host_sync" in names
    assert "launch_bound" in names        # 0.25 s vs a sub-ms roofline
    scores = [s["score"] for s in suspects]
    assert scores == sorted(scores, reverse=True)
    storm = next(s for s in suspects
                 if s["suspect"] == "recompile_storm")
    assert "fused_scan" in storm["evidence"]


def test_explain_perf_unmapped_spans_fail_conservation(tmp_path,
                                                       capsys):
    """The stage map polices itself: leaf time under a span name the
    stage map doesn't know (beyond --tolerance of the total) exits
    non-zero and names the unmapped spans."""
    ledger = tmp_path / "l.jsonl"
    _write_jsonl(str(ledger), _tagged_ledger_rows() + [
        {"t": "span", "name": "brand.new_stage", "trace": "cost-1",
         "tid": 1, "ts": 1.0, "dur": 4.0, "sid": 7}])
    assert explain_perf.main(["--ledger", str(ledger)]) == 1
    err = capsys.readouterr().err
    assert "could not name" in err
    assert "brand.new_stage" in err
    # inside tolerance the same ledger attributes fine
    assert explain_perf.main(["--ledger", str(ledger),
                              "--tolerance", "0.97"]) == 0


def test_explain_perf_error_exits(tmp_path, capsys):
    # no ledger resolvable → 2
    assert explain_perf.main([]) == 2
    assert "no ledger" in capsys.readouterr().err
    # a ledger with no span records → 1, with the arming hint
    empty = tmp_path / "empty.jsonl"
    _write_jsonl(str(empty), [{"t": "meta", "trace": "x", "pid": 1,
                               "epoch": 1e9, "mono": 0.0}])
    assert explain_perf.main(["--ledger", str(empty)]) == 1
    assert "GS_TELEMETRY=1" in capsys.readouterr().err


def test_explain_perf_regression_correlation(tmp_path, capsys):
    """The sentry→drill-down handoff: a bench_compare --out report's
    current_trace selects the ledger records, and the regression rows
    are echoed first."""
    ledger = tmp_path / "l.jsonl"
    rows = _tagged_ledger_rows()
    # a second run's records under a different trace id: must be
    # filtered OUT when the regression names trace cost-1
    rows += [{"t": "span", "name": "ingress.dispatch",
              "trace": "other-2", "tid": 1, "ts": 9.0, "dur": 9.0,
              "sid": 9, "a": {"chunk": 0}}]
    _write_jsonl(str(ledger), rows)
    report = tmp_path / "report.json"
    report.write_text(json.dumps({
        "regressions": [{"row": "t", "field": "value",
                         "baseline": 100, "current": 10, "ratio": 0.1,
                         "tolerance": 0.2,
                         "baseline_trace": "aaaa-1111",
                         "current_trace": "cost-1"}],
        "baseline_trace": "aaaa-1111", "current_trace": "cost-1"}))
    rc = explain_perf.main(["--ledger", str(ledger),
                            "--regression", str(report), "--json"])
    assert rc == 0
    captured = capsys.readouterr()
    assert "regression: t.value 100 -> 10" in captured.err
    doc = json.loads(captured.out)
    # only the regression's trace was attributed (9 s span excluded)
    assert doc["attributed_total_s"] == pytest.approx(0.26, abs=1e-6)
    assert doc["regression"]["current_trace"] == "cost-1"


def test_update_perf_md_appends_block_when_markers_absent(tmp_path):
    perf_path = str(tmp_path / "PERF.json")
    md_path = str(tmp_path / "PERF.md")
    with open(perf_path, "w") as f:
        json.dump(FIXTURE, f)
    with open(md_path, "w") as f:
        f.write("# PERF\n")
    update_perf_md.main(perf_path, md_path)
    with open(md_path) as f:
        out = f.read()
    assert out.startswith("# PERF")
    assert update_perf_md.MARK_BEGIN in out


# ----------------------------------------------------------------------
# chaos soak-summary schema (logs/CHAOS_*.json; ISSUE 12 serve leg)
# ----------------------------------------------------------------------
def _chaos_doc(**over):
    doc = {
        "parity": True,
        "fault_classes_fired": ["kill_resume"],
        "serve_leg": {
            "parity": True,
            "kill": {"parity": True},
            "torn_tail": {"parity": True},
            "slow_client": {"parity": True, "shed": True},
            "drain": {"parity": True, "rc": 0, "sealed": True,
                      "digest_match": True},
        },
    }
    doc.update(over)
    return doc


def test_chaos_schema_accepts_well_formed_serve_leg():
    doc = _chaos_doc()
    assert perf_schema.is_chaos(doc)
    assert perf_schema.validate_chaos(doc) == []


def test_chaos_schema_rejects_divergence_and_bad_drain():
    assert any("parity" in e for e in
               perf_schema.validate_chaos(_chaos_doc(parity=False)))
    bad = _chaos_doc()
    bad["serve_leg"]["parity"] = False
    assert any("serve_leg" in e for e in
               perf_schema.validate_chaos(bad))
    bad = _chaos_doc()
    bad["serve_leg"]["drain"]["rc"] = 143
    assert any("exit 0" in e for e in
               perf_schema.validate_chaos(bad))
    bad = _chaos_doc()
    del bad["serve_leg"]["drain"]["sealed"]
    assert any("sealed" in e for e in
               perf_schema.validate_chaos(bad))


def test_chaos_schema_legs_are_additive():
    # older soaks predate newer legs: absent legs are fine, present
    # ones must carry their keys
    doc = _chaos_doc()
    del doc["serve_leg"]
    assert perf_schema.validate_chaos(doc) == []
    doc = _chaos_doc(tenancy_leg={"parity": True})
    errs = perf_schema.validate_chaos(doc)
    assert any("tenancy_leg" in e and "faults_fired" in e
               for e in errs)


@pytest.mark.parametrize("fname", ["CHAOS_resident.json",
                                   "CHAOS_tenancy.json",
                                   "CHAOS_serve.json"])
def test_committed_chaos_logs_validate(fname):
    path = os.path.join(REPO, "logs", fname)
    if not os.path.exists(path):
        pytest.skip("%s not committed" % fname)
    with open(path) as f:
        doc = json.load(f)
    assert perf_schema.is_chaos(doc)
    assert perf_schema.validate_chaos(doc) == []

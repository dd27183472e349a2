#!/usr/bin/env python
"""Regenerate the auto-generated measurement section of PERF.md from
the committed PERF.json (roofline, device-trace breakdown, collective
measured-vs-modeled rows), between the ROOFLINE:BEGIN/END markers.

Run after tools/profile_kernels.py;
idempotent, and a no-op when PERF.json lacks the new sections. Keeping
the renderer separate from the profiler means an unattended chip run
updates the human-readable evidence table in the same window that
produces the JSON — no transcription step whose raw output can be lost
(the round-2 failure mode this tooling exists to prevent).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MARK_BEGIN = "<!-- ROOFLINE:BEGIN (auto-generated by tools/update_perf_md.py) -->"
MARK_END = "<!-- ROOFLINE:END -->"


def _fmt(v, nd=3):
    if v is None:
        return "—"
    if isinstance(v, float):
        return ("%%.%df" % nd) % v
    return str(v)


def render(perf: dict) -> str:
    lines = [MARK_BEGIN, ""]
    backend = perf.get("backend", "?")
    device = perf.get("device", "?")
    lines += [
        "## Measured roofline & trace (auto-generated)",
        "",
        "Backend of record: **%s** (`%s`). CPU-backend rows are" % (
            backend, device),
        "structure/fallback evidence only; chip rows replace them",
        "wholesale when a chip run completes.",
        "",
    ]
    degr = perf.get("degradations") or []
    if degr:
        lines += [
            "### DEGRADED RUN — tier demotions during this profile",
            "",
            "Rows below were measured while the runtime had fallen off",
            "its resolved tier (core/driver tier-demotion ladder); they",
            "are availability evidence, NOT device-tier measurements.",
            "",
            "| section | from | to | window | mesh | shard | reason |",
            "|---|---|---|---|---|---|---|",
        ]
        for e in degr:
            ms = e.get("mesh_shape")
            lines.append("| %s | %s | %s | %s | %s | %s | %s |" % (
                e.get("section", "?"), e.get("from"), e.get("to"),
                e.get("window"),
                "single-chip" if ms is None else "×".join(
                    str(x) for x in ms),
                "-" if e.get("shard_id") is None else e["shard_id"],
                (e.get("reason") or "")[:80]))
        lines.append("")
    roof = perf.get("roofline") or {}
    rows = roof.get("rows") or []
    if rows:
        peaks = roof.get("peaks", {})
        lines += [
            "### Roofline (vs %s: %s TFLOP/s bf16, %s GB/s HBM)" % (
                peaks.get("hw", "?"), peaks.get("bf16_tflops", "?"),
                peaks.get("hbm_gbps", "?")),
            "",
            "| program | ms | GFLOP/s | MFU | GB/s | HBM frac | bound |"
            " intensity |",
            "|---|---|---|---|---|---|---|---|",
        ]
        for r in rows:
            lines.append("| %s | %s | %s | %s | %s | %s | %s | %s |" % (
                r.get("program"), _fmt(r.get("ms")),
                _fmt(r.get("gflops_achieved"), 2),
                _fmt(r.get("mfu_vs_bf16_peak"), 5),
                _fmt(r.get("gbps_achieved"), 2),
                _fmt(r.get("hbm_frac_of_peak"), 5),
                r.get("bound", "—"),
                _fmt(r.get("arith_intensity_flops_per_byte"), 2)))
        lines.append("")
    tr = perf.get("trace") or {}
    if tr.get("top_ops"):
        lines += [
            "### Device trace: one %s-window eb=%s dispatch (%s ms; "
            "raw in `%s/`)" % (
                tr.get("windows"), tr.get("edge_bucket"),
                _fmt(tr.get("dispatch_wall_ms")), tr.get("trace_dir")),
            "",
            "| op | total ms | calls |",
            "|---|---|---|",
        ]
        for op in tr["top_ops"][:12]:
            lines.append("| `%s` | %s | %s |" % (
                op["op"], _fmt(op["total_ms"]), op["calls"]))
        lines.append("")
    tiers = [("host_stream", "Triangle window stream: host (numpy) "
              "tier vs device kernel", "host_vs_device"),
             ("host_reduce", "Windowed reduce: host (numpy) tier vs "
              "device segment kernels", "host_vs_device")]
    for key, title, ratio_key in tiers:
        rows = perf.get(key) or []
        if not rows:
            continue
        lines += [
            "### %s" % title, "",
            "| config | parity | host edges/s | device edges/s | "
            "host/device |",
            "|---|---|---|---|---|",
        ]
        for r in rows:
            cfg = "eb=%s" % r.get("edge_bucket")
            if r.get("name"):
                cfg = "%s %s" % (r["name"], cfg)
            lines.append("| %s | %s | %s | %s | %s |" % (
                cfg, r.get("parity"),
                r.get("host_edges_per_s"), r.get("device_edges_per_s"),
                _fmt(r.get(ratio_key), 2)))
        lines.append("")
    pipe_rows = perf.get("pipeline_stages") or []
    if pipe_rows:
        lines += [
            "### Ingress pipeline per-stage timing "
            "(prep ‖ h2d ‖ compute; tools/profile_kernels.py "
            "`pipeline_stages`)", "",
            "Per-chunk means over the timed reps; `prep` sums across "
            "the worker pool (CPU time, not critical path). Speedup "
            "is the pipelined path vs the same path forced "
            "single-threaded-synchronous, counts parity asserted.",
            "",
            "| engine | config | workers | prep ms | h2d ms | "
            "compute ms | pipelined edges/s | sync edges/s | "
            "speedup | parity |",
            "|---|---|---|---|---|---|---|---|---|---|",
        ]
        for r in pipe_rows:
            cfg = "eb=%s %s" % (r.get("edge_bucket"),
                                r.get("ingress", ""))
            lines.append(
                "| %s | %s | %s | %s | %s | %s | %s | %s | %s | %s |"
                % (r.get("engine"), cfg.strip(), r.get("workers"),
                   _fmt(r.get("prep_ms_per_chunk")),
                   _fmt(r.get("h2d_ms_per_chunk")),
                   _fmt(r.get("compute_ms_per_chunk")),
                   r.get("pipelined_edges_per_s"),
                   r.get("sync_edges_per_s"),
                   _fmt(r.get("pipeline_speedup"), 2),
                   r.get("parity")))
        lines.append("")
    probes = {p.get("probe"): p
              for p in (perf.get("ingress_probes") or [])
              if isinstance(p, dict)}
    ab_rows = perf.get("ingress_ab") or []
    if probes or ab_rows:
        lines += [
            "### Stream-dispatch decomposition (tools/ingress_ab.py "
            "probes)", "",
            "End-to-end chunk time ≈ h2d transfer + dispatch "
            "round-trip + device compute; the residual after compact "
            "ingress / deeper chunks is attributable, not mysterious.",
            "",
        ]
        lat = probes.get("dispatch_latency")
        if lat:
            lines.append("- dispatch round-trip: **%s s**"
                         % _fmt(lat.get("round_trip_s"), 4))
        h2d = probes.get("h2d")
        if h2d:
            lines.append(
                "- h2d bandwidth: standard %s MB/s, compact %s MB/s "
                "(%s bytes vs %s per chunk)" % (
                    _fmt((h2d.get("std_bytes_per_s") or 0) / 1e6, 1),
                    _fmt((h2d.get("compact_bytes_per_s") or 0) / 1e6,
                         1),
                    h2d.get("std_bytes"), h2d.get("compact_bytes")))
        dc = probes.get("device_compute")
        if dc:
            lines.append(
                "- device compute (resident data, eb=%s, %s windows/"
                "dispatch): %s s/chunk → **%s edges/s compute-only "
                "ceiling**" % (
                    dc.get("eb"), dc.get("windows_per_dispatch"),
                    _fmt(dc.get("compute_s"), 4),
                    dc.get("compute_only_edges_per_s")))
        for r in ab_rows:
            if r.get("probe") == "stream_ab":
                lines.append(
                    "- end-to-end A/B (%s edges): standard %s edges/s"
                    " vs compact %s edges/s — speedup %s, parity %s"
                    % (r.get("num_edges"), r.get("std_edges_per_s"),
                       r.get("compact_edges_per_s"),
                       _fmt(r.get("speedup"), 3), r.get("parity")))
        lines.append("")
    eg_rows = perf.get("egress_ab") or []
    if eg_rows:
        lines += [
            "### d2h egress A/B: full-vector vs delta-compacted "
            "(tools/egress_ab.py)", "",
            "Exact parity asserted before any speedup is claimed; "
            "median-of-3 with min/max dispersion. Delta egress is "
            "adopted (ops/delta_egress.resolve_egress) only when "
            "every row clears parity AND >=1.05x.",
            "",
            "| probe | config | parity | full edges/s | delta edges/s"
            " | speedup | worst..best |",
            "|---|---|---|---|---|---|---|",
        ]
        for r in eg_rows:
            lines.append("| %s | eb=%s vb=%s | %s | %s | %s | %s | "
                         "%s..%s |" % (
                             r.get("probe"), r.get("eb"), r.get("vb"),
                             r.get("parity"),
                             r.get("full_edges_per_s"),
                             r.get("delta_edges_per_s"),
                             _fmt(r.get("speedup"), 3),
                             _fmt(r.get("speedup_worst"), 3),
                             _fmt(r.get("speedup_best"), 3)))
        lines.append("")
    res_rows = perf.get("resident_ab") or []
    if res_rows:
        lines += [
            "### Resident-state tier A/B: megakernel vs per-window "
            "dispatch (tools/resident_ab.py)", "",
            "Exact parity asserted before any speedup is claimed; "
            "median-of-3 with min/max dispersion. `speedup` is the "
            "resident megakernel vs ONE DISPATCH PER WINDOW (the "
            "round-trip wall); adoption "
            "(ops/resident_engine.resolve_resident) additionally "
            "requires >=1.05x over the best committed alternative "
            "tier (scan AND native) on every driver row.",
            "",
            "| probe | config | parity | resident edges/s | "
            "scan edges/s | per-window edges/s | native edges/s | "
            "speedup | vs scan |",
            "|---|---|---|---|---|---|---|---|---|",
        ]
        for r in res_rows:
            cfg = "eb=%s vb=%s spb=%s" % (
                r.get("eb"), r.get("vb"), r.get("superbatch"))
            lines.append("| %s | %s | %s | %s | %s | %s | %s | %s |"
                         " %s |" % (
                             r.get("probe"), cfg, r.get("parity"),
                             r.get("resident_edges_per_s"),
                             r.get("scan_edges_per_s"),
                             r.get("perwindow_edges_per_s"),
                             r.get("native_edges_per_s", "—"),
                             _fmt(r.get("speedup"), 3),
                             _fmt(r.get("speedup_vs_scan"), 3)))
        lines.append("")
    pw_rows = perf.get("pallas_ab") or []
    if pw_rows:
        lines += [
            "### Fused window megakernel A/B: Pallas vs XLA "
            "scan-of-gathers (tools/pallas_ab.py)", "",
            "Exact sha256 window parity against the host twins "
            "asserted before any speedup is claimed; median-of-3 "
            "with min/max dispersion. Adoption "
            "(ops/pallas_window.resolve_pallas_window) requires "
            "parity AND >=1.05x on every committed row; `interpret` "
            "rows (non-TPU backends) are parity evidence only — the "
            "kernel is interpreted there and times nothing real.",
            "",
            "| probe | config | interpret | parity | pallas edges/s"
            " | xla edges/s | speedup | worst..best |",
            "|---|---|---|---|---|---|---|---|",
        ]
        for r in pw_rows:
            cfg = "eb=%s vb=%s kb=%s" % (r.get("eb"), r.get("vb"),
                                         r.get("kb"))
            lines.append("| %s | %s | %s | %s | %s | %s | %s | "
                         "%s..%s |" % (
                             r.get("probe"), cfg,
                             r.get("interpret"), r.get("parity"),
                             r.get("pallas_edges_per_s"),
                             r.get("xla_edges_per_s"),
                             _fmt(r.get("speedup"), 3),
                             _fmt(r.get("speedup_worst"), 3),
                             _fmt(r.get("speedup_best"), 3)))
        lines.append("")
    tn_rows = perf.get("tenancy_ab") or []
    if tn_rows:
        lines += [
            "### Multi-tenant cohort A/B: one vmapped dispatch vs N "
            "sequential streams (tools/tenancy_ab.py)", "",
            "Per-tenant sha256 parity vs the sequential single-tenant "
            "oracle asserted before any speedup is claimed; "
            "median-of-3 with min/max dispersion. `cohort_serving` is "
            "the window-by-window serving shape (the dispatch wall "
            "paid once per round instead of N times); `cohort_batch` "
            "is the queue-drain shape against the chunked-scan "
            "oracle; `cohort_resident` is the donated stacked-carry "
            "super-batch tier (one row per cohort size N — the N=1 "
            "row's ~1.0 speedup is the honest floor the auto gate "
            "must clear); `cohort_pallas` is the tenant-axis Pallas "
            "megakernel (interpret=True rows are parity-only "
            "evidence; resolve_cohort_pallas ignores them for "
            "adoption).",
            "",
            "| probe | tenants | config | interp | parity | "
            "cohort edges/s | sequential edges/s | speedup | "
            "worst..best |",
            "|---|---|---|---|---|---|---|---|---|",
        ]
        for r in tn_rows:
            lines.append("| %s | %s | eb=%s vb=%s | %s | %s | %s | "
                         "%s | %s | %s..%s |" % (
                             r.get("probe"), r.get("tenants"),
                             r.get("eb"), r.get("vb"),
                             r.get("interpret") or "",
                             r.get("parity"),
                             r.get("tenant_edges_per_s"),
                             r.get("sequential_edges_per_s"),
                             _fmt(r.get("speedup"), 3),
                             _fmt(r.get("speedup_worst"), 3),
                             _fmt(r.get("speedup_best"), 3)))
        lines.append("")
    pu_rows = perf.get("pump_ab") or []
    if pu_rows:
        lines += [
            "### Async serving pump & pane-composed sliding windows "
            "(tools/pump_ab.py)", "",
            "`serving_pump`: GS_PUMP=async vs the sync single-lock "
            "legacy on a paced 8-tenant loopback serve run — "
            "per-tenant sha256 parity asserted, then the latency "
            "plane's queue_wait/e2e p99 must IMPROVE (sync/async "
            "ratio, higher is better; a JIT-warmed cohort and "
            "identical pacing in both modes, median rep of 3). "
            "`sliding_panes`: pane-composed sliding reduce vs the "
            "naive per-emission refold twin at bit-exact parity.",
            "",
            "| probe | config | parity | queue_wait p99 s→a | "
            "e2e p99 s→a | speedup | worst..best |",
            "|---|---|---|---|---|---|---|",
        ]
        for r in pu_rows:
            if r.get("probe") == "serving_pump":
                cfg = "N=%s eb=%s vb=%s" % (
                    r.get("tenants"), r.get("eb"), r.get("vb"))
                qw = "%s→%s (%sx)" % (
                    _fmt(r.get("sync_queue_wait_p99_s"), 3),
                    _fmt(r.get("async_queue_wait_p99_s"), 3),
                    _fmt(r.get("queue_wait_improvement"), 2))
                e2e = "%s→%s (%sx)" % (
                    _fmt(r.get("sync_e2e_p99_s"), 3),
                    _fmt(r.get("async_e2e_p99_s"), 3),
                    _fmt(r.get("e2e_improvement"), 2))
                spread = "—"
            else:
                cfg = "eb=%s slide=%s (%s panes/window)" % (
                    r.get("eb"), r.get("slide"),
                    r.get("panes_per_window"))
                qw = e2e = "—"
                spread = "%s..%s" % (
                    _fmt(r.get("speedup_worst"), 3),
                    _fmt(r.get("speedup_best"), 3))
            lines.append("| %s | %s | %s | %s | %s | %s | %s |" % (
                r.get("probe"), cfg, r.get("parity"), qw, e2e,
                _fmt(r.get("speedup"), 3), spread))
        lines.append("")
    gn_rows = perf.get("gnn_ab") or []
    if gn_rows:
        lines += [
            "### Windowed-GNN A/B: MXU-shaped message passing at "
            "bit-exact parity (tools/gnn_ab.py)", "",
            "Every speedup is claimed only after sha256 parity over "
            "BOTH the summary stream and the final [vb, F] feature "
            "slab (the dyadic-lattice exactness contract of "
            "ops/gnn_window — device ≡ numpy twin is an equality, "
            "not a tolerance); median-of-3 with min/max dispersion. "
            "`gnn_engine` is the fused device scan vs the numpy "
            "host twin (on CPU the twin wins — that row is the "
            "honest dispatch-overhead floor, not a regression); "
            "`gnn_cohort` folds N tenants' windows in one vmapped "
            "dispatch vs N sequential engines (the N=1 row is the "
            "no-gain floor); `gnn_pallas` is the fused single-pass "
            "kernel vs the XLA gather/segment-sum round "
            "(interpret=True rows are parity-only evidence; "
            "resolve_gnn_pallas ignores them for adoption).",
            "",
            "| probe | N | config | interp | parity | "
            "edges/s (subject) | edges/s (baseline) | "
            "edge-feat/s | speedup | worst..best |",
            "|---|---|---|---|---|---|---|---|---|---|",
        ]
        for r in gn_rows:
            subj = (r.get("engine_edges_per_s")
                    or r.get("cohort_edges_per_s")
                    or r.get("pallas_edges_per_s"))
            base = (r.get("host_edges_per_s")
                    or r.get("sequential_edges_per_s")
                    or r.get("xla_edges_per_s"))
            lines.append(
                "| %s | %s | eb=%s vb=%s F=%s | %s | %s | %s | %s | "
                "%s | %s | %s..%s |" % (
                    r.get("probe"), r.get("tenants") or "—",
                    r.get("eb"), r.get("vb"), r.get("feature_dim"),
                    r.get("interpret") or "",
                    r.get("parity"), subj, base,
                    r.get("gnn_edge_features_per_s") or "—",
                    _fmt(r.get("speedup"), 3),
                    _fmt(r.get("speedup_worst"), 3),
                    _fmt(r.get("speedup_best"), 3)))
        lines.append("")
    at_rows = perf.get("autotune") or []
    if at_rows:
        lines += [
            "### Online dispatch autotuner (ops/autotune.py, "
            "tools/profile_kernels.py `autotune`)", "",
            "Static committed-gate configuration vs the live tuner, "
            "cold cache and seeded from the previous run's persisted "
            "optimum; counts parity asserted.",
            "",
            "| engine | config | static edges/s | tuned (cold) | "
            "tuned (seeded) | seeded/static | chosen | parity |",
            "|---|---|---|---|---|---|---|---|",
        ]
        for r in at_rows:
            chosen = r.get("chosen") or {}
            lines.append("| %s | eb=%s | %s | %s | %s | %s | %s | %s |"
                         % (r.get("engine"), r.get("edge_bucket"),
                            r.get("static_edges_per_s"),
                            r.get("tuned_cold_edges_per_s"),
                            r.get("tuned_seeded_edges_per_s"),
                            _fmt(r.get("seeded_vs_static"), 3),
                            " ".join("%s=%s" % kv
                                     for kv in sorted(chosen.items()))
                            or "—",
                            r.get("parity")))
        lines.append("")
    mm = perf.get("metrics") or {}
    if mm:
        lines += [
            "### Metrics plane: armed-registry overhead "
            "(utils/metrics, tools/profile_kernels.py `metrics`)",
            "",
            "GS_METRICS=1 on the %s/%s row: counts identical to the "
            "disarmed run (parity=%s), armed/disarmed wall ratio %s "
            "(bar: 1.05). Disarmed %s edges/s, armed %s edges/s; "
            "%s windows observed live. Serve it with "
            "`GS_METRICS_PORT=<port>` → `/metrics` + `/healthz`."
            % (mm.get("num_edges"), mm.get("edge_bucket"),
               mm.get("parity"), _fmt(mm.get("overhead_ratio"), 3),
               mm.get("disarmed_edges_per_s"),
               mm.get("armed_edges_per_s"),
               mm.get("windows_observed")),
            "",
        ]
    pv = perf.get("provenance") or {}
    if pv:
        attr = pv.get("attribution") or {}
        lines += [
            "### Provenance ledger: armed-overhead + truth proof "
            "(utils/provenance, tools/profile_kernels.py "
            "`provenance`)",
            "",
            "GS_PROVENANCE=1 on the %s/%s row: summaries identical "
            "to the disarmed run (parity=%s), armed/disarmed wall "
            "ratio %s (bar: 1.02). Disarmed %s edges/s, armed %s "
            "edges/s; %s ledger records across %s segment(s), all %s "
            "distinct windows digest-verified against the disarmed "
            "baseline (knob fingerprint `%s`). Per-tenant "
            "attribution of a %ss dispatch reconciles=%s across %d "
            "tenant rows (tools/tenant_report.py renders the live "
            "table; tools/replay_window.py re-derives any record)."
            % (pv.get("num_edges"), pv.get("edge_bucket"),
               pv.get("parity"), _fmt(pv.get("overhead_ratio"), 3),
               pv.get("disarmed_edges_per_s"),
               pv.get("armed_edges_per_s"), pv.get("records"),
               pv.get("segments"), pv.get("windows_verified"),
               pv.get("knob_fingerprint"), attr.get("span_s"),
               attr.get("reconciles"), len(attr.get("rows") or ())),
            "",
        ]
    cm = perf.get("cost_model") or {}
    if cm.get("programs"):
        peaks = cm.get("peaks") or {}
        lines += [
            "### Program cost observatory (utils/costmodel, "
            "tools/profile_kernels.py `cost_model`)",
            "",
            "XLA `cost_analysis` FLOPs/bytes per program per abstract "
            "shape on the %s/%s row (parity=%s, trace `%s`), judged "
            "against a %s GFLOP/s / %s GB/s roofline. Drill into the "
            "committed ledger with `python tools/explain_perf.py "
            "--perf PERF_%s.json`." % (
                cm.get("num_edges"), cm.get("edge_bucket"),
                cm.get("parity"), cm.get("trace"),
                peaks.get("gflops"), peaks.get("gbps"),
                perf.get("backend", "?")),
            "",
            "| program | sig | GFLOPs | MB accessed | intensity | "
            "bound | dispatches | mean s | roofline frac |",
            "|---|---|---|---|---|---|---|---|---|",
        ]
        for r in cm["programs"]:
            flops, bts = r.get("flops"), r.get("bytes_accessed")
            lines.append(
                "| %s | `%s` | %s | %s | %s | %s | %s | %s | %s |" % (
                    r.get("program"), (r.get("sig") or "")[:48],
                    _fmt(flops / 1e9 if flops else None, 3),
                    _fmt(bts / 1e6 if bts else None, 1),
                    _fmt(r.get("arith_intensity_flops_per_byte"), 3),
                    r.get("bound", "—"),
                    r.get("dispatches"),
                    _fmt(r.get("measured_mean_s"), 4),
                    _fmt(r.get("roofline_frac"), 5)))
        lines.append("")
    gc = perf.get("gnn") or {}
    if gc.get("programs"):
        lines += [
            "### Windowed-GNN cost observatory (ops/gnn_window, "
            "tools/gnn_ab.py --commit / profile_kernels `gnn`)",
            "",
            "Analytic slab-model rows for the GNN programs on the "
            "%s-edge / eb=%s / F=%s acceptance shape (parity=%s vs "
            "disarmed AND the numpy twin; measured %s edges/s, %s "
            "edge-features/s). The dense update's 2·(vb+1)·F² MXU "
            "FLOPs lift the stated arithmetic intensity past every "
            "gather-only program's ~0.25 FLOPs/byte — the intensity "
            "column is the MODEL's claim per window; on the %s "
            "backend the achieved rate stays far below the roofline "
            "either way, and `bound` says which wall the model "
            "predicts on chip." % (
                gc.get("num_edges"), gc.get("edge_bucket"),
                gc.get("feature_dim"), gc.get("parity"),
                gc.get("edges_per_s"),
                gc.get("gnn_edge_features_per_s"),
                gc.get("backend", "?")),
            "",
            "| program | sig | MFLOPs | MB accessed | intensity "
            "FLOPs/byte | bound | dispatches |",
            "|---|---|---|---|---|---|---|",
        ]
        for r in gc["programs"]:
            flops, bts = r.get("flops"), r.get("bytes_accessed")
            lines.append("| %s | `%s` | %s | %s | %s | %s | %s |" % (
                r.get("program"), (r.get("sig") or "")[:48],
                _fmt(flops / 1e6 if flops else None, 2),
                _fmt(bts / 1e6 if bts else None, 2),
                _fmt(r.get("arith_intensity_flops_per_byte"), 3),
                r.get("bound", "—"),
                r.get("dispatches")))
        lines.append("")
    tel_rows = perf.get("telemetry") or []
    if tel_rows:
        tm = perf.get("telemetry_meta") or {}
        lines += [
            "### Flight recorder: per-stage span latencies "
            "(utils/telemetry, tools/profile_kernels.py `telemetry`)",
            "",
            "Armed-recorder run (GS_TELEMETRY=1), digests asserted "
            "identical to the disarmed run%s. Read any run's full "
            "ledger with `python tools/trace_report.py "
            "<GS_TRACE_DIR>/trace_<id>.jsonl`." % (
                "; armed/disarmed wall ratio %s"
                % _fmt(tm.get("overhead_ratio"), 3)
                if tm.get("overhead_ratio") is not None else ""),
            "",
            "| span | count | total ms | p50 ms | p95 ms | p99 ms |",
            "|---|---|---|---|---|---|",
        ]
        for r in tel_rows:
            lines.append("| `%s` | %s | %s | %s | %s | %s |" % (
                r.get("span"), r.get("count"),
                _fmt(r.get("total_ms")), _fmt(r.get("p50_ms")),
                _fmt(r.get("p95_ms")), _fmt(r.get("p99_ms"))))
        lines.append("")
    coll = (perf.get("sharded") or {}).get("collectives") or {}
    if coll.get("rows"):
        cfg = coll.get("config", {})
        lines += [
            "### Sharded collectives: measured vs modeled (n=%s, "
            "vb=%s, kb=%s, cap=%s)" % (
                cfg.get("n"), cfg.get("vb"), cfg.get("kb"),
                cfg.get("cap")),
            "",
            coll.get("note", ""),
            "",
            "| collective | modeled ICI bytes/chip | modeled ms "
            "(v5e ICI) | measured ms (%s) |" % coll.get(
                "backend", "cpu-virtual-mesh"),
            "|---|---|---|---|",
        ]
        for r in coll["rows"]:
            # a failed collective records an "error" key instead of a
            # measurement — render it, don't crash the unattended stage
            measured = (_fmt(r.get("measured_ms_cpu_mesh"))
                        if "error" not in r
                        else "error: %s" % r["error"][:60])
            lines.append("| %s | %s | %s | %s |" % (
                r["collective"], r["modeled_ici_bytes_per_chip"],
                _fmt(r["modeled_ms_v5e_ici"], 4), measured))
        lines.append("")
    lines.append(MARK_END)
    return "\n".join(lines)


def main(perf_path: str = None, md_path: str = None):
    """Regenerate md_path's marker block from perf_path (repo defaults;
    injectable so the tier-1 renderer round-trip test can run on a
    fixture without touching the committed evidence)."""
    perf_path = perf_path or os.path.join(REPO, "PERF.json")
    md_path = md_path or os.path.join(REPO, "PERF.md")
    try:
        with open(perf_path) as f:
            perf = json.load(f)
    except (OSError, ValueError) as e:
        sys.exit("no usable PERF.json: %r" % e)
    block = render(perf)
    with open(md_path) as f:
        md = f.read()
    if MARK_BEGIN in md:
        pre = md.split(MARK_BEGIN)[0]
        post = md.split(MARK_END, 1)[1] if MARK_END in md else "\n"
        md = pre + block + post
    else:
        md = md.rstrip() + "\n\n" + block + "\n"
    with open(md_path, "w") as f:
        f.write(md)
    print("PERF.md section regenerated (backend=%s)"
          % perf.get("backend"))


if __name__ == "__main__":
    main()

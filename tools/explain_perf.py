#!/usr/bin/env python
"""Answer "where did the time go" for a streaming run: the
drill-down that joins the flight recorder's ledger (utils/telemetry),
the program cost observatory (utils/costmodel, PERF.json `cost_model`)
and, optionally, a bench_compare regression report into one
attribution verdict:

  - **per-stage attribution**: the ledger's leaf stage spans (prep /
    h2d / dispatch / d2h+finalize / checkpoint, `other` for anything
    unmapped) summed per stage. Container spans are excluded
    STRUCTURALLY — any span that parents another span double-books
    its children's time, whatever it is named — with the known
    envelope names as a fallback for ledgers without parent links.
    The conservation check is on the mapped fraction: leaf time the
    stage map could NOT name (`other`) beyond `--tolerance`
    (default 5%) of the ledger's leaf-span total exits non-zero,
    naming the unmapped spans — a new span name can't silently
    vanish from the attribution;
  - **per-program attribution**: dispatch spans tagged program/sig
    (the cost observatory stamps them) joined with the cost
    registry's FLOPs/bytes → achieved-vs-roofline fraction and the
    bytes/FLOPs boundedness verdict per program per shape; each
    chunk-correlated finalize span is attributed to its chunk's
    program as materialize (d2h) time;
  - **ranked suspects**: deterministic heuristics over the above —
    recompile storm (durable events in the ledger), host-sync /
    d2h-bound (finalize-stage fraction), launch-bound (measured
    dispatch ≫ roofline-implied seconds), bytes-bound (the cost
    verdict where it dominates), prep-bound (host prep fraction).

Usage:
  python tools/explain_perf.py [--ledger L.jsonl] [--perf PERF_cpu.json]
        [--trace-id ID] [--regression REPORT.json] [--json]
        [--tolerance 0.05] [--top N]

With only --perf, the ledger is resolved from the committed
`cost_model` section (the profiler commits its attribution ledger
beside the rows). With --regression (a bench_compare --out report),
the regression rows and their trace IDs are printed first, so a
sentry's non-zero exit links directly to its attributed cause.

Exit status: 0 attributed; 1 no usable records OR the stage table
fails conservation; 2 usage/IO errors.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import importlib.util as _ilu  # noqa: E402


def _load_tool(name):
    spec = _ilu.spec_from_file_location(
        name, os.path.join(REPO, "tools", name + ".py"))
    mod = _ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


trace_report = _load_tool("trace_report")

# leaf stage spans → attribution stage. Container spans (chunk/round
# envelopes that ENCLOSE leaves) are excluded from totals entirely —
# counting both would double-book every second.
STAGE_OF = {
    "ingress.prep": "prep",
    "step.intern": "prep",
    "ingress.h2d": "h2d",
    "ingress.dispatch": "dispatch",
    "step.snapshot_scan": "dispatch",
    "step.triangles": "dispatch",
    "ingress.finalize": "d2h+finalize",
    "step.snapshot_wait": "d2h+finalize",
    "step.snapshot_extract": "d2h+finalize",
    "step.checkpoint": "checkpoint",
}
CONTAINERS = {
    "ingress.chunk", "fused_scan.round", "triangles.round",
    "reduce.stream", "driver.scan_round", "resident.superbatch",
    "sharded.stream", "sharded.window",
}
STAGE_ORDER = ("prep", "h2d", "dispatch", "d2h+finalize",
               "checkpoint", "other")


def leaf_spans(records):
    """Span records minus containers. A container is detected
    STRUCTURALLY — its span id is some other span's parent (keyed per
    trace: sids restart per recorder), so an envelope the CONTAINERS
    list doesn't know yet still can't double-book its children — with
    the known names as a fallback for ledgers without parent links."""
    spans = [r for r in records if r.get("t") == "span"]
    parents = {(r.get("trace"), r["par"])
               for r in spans if r.get("par") is not None}
    return [r for r in spans
            if r.get("name") not in CONTAINERS
            and (r.get("trace"), r.get("sid")) not in parents]


def stage_attribution(records):
    """Per-stage totals over the ledger's leaf spans, plus the
    conservation numbers: the attributed total, the independent
    leaf-span total (via trace_report's own accounting), and the
    `other` rows' unmapped span names — main() fails the run when
    the stage map couldn't name more than --tolerance of the time."""
    totals = {s: {"stage": s, "count": 0, "total_s": 0.0}
              for s in STAGE_ORDER}
    unmapped = {}
    for rec in leaf_spans(records):
        stage = STAGE_OF.get(rec.get("name"), "other")
        totals[stage]["count"] += 1
        totals[stage]["total_s"] += float(rec.get("dur", 0.0))
        if stage == "other":
            unmapped[rec.get("name")] = unmapped.get(
                rec.get("name"), 0) + 1
    # independent accounting through trace_report's own per-span rows
    # (same records, different code path — the cross-check)
    ledger_total = sum(r["total_ms"] for r in trace_report.span_rows(
        leaf_spans(records))) / 1e3
    attributed = sum(t["total_s"] for t in totals.values())
    rows = [dict(t, total_s=round(t["total_s"], 6),
                 frac=round(t["total_s"] / attributed, 4)
                 if attributed else 0.0)
            for t in (totals[s] for s in STAGE_ORDER) if t["count"]]
    return rows, round(attributed, 6), round(ledger_total, 6), \
        sorted(unmapped)


def program_attribution(records, cost_rows):
    """Per-(program, sig) measured economics joined with the cost
    registry: dispatch spans tagged by the observatory, plus each
    chunk-correlated finalize span attributed to its chunk's program
    as materialize (d2h) time."""
    from gelly_streaming_tpu.utils import costmodel

    cost_by_key = {}
    for row in cost_rows or []:
        cost_by_key[(row.get("program"), row.get("sig"))] = row
    measured = {}
    chunk_prog = {}
    # one time-ordered pass: chunk indices restart at 0 for every
    # pipelined call in the process, so a finalize must be attributed
    # to whichever program held its chunk id AT THAT TIME, not to the
    # last program that ever used the id
    for rec in sorted(leaf_spans(records),
                      key=lambda r: float(r.get("ts", 0.0))):
        a = rec.get("a") or {}
        prog = a.get("program")
        if prog:
            key = (prog, a.get("sig", "?"))
            m = measured.setdefault(key, {"count": 0, "total_s": 0.0,
                                          "materialize_s": 0.0})
            m["count"] += 1
            m["total_s"] += float(rec.get("dur", 0.0))
            if a.get("chunk") is not None:
                chunk_prog[(rec.get("trace"), a["chunk"])] = key
        elif rec.get("name") == "ingress.finalize":
            key = chunk_prog.get((rec.get("trace"), a.get("chunk")))
            if key is not None:
                measured[key]["materialize_s"] += float(
                    rec.get("dur", 0.0))
    rows = []
    for key, m in measured.items():
        entry = dict(cost_by_key.get(
            key, costmodel.classify({"program": key[0],
                                     "sig": key[1]})))
        costmodel.join_measure(entry, m["count"], m["total_s"])
        entry["materialize_s"] = round(m["materialize_s"], 6)
        rows.append(entry)
    rows.sort(key=lambda r: -(r.get("measured_total_s", 0.0)
                              + r.get("materialize_s", 0.0)))
    return rows


def tenant_attribution(records):
    """Per-tenant measured seconds over spans that carry the tenant
    label (the cohort's `tenant.single` dispatches, a tenant-labeled
    driver's steps), plus one `<cohort>` row aggregating the vmapped
    `cohort.dispatch` spans — whose time is SHARED by all tenants in
    the slab, so it is reported with its mean tenants-per-dispatch
    instead of being split by guesswork. Unlike the stage map,
    this table reads ALL spans (not just leaves): a tenant-labeled
    span legitimately envelopes its engine's internal chunk spans —
    its duration IS the tenant's wall time, and the table is rendered
    beside (never summed into) the conservation-checked stage totals.
    Empty ledger → empty list (the section only renders when a
    multi-tenant run produced it)."""
    per = {}
    cohort = {"count": 0, "total_s": 0.0, "tenants": 0, "edges": 0}
    for rec in (r for r in records if r.get("t") == "span"):
        a = rec.get("a") or {}
        if a.get("tenant") is not None:
            t = per.setdefault(str(a["tenant"]),
                               {"count": 0, "total_s": 0.0,
                                "edges": 0})
            t["count"] += 1
            t["total_s"] += float(rec.get("dur", 0.0))
            t["edges"] += int(a.get("edges") or 0)
        elif rec.get("name") == "cohort.dispatch":
            cohort["count"] += 1
            cohort["total_s"] += float(rec.get("dur", 0.0))
            cohort["tenants"] += int(a.get("tenants") or 0)
            cohort["edges"] += int(a.get("edges") or 0)
    rows = [dict(tenant=tid, count=t["count"],
                 total_s=round(t["total_s"], 6), edges=t["edges"])
            for tid, t in sorted(per.items())]
    if cohort["count"]:
        rows.append({
            "tenant": "<cohort>", "count": cohort["count"],
            "total_s": round(cohort["total_s"], 6),
            "edges": cohort["edges"],
            "mean_tenants_per_dispatch": round(
                cohort["tenants"] / cohort["count"], 2)})
    rows.sort(key=lambda r: -r["total_s"])
    return rows


def rank_suspects(stage_rows, prog_rows, records):
    """Deterministic heuristics → ranked suspect list, each with a
    score in [0, 1] and the evidence line an operator acts on."""
    stages = {r["stage"]: r for r in stage_rows}
    total = sum(r["total_s"] for r in stage_rows) or 1.0
    suspects = []

    storms = [r for r in records if r.get("t") == "event"
              and r.get("name") == "recompile_storm"]
    if storms:
        fns = sorted({(r.get("a") or {}).get("fn", "?")
                      for r in storms})
        suspects.append({
            "suspect": "recompile_storm", "score": 1.0,
            "evidence": "%d recompile_storm event(s) in the ledger "
                        "(fn: %s) — shape churn is recompiling per "
                        "dispatch; check bucket growth / signature "
                        "churn" % (len(storms), ", ".join(fns))})

    fin = stages.get("d2h+finalize", {"total_s": 0.0})["total_s"]
    if fin / total > 0.35:
        suspects.append({
            "suspect": "host_sync", "score": round(fin / total, 3),
            "evidence": "d2h+finalize holds %.0f%% of attributed time "
                        "— the materialize boundary (device→host "
                        "round trip) dominates; delta egress / deeper "
                        "chunks are the levers" % (100 * fin / total)})

    prep = stages.get("prep", {"total_s": 0.0})["total_s"]
    if prep / total > 0.40:
        suspects.append({
            "suspect": "prep_bound", "score": round(prep / total, 3),
            "evidence": "host prep holds %.0f%% of attributed time — "
                        "widen GS_PIPELINE_WORKERS or move to the "
                        "compact wire" % (100 * prep / total)})

    for row in prog_rows:
        roof = row.get("roofline_s")
        mean = row.get("measured_mean_s")
        if not roof or not mean:
            continue
        ratio = mean / roof
        if ratio > 20 and roof < 1e-3:
            import math

            suspects.append({
                "suspect": "launch_bound",
                "score": round(min(1.0, math.log10(ratio) / 3), 3),
                "evidence": "%s@%s: measured %.3g s/dispatch vs "
                            "roofline %.3g s (×%.0f) with a sub-ms "
                            "roofline — fixed dispatch overhead, not "
                            "compute, bounds it; batch more windows "
                            "per dispatch (resident tier)"
                            % (row.get("program"), row.get("sig"),
                               mean, roof, ratio)})
        elif row.get("bound") == "bytes" \
                and row.get("roofline_frac", 0) > 0.3:
            suspects.append({
                "suspect": "bytes_bound",
                "score": round(row["roofline_frac"], 3),
                "evidence": "%s@%s: bytes-bound at %.0f%% of its "
                            "roofline — intensity %.2f FLOPs/byte "
                            "under the machine balance; shrink the "
                            "wire (compact ingress / delta egress)"
                            % (row.get("program"), row.get("sig"),
                               100 * row["roofline_frac"],
                               row.get("arith_intensity_flops_per_byte")
                               or 0.0)})
    suspects.sort(key=lambda s: -s["score"])
    return suspects


def resolve_ledger(args, perf):
    """The ledger path: --ledger wins; else the committed cost_model
    section names one (repo-relative)."""
    if args.ledger:
        return args.ledger
    cm = (perf or {}).get("cost_model") or {}
    rel = cm.get("ledger")
    if rel:
        path = rel if os.path.isabs(rel) else os.path.join(REPO, rel)
        if os.path.exists(path):
            return path
    return None


def render(report, top=0):
    lines = ["explain_perf: trace=%s  (%d ledger records, %d leaf "
             "spans)" % (report["trace"] or "?",
                         report["ledger_records"],
                         report["leaf_spans"]), ""]
    lines += ["stage attribution (%.3f s attributed; ledger leaf "
              "total %.3f s; reconciled: %.1f%% mapped, tolerance "
              "%.1f%%):"
              % (report["attributed_total_s"],
                 report["ledger_total_s"],
                 100 * report["mapped_frac"],
                 100 * report["tolerance"])]
    lines += ["  %-14s %6s %10s %7s" % ("stage", "spans", "total s",
                                        "frac")]
    for r in report["stages"]:
        lines.append("  %-14s %6d %10.4f %6.1f%%"
                     % (r["stage"], r["count"], r["total_s"],
                        100 * r["frac"]))
    lines.append("")
    progs = report["programs"][:top] if top else report["programs"]
    if progs:
        lines.append("program attribution (dispatch spans tagged by "
                     "the cost observatory):")
        for r in progs:
            lines.append(
                "  %s@%s" % (r.get("program"), (r.get("sig") or "")[:48]))
            lines.append(
                "    dispatches=%s  dispatch_s=%s  materialize_s=%s  "
                "bound=%s" % (r.get("dispatches"),
                              r.get("measured_total_s"),
                              r.get("materialize_s"),
                              r.get("bound")))
            if r.get("flops"):
                lines.append(
                    "    flops=%s bytes=%s intensity=%s "
                    "roofline_frac=%s achieved=%s GFLOP/s"
                    % (r.get("flops"), r.get("bytes_accessed"),
                       r.get("arith_intensity_flops_per_byte"),
                       r.get("roofline_frac"),
                       r.get("achieved_gflops")))
        lines.append("")
    if report.get("tenants"):
        lines.append("tenant attribution (tenant-labeled spans; "
                     "<cohort> rows are shared vmapped dispatches):")
        for r in report["tenants"]:
            extra = ("  tenants/dispatch=%s"
                     % r["mean_tenants_per_dispatch"]
                     if "mean_tenants_per_dispatch" in r else "")
            lines.append("  %-16s spans=%-5d total_s=%-10.4f "
                         "edges=%d%s" % (r["tenant"], r["count"],
                                         r["total_s"], r["edges"],
                                         extra))
        lines.append("")
    if report["suspects"]:
        lines.append("ranked suspects:")
        for i, s in enumerate(report["suspects"], 1):
            lines.append("  %d. [%.2f] %s — %s"
                         % (i, s["score"], s["suspect"],
                            s["evidence"]))
    else:
        lines.append("no suspects fired — the run tracks its "
                     "roofline within the heuristics' thresholds")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--ledger", default=None,
                    help="run ledger (trace_*.jsonl); default: the "
                         "one the --perf cost_model section names")
    ap.add_argument("--perf", default=None,
                    help="PERF*.json with a cost_model section "
                         "(FLOPs/bytes per program)")
    ap.add_argument("--trace-id", default=None,
                    help="narrow the ledger to one run's records")
    ap.add_argument("--regression", default=None,
                    help="bench_compare --out report: print the "
                         "regressions + trace correlation first")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="stage-total conservation tolerance "
                         "(default 0.05 = 5%%)")
    ap.add_argument("--top", type=int, default=0,
                    help="limit the program table to the top N rows")
    ap.add_argument("--json", action="store_true",
                    help="print the report as JSON instead of text")
    args = ap.parse_args(argv)

    perf = None
    if args.perf:
        try:
            with open(args.perf) as f:
                perf = json.load(f)
        except (OSError, ValueError) as e:
            print("explain_perf: unreadable --perf %s (%s)"
                  % (args.perf, e), file=sys.stderr)
            return 2

    regression = None
    if args.regression:
        try:
            with open(args.regression) as f:
                regression = json.load(f)
        except (OSError, ValueError) as e:
            print("explain_perf: unreadable --regression %s (%s)"
                  % (args.regression, e), file=sys.stderr)
            return 2
        for r in regression.get("regressions") or []:
            print("regression: %s.%s %s -> %s (x%s)%s"
                  % (r.get("row"), r.get("field"), r.get("baseline"),
                     r.get("current"), r.get("ratio"),
                     "  [trace %s -> %s]" % (r.get("baseline_trace"),
                                             r.get("current_trace"))
                     if r.get("current_trace") else ""),
                  file=sys.stderr)
        if args.trace_id is None \
                and regression.get("current_trace"):
            args.trace_id = regression["current_trace"]

    ledger = resolve_ledger(args, perf)
    if ledger is None:
        print("explain_perf: no ledger — pass --ledger, or --perf "
              "with a cost_model section that names one",
              file=sys.stderr)
        return 2
    records = trace_report.load(ledger)
    records = trace_report.filter_records(records, args.trace_id)
    if not [r for r in records if r.get("t") == "span"]:
        print("explain_perf: no span records in %s%s — arm "
              "GS_TELEMETRY=1 (and GS_COSTMODEL=1 for program tags) "
              "and flush" % (ledger,
                             " matching --trace-id %s" % args.trace_id
                             if args.trace_id else ""),
              file=sys.stderr)
        return 1

    cost_rows = ((perf or {}).get("cost_model") or {}).get("programs")
    stages, attributed, ledger_total, unmapped = \
        stage_attribution(records)
    other_s = sum(r["total_s"] for r in stages
                  if r["stage"] == "other")
    mapped_frac = (1.0 - other_s / ledger_total if ledger_total > 0
                   else 1.0)
    programs = program_attribution(records, cost_rows)
    tenants = tenant_attribution(records)
    suspects = rank_suspects(stages, programs, records)
    report = {
        "trace": trace_report.meta_of(records).get("trace"),
        "ledger": ledger,
        "ledger_records": len(records),
        "leaf_spans": len(leaf_spans(records)),
        "tolerance": args.tolerance,
        "attributed_total_s": attributed,
        "ledger_total_s": ledger_total,
        "mapped_frac": round(mapped_frac, 4),
        "unmapped_spans": unmapped,
        "stages": stages,
        "programs": programs,
        "tenants": tenants,
        "suspects": suspects,
    }
    if regression is not None:
        report["regression"] = {
            "path": args.regression,
            "rows": regression.get("regressions"),
            "baseline_trace": regression.get("baseline_trace"),
            "current_trace": regression.get("current_trace"),
        }
    if args.json:
        print(json.dumps(report, indent=2, default=str))
    else:
        print(render(report, args.top))
    if mapped_frac < 1.0 - args.tolerance:
        print("explain_perf: the stage map could not name "
              "%.1f%% of the ledger's leaf-span time (> %.1f%% "
              "tolerance) — unmapped spans: %s; add them to STAGE_OF "
              "(or CONTAINERS if they envelope other spans)"
              % (100 * (1.0 - mapped_frac), 100 * args.tolerance,
                 ", ".join(unmapped) or "?"), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

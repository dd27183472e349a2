#!/usr/bin/env python
"""Perf regression sentry: compare a bench/profile run against a
committed baseline and exit non-zero on regression — the automated
"did this PR make it slower" answer for CI and the chip window.

Baselines and current runs may be any of:

  - a committed `BENCH_r*.json` capture ({"tail": ..., "parsed": ...}
    — every JSON metric line in the tail is a row),
  - a raw `python bench.py` stdout capture (one JSON object per line),
  - a `PERF*.json` evidence file (rows are pulled from the sections
    that carry throughput numbers: host_stream / host_snapshot /
    host_reduce / pipeline_stages / ingress_ab / egress_ab /
    telemetry_meta / metrics).

Rows are matched by their stable identity (the bench `metric` string,
or section + probe/bucket keys), and every shared throughput field
(`value`, `*_edges_per_s`) plus `pipeline_speedup` / `speedup` /
`vs_baseline` is compared: current/baseline below `1 - tolerance` is
a regression. Latency identities invert: every shared
`*_p{50,95,99}_s` field (bench serving rows, the PERF `latency`
section) regresses when current/baseline EXCEEDS `1 + tolerance` —
lower is better there. The bench rows on this host historically swing with
load (bench.py medians exist for that reason), so the default
tolerance is deliberately wide (--tolerance 0.2 = flag >20% drops);
CI that controls its host can tighten it.

Output: a JSON report whose `regressions` section is schema-validated
(tools/perf_schema.py) before it is written — a malformed sentry
report must fail the sentry, not the consumer. Exit status: 0 clean,
1 regressions found, 2 usage/IO errors.

Usage:
  python tools/bench_compare.py --baseline BASELINE_RUN.jsonl \
         [--current RUN.jsonl] [--tolerance 0.2] [--out REPORT.json]

With no --current the baseline is compared against itself — a wiring
smoke check that must always exit 0.
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# fields compared when present in BOTH rows: absolute-throughput
# fields (higher is better) and ratio fields (higher is better)
RATE_FIELDS = (
    "value", "sync_prep_edges_per_s", "device_path_edges_per_s",
    "baseline_cpu_edges_per_s", "host_edges_per_s",
    "device_edges_per_s", "native_edges_per_s", "scan_edges_per_s",
    "pipelined_edges_per_s", "sync_edges_per_s", "std_edges_per_s",
    "compact_edges_per_s", "full_edges_per_s", "delta_edges_per_s",
    "armed_edges_per_s", "disarmed_edges_per_s", "edges_per_s",
    "resident_edges_per_s", "perwindow_edges_per_s",
    "tenant_edges_per_s", "sequential_edges_per_s",
    "gnn_edge_features_per_s", "cohort_edges_per_s",
)
RATIO_FIELDS = ("pipeline_speedup", "speedup", "vs_baseline",
                "cohort_speedup", "queue_wait_improvement",
                "e2e_improvement")

# latency identities (LOWER is better — the comparison inverts):
# any field both rows share whose name ends in a percentile-seconds
# suffix is compared as current/baseline ABOVE 1 + tolerance = a
# latency regression. bench.py serving rows emit serve_e2e_p{50,95,
# 99}_s and PERF latency sections emit e2e_p{50,95,99}_s.
LATENCY_SUFFIXES = ("_p50_s", "_p95_s", "_p99_s")

# robustness counters (LOWER is better, zero is the healthy state):
# rejected-record and quarantine totals a clean serving run must keep
# at 0 — a baseline-0 counter that turns non-zero is a regression
# regardless of ratio, and a non-zero baseline regresses past
# 1 + tolerance like the latency identities
COUNTER_FIELDS = ("dlq_records", "quarantines")

# PERF.json sections that carry comparable rows, with the keys that
# identify a row within the section
PERF_SECTIONS = {
    "host_stream": ("edge_bucket",),
    "host_snapshot": ("edge_bucket",),
    "host_reduce": ("edge_bucket", "name"),
    "pipeline_stages": ("engine", "edge_bucket"),
    "ingress_ab": ("probe",),
    "egress_ab": ("probe",),
    "resident_ab": ("probe",),
    "tenancy_ab": ("probe", "tenants"),
    "pump_ab": ("probe",),
    "gnn_ab": ("probe", "tenants"),
    "autotune": ("engine", "edge_bucket"),
}


def _json_lines(text: str) -> list:
    rows = []
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            row = json.loads(line)
        except ValueError:
            continue
        if isinstance(row, dict):
            rows.append(row)
    return rows


def extract_rows(doc, label: str) -> dict:
    """{row identity → row dict} from any supported shape."""
    out = {}

    def add(key, row):
        # duplicate identities (a re-run scale): last wins, matching
        # bench.py's the-last-line-wins convention
        out[key] = row

    # identity fields that are present-but-null are treated exactly
    # like missing ones: a row {"metric": null} must neither create a
    # phantom `None` identity nor match differently than a row that
    # simply lacks the key (pinned by test_perf_tooling)
    if isinstance(doc, str):
        for row in _json_lines(doc):
            if row.get("metric") is not None:
                add(row["metric"], row)
        return out
    if not isinstance(doc, dict):
        raise ValueError("%s: unsupported document shape %s"
                         % (label, type(doc).__name__))
    if "tail" in doc and isinstance(doc.get("tail"), str):
        # committed BENCH_r*.json capture
        for row in _json_lines(doc["tail"]):
            if row.get("metric") is not None:
                add(row["metric"], row)
        parsed = doc.get("parsed")
        if isinstance(parsed, dict) \
                and parsed.get("metric") is not None:
            add(parsed["metric"], parsed)
        return out
    if doc.get("metric") is not None:
        add(doc["metric"], doc)
        return out
    # PERF*.json evidence file
    for section, keys in PERF_SECTIONS.items():
        rows = doc.get(section)
        if not isinstance(rows, list):
            continue
        for row in rows:
            if not isinstance(row, dict):
                continue
            ident = "%s[%s]" % (section, ",".join(
                str(row.get(k)) for k in keys))
            add(ident, row)
    for meta_key in ("telemetry_meta", "metrics", "latency",
                     "sanitize", "provenance"):
        meta = doc.get(meta_key)
        if isinstance(meta, dict):
            add(meta_key, meta)
    return out


def row_trace(row) -> str:
    """The run trace ID one row carries (bench rows stamp `trace`,
    armed ones nest it under `telemetry` too); None when absent."""
    if not isinstance(row, dict):
        return None
    t = row.get("trace")
    if isinstance(t, str) and t:
        return t
    tel = row.get("telemetry")
    if isinstance(tel, dict) and isinstance(tel.get("trace"), str):
        return tel["trace"]
    return None


def doc_trace(rows: dict) -> str:
    """The first run trace ID any of a document's rows carries — the
    correlation key that links a sentry regression to the ledger
    tools/explain_perf.py drills into. None when no row carries one."""
    for row in rows.values():
        t = row_trace(row)
        if t:
            return t
    return None


def load_rows(path: str) -> dict:
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = text  # raw bench stdout: one JSON object per line
    rows = extract_rows(doc, path)
    if not rows:
        raise ValueError(
            "%s: no comparable rows found (expected bench JSON lines, "
            "a BENCH_r*.json capture, or a PERF*.json file)" % path)
    return rows


def compare(base_rows: dict, cur_rows: dict, tolerance: float) -> dict:
    """The sentry verdict: per-row field comparisons plus the
    schema-validated `regressions` section."""
    compared, regressions, skipped = [], [], []
    for ident in sorted(base_rows):
        if ident not in cur_rows:
            skipped.append(ident)
            continue
        b, c = base_rows[ident], cur_rows[ident]
        for field in RATE_FIELDS + RATIO_FIELDS:
            bv, cv = b.get(field), c.get(field)
            if not isinstance(bv, (int, float)) \
                    or not isinstance(cv, (int, float)) \
                    or isinstance(bv, bool) or isinstance(cv, bool) \
                    or bv <= 0:
                continue
            ratio = cv / bv
            row = {"row": ident, "field": field,
                   "baseline": bv, "current": cv,
                   "ratio": round(ratio, 4)}
            compared.append(row)
            if ratio < 1.0 - tolerance:
                regressions.append(dict(row, tolerance=tolerance))
        # robustness counters: lower is better, and a clean (0)
        # baseline turning non-zero is a regression outright — there
        # is no ratio that makes new rejected records acceptable
        for field in COUNTER_FIELDS:
            bv, cv = b.get(field), c.get(field)
            if not isinstance(bv, (int, float)) \
                    or not isinstance(cv, (int, float)) \
                    or isinstance(bv, bool) or isinstance(cv, bool):
                continue
            ratio = (cv / bv) if bv > 0 else float(cv)
            row = {"row": ident, "field": field,
                   "baseline": bv, "current": cv,
                   "ratio": round(ratio, 4),
                   "direction": "lower_is_better"}
            compared.append(row)
            if (bv == 0 and cv > 0) \
                    or (bv > 0 and ratio > 1.0 + tolerance):
                regressions.append(dict(row, tolerance=tolerance))
        # latency identities: every shared *_p{50,95,99}_s field,
        # compared inverted (LOWER is better — current/baseline past
        # 1 + tolerance is the regression)
        for field in sorted(k for k in b
                            if isinstance(k, str)
                            and k.endswith(LATENCY_SUFFIXES)):
            bv, cv = b.get(field), c.get(field)
            if not isinstance(bv, (int, float)) \
                    or not isinstance(cv, (int, float)) \
                    or isinstance(bv, bool) or isinstance(cv, bool) \
                    or bv <= 0:
                continue
            ratio = cv / bv
            row = {"row": ident, "field": field,
                   "baseline": bv, "current": cv,
                   "ratio": round(ratio, 4),
                   "direction": "lower_is_better"}
            compared.append(row)
            if ratio > 1.0 + tolerance:
                regressions.append(dict(row, tolerance=tolerance))
    return {
        "backend": "bench_compare",
        "tolerance": tolerance,
        "rows_compared": len({r["row"] for r in compared}),
        "fields_compared": len(compared),
        "rows_only_in_baseline": skipped,
        "comparisons": compared,
        "regressions": regressions,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--baseline", required=True,
                    help="committed baseline (BENCH_r*.json, bench "
                         "stdout, or PERF*.json)")
    ap.add_argument("--current", default=None,
                    help="current run in any supported shape; omitted "
                         "= self-compare the baseline (smoke mode, "
                         "always exit 0)")
    ap.add_argument("--tolerance", type=float, default=0.2,
                    help="relative drop that counts as a regression "
                         "(default 0.2 = 20%%)")
    ap.add_argument("--out", default=None,
                    help="write the JSON report here")
    args = ap.parse_args(argv)
    if not 0 < args.tolerance < 1:
        print("bench_compare: --tolerance must be in (0, 1)",
              file=sys.stderr)
        return 2

    try:
        base_rows = load_rows(args.baseline)
        cur_rows = (load_rows(args.current)
                    if args.current else dict(base_rows))
    except (OSError, ValueError) as e:
        print("bench_compare: %s" % e, file=sys.stderr)
        return 2
    if args.current is None:
        print("bench_compare: no --current given — self-comparing "
              "the baseline (smoke mode)", file=sys.stderr)

    report = compare(base_rows, cur_rows, args.tolerance)
    report["baseline_path"] = args.baseline
    report["current_path"] = args.current or args.baseline
    # trace-ID correlation: a non-zero exit should link straight to
    # its attributed cause — stamp the run trace IDs the rows carry
    # so `tools/explain_perf.py --regression <report>` can find the
    # right ledger without guesswork
    base_trace, cur_trace = doc_trace(base_rows), doc_trace(cur_rows)
    if base_trace:
        report["baseline_trace"] = base_trace
    if cur_trace:
        report["current_trace"] = cur_trace
    for r in report["regressions"]:
        # per-row first: a file accumulated across several runs holds
        # several trace IDs, and the drill-down must follow the
        # REGRESSING row's run, not whichever row was seen first
        bt = row_trace(base_rows.get(r["row"])) or base_trace
        ct = row_trace(cur_rows.get(r["row"])) or cur_trace
        if bt:
            r["baseline_trace"] = bt
        if ct:
            r["current_trace"] = ct

    # the sentry's own output contract: a malformed `regressions`
    # section must fail HERE, not in a CI consumer
    from tools import perf_schema

    problems = perf_schema.validate(report)
    if problems:
        print("bench_compare: internal schema violation:\n  %s"
              % "\n  ".join(problems), file=sys.stderr)
        return 2

    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print("wrote %s" % args.out, file=sys.stderr)
    if report["regressions"]:
        for r in report["regressions"]:
            if r.get("direction") == "lower_is_better":
                print("REGRESSION %s.%s: %s -> %s (x%.3f > 1+%.2f, "
                      "latency)" % (r["row"], r["field"],
                                    r["baseline"], r["current"],
                                    r["ratio"], args.tolerance),
                      file=sys.stderr)
                continue
            print("REGRESSION %s.%s: %s -> %s (x%.3f < 1-%.2f)"
                  % (r["row"], r["field"], r["baseline"], r["current"],
                     r["ratio"], args.tolerance), file=sys.stderr)
        if args.out:
            print("drill down: python tools/explain_perf.py "
                  "--regression %s" % args.out, file=sys.stderr)
        return 1
    if not report["fields_compared"]:
        print("bench_compare: no overlapping rows/fields to compare",
              file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())

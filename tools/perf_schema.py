#!/usr/bin/env python
"""Lightweight schema check for PERF.json (and the per-backend
archives PERF_<backend>.json).

The committed record feeds the PERF.md renderer
(tools/update_perf_md.py). A malformed section — a dict where a row
list belongs, a parity-true row without a speedup, a degradation
event missing its tiers — crashes the unattended renderer at the
END of a chip window, which is exactly when raw output is lost.
This validator is the cheap
tier-1 guard (tests/test_perf_tooling.py) that new profiler sections
can't break the contract unnoticed.

Usage: python tools/perf_schema.py [PERF.json ...]   (repo default)
Exit 0 = every file clean; errors list file:section:problem lines.

Forward-compatible by design: UNKNOWN top-level keys are allowed
(new sections land before the validator learns them); only the shape
of KNOWN sections is enforced.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# sections whose value must be a list of dict rows, with per-row
# REQUIRED keys (value None = key must exist, any type)
LIST_SECTIONS = {
    "intersect": (),          # dict OR list historically: checked below
    "window": ("edge_bucket",),
    "host_stream": ("edge_bucket", "parity"),
    "host_reduce": ("edge_bucket", "name", "parity"),
    "host_snapshot": ("edge_bucket", "parity"),
    "ingress_ab": ("probe", "parity"),
    "egress_ab": ("probe", "parity"),
    "resident_ab": ("probe", "parity"),
    # fused Pallas window megakernel A/B (tools/pallas_ab.py):
    # megakernel vs XLA scan-of-gathers, sha256 window parity vs the
    # host twins; resolve_pallas_window gates on these rows
    "pallas_ab": ("probe", "parity"),
    # multi-tenant cohort A/B (tools/tenancy_ab.py): N-tenant vmapped
    # dispatch vs N sequential single-tenant engines, per-tenant
    # sha256 parity. Probes: cohort_serving/cohort_batch (scan tier),
    # cohort_resident (donated stacked-carry super-batch tier, one row
    # per N — resolve_resident_cohort's adoption evidence),
    # cohort_pallas (tenant-axis Pallas megakernel; off-chip rows must
    # be interpret-marked, see _check_rows)
    "tenancy_ab": ("probe", "parity", "tenants"),
    # async-pump / sliding-pane A/B (tools/pump_ab.py). Probes:
    # serving_pump (GS_PUMP=async vs sync on a paced 8-tenant loopback
    # serve run, per-tenant sha256 parity, queue_wait/e2e p99
    # improvements), sliding_panes (pane-composed sliding reduce vs
    # the naive refold twin, bit-exact parity)
    "pump_ab": ("probe", "parity"),
    # windowed GNN A/B (tools/gnn_ab.py): engine vs numpy twin and
    # cohort vs N-sequential at sha256 feature-slab parity. Probes:
    # gnn_engine (device scan vs host twin), gnn_cohort (vmapped
    # N-tenant dispatch vs N sequential engines, one row per N),
    # gnn_pallas (fused kernel vs XLA round — resolve_gnn_pallas's
    # adoption evidence; off-chip rows must be interpret-marked, see
    # _check_rows)
    "gnn_ab": ("probe", "parity"),
    "autotune": ("engine", "parity"),
    "pipeline_stages": ("engine", "edge_bucket"),
    "chunk_deep": ("edge_bucket",),
    "compile_probe": ("program", "slots", "ok"),
    "compile_probe_scan": ("program", "slots", "ok"),
    # mesh_shape is REQUIRED (null = single-chip): a demoted mesh run
    # must carry its mesh provenance, so it can never masquerade as a
    # healthy sharded-tier row (utils/resilience.record_demotion is
    # the single producer and always stamps it, with shard_id beside)
    "degradations": ("from", "to", "window", "mesh_shape"),
    "ingress_probes": ("probe",),
    # flight-recorder summary rows (utils/telemetry.summary():
    # per-span latency aggregates a profiler/chaos run commits)
    "telemetry": ("span", "count"),
    # perf regression sentry rows (tools/bench_compare.py): one row
    # per (baseline row, field) whose current/baseline ratio fell
    # below tolerance — CI keys its red/green off this section
    "regressions": ("row", "field", "baseline", "current", "ratio"),
}

# dict-shaped sections with required keys (telemetry_meta predates
# this table and stays unvalidated for compatibility)
DICT_SECTIONS = {
    # metrics-plane overhead proof (tools/profile_kernels.py
    # section_metrics): armed-vs-disarmed wall ratio + digest parity
    # on the 524K/32768 row — the committed evidence for the
    # GS_METRICS ≤1.05× bar
    "metrics": ("engine", "parity", "overhead_ratio",
                "disarmed_edges_per_s", "armed_edges_per_s"),
    # program cost observatory (utils/costmodel, tools/
    # profile_kernels.py section_cost_model): per-program FLOPs/bytes
    # rows + the trace id of the committed attribution ledger
    # tools/explain_perf.py drills into
    "cost_model": ("programs", "parity", "edge_bucket", "trace",
                   "ledger"),
    # latency-plane overhead + reconciliation proof (utils/latency,
    # tools/profile_kernels.py section_latency): armed-vs-disarmed
    # wall ratio with digest parity on the 524K/32768 row, plus the
    # per-window waterfall conservation check (stages sum to e2e) —
    # the committed evidence for the GS_LATENCY ≤1.05× bar
    "latency": ("engine", "parity", "overhead_ratio",
                "disarmed_edges_per_s", "armed_edges_per_s",
                "reconciled_windows", "e2e_p99_s"),
    # admission-sanitizer overhead proof (utils/sanitize,
    # tools/profile_kernels.py section_sanitize): armed-vs-disarmed
    # wall ratio at digest parity on the 524K/32768 row, plus the
    # dlq_records/quarantines counters bench_compare checks
    # not-worse — the committed evidence for the GS_SANITIZE ≤1.02×
    # bar
    "sanitize": ("engine", "parity", "overhead_ratio",
                 "disarmed_edges_per_s", "armed_edges_per_s",
                 "dlq_records", "quarantines"),
    # provenance-ledger overhead + truth proof (utils/provenance,
    # tools/profile_kernels.py section_provenance): armed-vs-disarmed
    # wall ratio at digest parity on the 524K/32768 row, every armed
    # window's ledger digest asserted against the disarmed baseline
    # summary, plus the per-tenant attribution rows whose seconds
    # reconcile to the dispatch span — the committed evidence for the
    # GS_PROVENANCE ≤1.02× bar (ISSUE 20)
    "provenance": ("engine", "parity", "overhead_ratio",
                   "disarmed_edges_per_s", "armed_edges_per_s",
                   "records", "windows_verified", "attribution"),
    # windowed-GNN cost observatory rows (tools/profile_kernels.py
    # section_gnn / tools/gnn_ab.py --commit): the per-program
    # analytic cost rows for the MXU workload, with the stated
    # arithmetic intensity beside the measured throughput so PERF.md
    # shows whether the dense update moves the bound verdict off
    # `bytes` — plus digest parity vs the host twin on the same run
    "gnn": ("programs", "parity", "edge_bucket", "feature_dim"),
}

# per-row required keys of the cost_model section's `programs` list
# (flops/bytes may be null on a backend that doesn't report them, but
# the keys must exist so a consumer can tell "not reported" from a
# silently dropped capture)
_COST_PROGRAM_KEYS = ("program", "sig", "flops", "bytes_accessed",
                      "bound", "dispatches")

# A/B sections whose parity-true rows must claim a positive speedup
_AB_SECTIONS = ("ingress_ab", "egress_ab", "resident_ab",
                "tenancy_ab", "pallas_ab", "pump_ab", "gnn_ab")


def _check_rows(name: str, rows, errors) -> None:
    if not isinstance(rows, list):
        errors.append("%s: expected a list of rows, got %s"
                      % (name, type(rows).__name__))
        return
    for i, row in enumerate(rows):
        if not isinstance(row, dict):
            errors.append("%s[%d]: expected a dict row, got %s"
                          % (name, i, type(row).__name__))
            continue
        for key in LIST_SECTIONS.get(name, ()):
            if key not in row:
                errors.append("%s[%d]: missing required key %r"
                              % (name, i, key))
        if name in _AB_SECTIONS and row.get("parity") is True:
            sp = row.get("speedup")
            if not isinstance(sp, (int, float)) or sp <= 0:
                errors.append(
                    "%s[%d]: parity-true row needs a positive "
                    "'speedup' (got %r)" % (name, i, sp))
        if name == "tenancy_ab" \
                and row.get("probe") == "cohort_pallas" \
                and row.get("backend") != "tpu" \
                and row.get("interpret") is not True:
            # resolve_cohort_pallas ignores interpret rows for
            # adoption; an off-chip row missing the marker would
            # masquerade as chip speed evidence
            errors.append(
                "tenancy_ab[%d]: cohort_pallas row on backend %r "
                "must carry interpret: true" % (i, row.get("backend")))
        if name == "gnn_ab" \
                and row.get("probe") == "gnn_pallas" \
                and row.get("backend") != "tpu" \
                and row.get("interpret") is not True:
            # same contract for resolve_gnn_pallas's evidence rows
            errors.append(
                "gnn_ab[%d]: gnn_pallas row on backend %r must "
                "carry interpret: true" % (i, row.get("backend")))
        if name == "degradations":
            ms = row.get("mesh_shape")
            if ms is not None and not (
                    isinstance(ms, list)
                    and all(isinstance(x, int) for x in ms)):
                errors.append(
                    "degradations[%d]: 'mesh_shape' must be null or a "
                    "list of ints (got %r)" % (i, ms))
            sid = row.get("shard_id")
            if sid is not None and not isinstance(sid, int):
                errors.append(
                    "degradations[%d]: 'shard_id' must be null or an "
                    "int (got %r)" % (i, sid))


def validate(perf) -> list:
    """Error strings for one parsed PERF dict; empty = clean."""
    errors = []
    if not isinstance(perf, dict):
        return ["top level: expected a dict, got %s"
                % type(perf).__name__]
    if not isinstance(perf.get("backend"), str):
        errors.append("top level: 'backend' must be a string "
                      "(got %r)" % (perf.get("backend"),))
    for name, val in perf.items():
        if name.endswith("_error"):
            if not (isinstance(val, dict) and "error" in val):
                errors.append("%s: failed-section stub must be a dict "
                              "with an 'error' key" % name)
            continue
        if name == "intersect":
            # historically a single dict row; a list is also accepted
            if not isinstance(val, (dict, list)):
                errors.append("intersect: expected dict or list")
            continue
        if name in LIST_SECTIONS:
            _check_rows(name, val, errors)
        elif name in DICT_SECTIONS:
            if not isinstance(val, dict):
                errors.append("%s: expected a dict section, got %s"
                              % (name, type(val).__name__))
                continue
            for key in DICT_SECTIONS[name]:
                if key not in val:
                    errors.append("%s: missing required key %r"
                                  % (name, key))
            if name in ("cost_model", "gnn"):
                rows = val.get("programs")
                if not isinstance(rows, list):
                    if "programs" in val:
                        errors.append(
                            "%s: 'programs' must be a list of "
                            "rows, got %s" % (name, type(rows).__name__))
                else:
                    for i, row in enumerate(rows):
                        if not isinstance(row, dict):
                            errors.append(
                                "%s.programs[%d]: expected a "
                                "dict row, got %s"
                                % (name, i, type(row).__name__))
                            continue
                        for key in _COST_PROGRAM_KEYS:
                            if key not in row:
                                errors.append(
                                    "%s.programs[%d]: missing "
                                    "required key %r" % (name, i, key))
    return errors


def validate_capture(doc) -> list:
    """Error strings for one parsed BENCH_r*.json capture ({"n",
    "cmd", "rc", "tail", "parsed"} — the shape bench runs commit and
    tools/bench_compare.py reads); empty = clean."""
    errors = []
    if not isinstance(doc, dict):
        return ["top level: expected a dict capture, got %s"
                % type(doc).__name__]
    if not isinstance(doc.get("tail"), str):
        errors.append("capture: 'tail' must be the bench stdout tail "
                      "string (got %r)" % type(doc.get("tail")).__name__)
    if "rc" in doc and not isinstance(doc["rc"], int):
        errors.append("capture: 'rc' must be an int exit status")
    parsed = doc.get("parsed")
    if parsed is not None and not isinstance(parsed, dict):
        errors.append("capture: 'parsed' must be null or the last "
                      "metric row dict")
    return errors


def is_capture(doc) -> bool:
    """True for the BENCH_r*.json capture shape (tail + cmd/rc),
    which main() routes to validate_capture instead of validate."""
    return isinstance(doc, dict) and "tail" in doc \
        and ("cmd" in doc or "rc" in doc)


# per-leg required keys of the chaos soak summary
# (tools/chaos_run.py; every committed logs/CHAOS_*.json). Legs are
# optional (older soaks predate newer legs) but a PRESENT leg must
# carry its keys — a soak that "passed" without its parity flag is
# exactly the silent-drift CI must refuse.
_CHAOS_LEGS = {
    "driver_leg": ("parity", "faults_fired", "resumed_from_window"),
    "engine_leg": ("parity", "faults_fired", "killed_at_call"),
    "resident_leg": ("parity", "faults_fired"),
    "tenancy_leg": ("parity", "faults_fired", "resumed"),
    # the durable-serving drill (ISSUE 12): kill→WAL-replay parity,
    # torn tail falling back one record, slow-client shed, and the
    # graceful SIGTERM drain (subprocess exits 0, sealed journal,
    # drain digest ≡ keep-running digest)
    "serve_leg": ("parity", "kill", "torn_tail", "slow_client",
                  "drain"),
    # the latency-plane drill (latency ISSUE): kill→WAL-replay
    # recovery must preserve admission timestamps — replayed windows
    # report honest, larger latency, never reset-to-zero — at armed
    # summaries digest-identical to the fault-free oracle
    "latency_leg": ("parity", "preserved", "replayed_windows"),
    # the poison-input drill (ISSUE 15): a hostile tenant flooding
    # garbage is sanitized (every rejected edge recoverable from the
    # dead-letter journal) and quarantined by the cohort bulkhead
    # while the healthy tenants stay bit-identical; the serve
    # subprocess under the flood must still drain rc=0
    "poison_leg": ("parity", "quarantined", "dlq_recovered", "drain"),
    # the async-pump drill (ISSUE 18): SIGKILL a GS_PUMP=async serve
    # subprocess mid-pump, WAL-replay into a fresh async server, and
    # the union of pre-kill deliveries + replayed windows must be
    # digest-identical to the sync fault-free oracle — with at least
    # one ingest batch accepted while a dispatch was in flight
    # (overlap_feeds > 0: the leg proves the overlap path, not a
    # quietly serialized pump)
    "pump_leg": ("parity", "faults_fired", "overlap_feeds"),
    # the windowed-GNN drill (ISSUE 19): fatal kill mid-stream on a
    # checkpoint+WAL-armed GnnSummaryEngine, resume into a fresh
    # engine, and the final feature slab + combined summaries must be
    # digest-identical to the fault-free oracle (weights restored
    # from the checkpoint's gnn section, never re-seeded)
    "gnn_leg": ("parity", "faults_fired", "resumed_from_window"),
    # the provenance-ledger drill (ISSUE 20): a fully armed cohort
    # (provenance + WAL + checkpoints) killed fatally mid-dispatch,
    # recovered, and the re-emitted provenance records — including
    # the at-least-once duplicates for replayed windows — must be
    # byte-identical to the fault-free oracle's ledger (a crash can
    # never fork the audit trail)
    "provenance_leg": ("parity", "faults_fired", "records",
                       "re_emitted"),
}


def is_chaos(doc) -> bool:
    """True for the tools/chaos_run.py soak-summary shape, which
    main() routes to validate_chaos."""
    return isinstance(doc, dict) and "fault_classes_fired" in doc


def validate_chaos(doc) -> list:
    """Error strings for one parsed logs/CHAOS_*.json soak summary;
    empty = clean."""
    errors = []
    if not isinstance(doc, dict):
        return ["top level: expected a dict soak summary"]
    if doc.get("parity") is not True:
        errors.append("chaos: top-level 'parity' must be true — a "
                      "diverged soak must never be committed")
    if not isinstance(doc.get("fault_classes_fired"), list):
        errors.append("chaos: 'fault_classes_fired' must be a list")
    for leg, keys in _CHAOS_LEGS.items():
        val = doc.get(leg)
        if val is None:
            continue  # legs are additive across soak generations
        if not isinstance(val, dict):
            errors.append("%s: expected a dict leg, got %s"
                          % (leg, type(val).__name__))
            continue
        for key in keys:
            if key not in val:
                errors.append("%s: missing required key %r"
                              % (leg, key))
        if val.get("parity") is not True:
            errors.append("%s: leg 'parity' must be true" % leg)
    for leg_name in ("serve_leg", "poison_leg"):
        leg = doc.get(leg_name)
        if not isinstance(leg, dict):
            continue
        drain = leg.get("drain")
        if isinstance(drain, dict):
            for key in ("rc", "sealed", "digest_match"):
                if key not in drain:
                    errors.append("%s.drain: missing required "
                                  "key %r" % (leg_name, key))
            if drain.get("rc") != 0:
                errors.append("%s.drain: SIGTERM drain must "
                              "exit 0 (got %r)"
                              % (leg_name, drain.get("rc")))
        elif drain is not None:
            errors.append("%s.drain: expected a dict" % leg_name)
    return errors


def main(paths=None) -> int:
    paths = paths or [os.path.join(REPO, "PERF.json")]
    rc = 0
    for path in paths:
        try:
            with open(path) as f:
                perf = json.load(f)
        except (OSError, ValueError) as e:
            print("%s: unreadable (%s)" % (path, e))
            rc = 1
            continue
        errors = (validate_capture(perf) if is_capture(perf)
                  else validate_chaos(perf) if is_chaos(perf)
                  else validate(perf))
        if errors:
            rc = 1
            for e in errors:
                print("%s: %s" % (os.path.basename(path), e))
        else:
            print("%s: ok (%d top-level keys)"
                  % (os.path.basename(path), len(perf)))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or None))

#!/usr/bin/env bash
# One-command CI gate: everything a PR must hold green, in the order
# that fails fastest on the cheapest signal after the test suite.
#
#   1. tier-1 pytest (ROADMAP.md's verify command, CPU backend)
#   2. gslint clean (no non-baselined findings, README in sync)
#   3. perf_schema over every committed PERF*/CHAOS evidence file
#      (PERF files validate section shapes)
#   4. tenancy parity smoke (tools/tenancy_ab.py --smoke): a 1-tenant
#      cohort must be digest-identical to the single-stream engine,
#      so the vmapped cohort path can't silently drift from the
#      single-stream semantics
#   5. serve parity smoke (tools/serve_smoke.py): one tenant fed
#      through a real loopback socket into the journal-armed
#      StreamServer (feed -> pump -> graceful drain) must be
#      digest-identical to the direct cohort feed, with a sealed
#      journal — the wire/durability layer changes availability,
#      never results
#   6. pallas megakernel smoke (tools/pallas_smoke.py): one window
#      through the interpret-mode fused window megakernel must be
#      digest-identical to the XLA fused scan, so Pallas API drift
#      is caught without a chip
#   7. latency-plane smoke (tools/latency_smoke.py): an armed
#      loopback serve run must deliver rows with latency_s, populate
#      the /healthz `latency` section, and leave a ledger whose
#      per-window stage waterfalls SUM to the measured ingest→deliver
#      end-to-end within 5% (tools/latency_report.py exits non-zero
#      otherwise) — at summaries digest-identical to a disarmed run
#   8. poison-input smoke (tools/poison_smoke.py): an 8-tenant cohort
#      with one hostile tenant flooding garbage — the 7 healthy
#      tenants' digests stay bit-identical to a fault-free oracle,
#      the hostile stream is quarantined, and every rejected edge is
#      recoverable from (and replay-exactly re-injectable out of) the
#      dead-letter journal
#   9. cohort-resident smoke (tools/tenancy_ab.py --resident-smoke):
#      a 2-tenant cohort pinned GS_COHORT_RESIDENT=on must be
#      digest-identical to two single-stream engines AND must have
#      actually dispatched through the donated stacked-carry
#      super-batch program (resident_dispatches > 0) — a silent
#      decline to the scan tier fails the gate instead of passing
#      vacuously
#  10. async-pump smoke (tools/pump_smoke.py): a GS_PUMP=async
#      loopback run must be digest-identical per tenant to the sync
#      single-lock legacy AND must actually overlap ingest with
#      dispatch (overlap_feeds > 0, forced deterministically by a
#      hung dispatch) — a pump that quietly serializes fails; plus
#      the sliding default pin (slide == edge_bucket ≡ tumbling)
#  11. windowed-GNN smoke (tools/gnn_smoke.py): one GNN round through
#      the device engine AND the interpret-mode fused Pallas kernel
#      must leave a feature slab + summary stream bit-identical to
#      the numpy lattice twin — a broken lattice edit or a silently
#      refused kernel probe fails the gate instead of passing
#      vacuously
#  12. provenance smoke (tools/provenance_smoke.py): an armed
#      8-tenant cohort run must leave a provenance ledger in which
#      EVERY record — one per finalized window — replays digest-exact
#      through tools/replay_window.py on both the host twin and the
#      fused scan tier (checkpoint + WAL span + recompute); a missing
#      or unreplayable record fails, never silently skips
#
# Usage: tools/ci_check.sh [--skip-tests]
#   --skip-tests  run only the static/evidence gates (seconds, not
#                 minutes) — for pre-commit iteration; CI runs full.
set -euo pipefail
cd "$(dirname "$0")/.."

if [[ "${1:-}" != "--skip-tests" ]]; then
  echo "== [1/12] tier-1 pytest (JAX_PLATFORMS=cpu, -m 'not slow') =="
  JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider
else
  echo "== [1/12] tier-1 pytest SKIPPED (--skip-tests) =="
fi

echo "== [2/12] gslint =="
python -m tools.gslint

echo "== [3/12] perf_schema: committed PERF*/CHAOS evidence =="
evidence=(PERF*.json logs/CHAOS_*.json)
python tools/perf_schema.py "${evidence[@]}"

echo "== [4/12] tenancy parity smoke (1-tenant cohort ≡ single stream) =="
JAX_PLATFORMS=cpu python tools/tenancy_ab.py --smoke

echo "== [5/12] serve parity smoke (loopback + drain ≡ direct feed) =="
JAX_PLATFORMS=cpu python tools/serve_smoke.py

echo "== [6/12] pallas megakernel smoke (interpret ≡ XLA fused scan) =="
JAX_PLATFORMS=cpu python tools/pallas_smoke.py

echo "== [7/12] latency-plane smoke (waterfalls reconcile, armed ≡ disarmed) =="
JAX_PLATFORMS=cpu python tools/latency_smoke.py

echo "== [8/12] poison-input smoke (isolation + DLQ replay-exact re-injection) =="
JAX_PLATFORMS=cpu python tools/poison_smoke.py

echo "== [9/12] cohort-resident smoke (resident tier ≡ single streams, no silent decline) =="
JAX_PLATFORMS=cpu python tools/tenancy_ab.py --resident-smoke

echo "== [10/12] async-pump smoke (async ≡ sync, real overlap; sliding pin) =="
JAX_PLATFORMS=cpu python tools/pump_smoke.py

echo "== [11/12] windowed-GNN smoke (device ≡ pallas ≡ numpy lattice twin) =="
JAX_PLATFORMS=cpu python tools/gnn_smoke.py

echo "== [12/12] provenance smoke (every ledger record replays digest-exact on 2 tiers) =="
JAX_PLATFORMS=cpu python tools/provenance_smoke.py

echo "ci_check: all gates green"

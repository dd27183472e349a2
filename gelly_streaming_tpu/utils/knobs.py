"""The typed `GS_*` knob registry — the ONE place environment knobs
are declared, parsed, and documented.

Before this module, 33 `GS_*` knobs were read at 23 scattered
`os.environ` sites, each reimplementing the same parse-clamp-default
helper (utils/resilience, utils/telemetry, ops/autotune,
ops/delta_egress, ops/ingress_pipeline all had private copies), and
the README knob table was maintained by hand — so a renamed knob, a
changed default, or a typo'd value degraded silently. Here every knob
is a `Knob` entry with a kind, a default, clamp bounds, and the
one-line meaning the README table renders, and every read goes
through `get()`:

- Reads are LIVE (`os.environ` consulted per call, never cached):
  tests and tools/chaos_run.py flip knobs mid-process, and the old
  helpers were deliberately per-call for exactly that reason.
- A malformed value raises typed `KnobError` naming the knob, the
  offending text, and the expected kind — failing fast at the read
  site instead of silently running with a default the operator did
  not ask for (the old helpers swallowed `ValueError` into the
  default, which is how a mistyped `GS_STAGE_TIMEOUT_S=3O` disarms
  the watchdog unnoticed).
- `tools/gslint` rule R3 enforces adoption: any `os.environ` read
  inside `gelly_streaming_tpu/` outside this module (and the
  non-knob backend setup in core/platform.py) is a lint finding, and
  the README table is diffed row-for-row against `render_table()` so
  the docs cannot drift from the code.

Unset and empty both mean "default": an empty string is what
`VAR= python ...` and CI templating produce for "not configured",
and no knob here distinguishes empty from absent on purpose.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

__all__ = [
    "Knob", "KnobError", "REGISTRY", "register",
    "get_int", "get_float", "get_bool", "get_str", "get_path",
    "render_table",
]

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")


class KnobError(ValueError):
    """A `GS_*` environment value could not be parsed as its declared
    kind. Carries `.knob` (the Knob) and `.value` (the offending
    text) so a harness can report exactly what to fix."""

    def __init__(self, knob: "Knob", value: str, problem: str):
        super().__init__(
            "%s=%r: %s (expected %s; default %r)"
            % (knob.name, value, problem, knob.kind, knob.default))
        self.knob = knob
        self.value = value


@dataclass(frozen=True)
class Knob:
    """One declared environment knob. `kind` is one of
    'int' / 'float' / 'bool' / 'str' / 'path'; `lo`/`hi` clamp parsed
    numbers (clamping, not raising: the bounds encode "16 is the
    smallest useful ring", not user error); `choices` restricts str
    knobs; `default_text` overrides how the default renders in the
    README table (e.g. "min(2·eb, vb)" for a computed default);
    `help` is the table's meaning column."""

    name: str
    kind: str
    default: object
    help: str
    lo: Optional[float] = None
    hi: Optional[float] = None
    choices: Optional[Tuple[str, ...]] = None
    default_text: Optional[str] = None


REGISTRY: Dict[str, Knob] = {}


def register(name: str, kind: str, default, help: str, **kw) -> Knob:
    assert name.startswith("GS_"), name
    assert kind in ("int", "float", "bool", "str", "path"), kind
    assert name not in REGISTRY, "duplicate knob %s" % name
    knob = Knob(name, kind, default, help, **kw)
    REGISTRY[name] = knob
    return knob


def _raw(name: str) -> Optional[str]:
    """The live environment text, with unset and empty unified to
    None (= use the default)."""
    val = os.environ.get(name)
    return None if val is None or val == "" else val


def _clamp(knob: Knob, num):
    if knob.lo is not None and num < knob.lo:
        num = type(num)(knob.lo)
    if knob.hi is not None and num > knob.hi:
        num = type(num)(knob.hi)
    return num


def _knob(name: str, kind: str) -> Knob:
    knob = REGISTRY.get(name)
    assert knob is not None, "unregistered knob %s" % name
    assert knob.kind == kind, (name, knob.kind, kind)
    return knob


def get_int(name: str) -> Optional[int]:
    knob = _knob(name, "int")
    raw = _raw(name)
    if raw is None:
        return knob.default if knob.default is None \
            else _clamp(knob, int(knob.default))
    try:
        num = int(raw)
    except ValueError:
        raise KnobError(knob, raw, "not an integer") from None
    return _clamp(knob, num)


def get_float(name: str) -> Optional[float]:
    knob = _knob(name, "float")
    raw = _raw(name)
    if raw is None:
        return knob.default if knob.default is None \
            else _clamp(knob, float(knob.default))
    try:
        num = float(raw)
    except ValueError:
        raise KnobError(knob, raw, "not a number") from None
    return _clamp(knob, num)


def get_bool(name: str) -> bool:
    knob = _knob(name, "bool")
    raw = _raw(name)
    if raw is None:
        return bool(knob.default)
    low = raw.lower()
    if low in _TRUE:
        return True
    if low in _FALSE:
        return False
    raise KnobError(knob, raw, "not a boolean (%s / %s)"
                    % ("/".join(_TRUE), "/".join(_FALSE)))


def get_str(name: str) -> str:
    knob = _knob(name, "str")
    raw = _raw(name)
    if raw is None:
        return knob.default
    if knob.choices is not None and raw not in knob.choices:
        raise KnobError(knob, raw,
                        "not one of %s" % "/".join(knob.choices))
    return raw


def get_path(name: str) -> Optional[str]:
    """Path knobs: a filesystem location (or the conventional "0" =
    explicitly disabled, which callers test for). None = unset."""
    knob = _knob(name, "path")
    raw = _raw(name)
    return knob.default if raw is None else raw


# ----------------------------------------------------------------------
# the registry — grouped as the README table renders them
# ----------------------------------------------------------------------

# ingress pipeline (ops/ingress_pipeline.py)
register("GS_PIPELINE_WORKERS", "int", None, lo=0,
         help="prep worker-pool width; unset = min(4, cpus-1), `0` "
              "pins the synchronous single-thread form",
         default_text="min(4, cpus-1)")
register("GS_PIPELINE_INFLIGHT", "int", 3, lo=1,
         help="max prepped+transferred chunks kept in flight ahead of "
              "dispatch (the bounded-footprint contract)")
register("GS_STREAM_PREFETCH", "bool", True,
         help="`0` pins the synchronous ingress form everywhere (the "
              "A/B lever `ops/ingress_pipeline.forced_sync` scopes "
              "per-measurement)")

# stage watchdogs & tier demotion (utils/resilience.py)
register("GS_STAGE_TIMEOUT_S", "float", 0.0, lo=0.0,
         help="per-stage watchdog deadline: a hung "
              "prep/h2d/dispatch/finalize surfaces as a typed "
              "`StageTimeout` naming the chunk instead of stalling "
              "forever; 0 = off",
         default_text="0 (off)")
register("GS_STAGE_RETRIES", "int", 0, lo=0,
         help="bounded retry for the pure stages (prep, h2d, the "
              "driver's scan dispatch); exhaustion raises "
              "`StageFailed` with per-attempt timings")
register("GS_STAGE_BACKOFF_S", "float", 0.05, lo=0.0,
         help="deterministic (jitterless) exponential backoff base "
              "between attempts")
register("GS_TIER_RETRY_WINDOWS", "int", 0, lo=0,
         help="probation length before a demoted snapshot tier "
              "re-probes the faster one; 0 = never",
         default_text="0 (never)")
register("GS_TIER_DEMOTE", "bool", True,
         help="`0` pins the resolved tier: persistent device failure "
              "raises instead of degrading sharded→scan→native→host")
register("GS_MESH_DEMOTE", "bool", True,
         help="`0` pins a sharded session to the mesh (the "
              "`sharded→scan` rung specifically): a dead shard then "
              "raises the typed stage error instead of degrading to "
              "one device; subordinate to `GS_TIER_DEMOTE`")
register("GS_MESH_WIRE_CHECK", "bool", False,
         help="`1` arms the per-shard range check of every mesh-bound "
              "h2d stack (`parallel/sharded.guard_wire`): a corrupt "
              "shard wire surfaces as a typed stage failure naming "
              "the shard instead of scattering garbage ids into "
              "carried state",
         default_text="0 (off)")

# dispatch autotuner (ops/autotune.py)
register("GS_AUTOTUNE", "bool", True,
         help="`0` disables the online dispatch scheduler "
              "(`ops/autotune.py`): windows-per-dispatch / K / "
              "ingress then run their static defaults "
              "bit-identically; on, the tuner ε-greedily "
              "(deterministically, with 1.05× hysteresis) finds the "
              "fast configuration on the live stream")
register("GS_AUTOTUNE_ROUND", "int", 4, lo=1,
         help="dispatch chunks per tuner measurement round; a 1-chunk "
              "round would silently measure the synchronous form")
register("GS_AUTOTUNE_EXPLORE", "int", 3, lo=2,
         help="every Nth measurement round explores the next "
              "single-knob move off the incumbent; the rest exploit")
register("GS_TUNE_CACHE", "path", None,
         help="directory of the per-backend tuning cache "
              "(`tuning_<backend>.json`) that seeds the next run with "
              "this run's optimum; `0` disables persistence",
         default_text="`~/.cache/gelly_streaming_tpu`")

# resident-state tier (ops/resident_engine.py)
register("GS_RESIDENT", "str", "", choices=("on", "off"),
         help="pin the resident-state snapshot tier "
              "(`ops/resident_engine.py`): `on` selects it; unset or "
              "`off` = the scan tier",
         default_text="off")
register("GS_RESIDENT_SPB", "int", 256, lo=1,
         help="windows per super-batch of the resident megakernel "
              "(one donated dispatch folds this many windows; "
              "compile-size-capped on TPU backends)")
register("GS_RESIDENT_SLOTS", "int", 2, lo=1,
         help="ingest-ring depth of the resident tier: super-batches "
              "prepped+transferred ahead of dispatch (2 = the "
              "double-buffered form — slot N+1 fills while N computes)")

# fused window megakernel (ops/pallas_window.py)
register("GS_PALLAS_WINDOW", "str", "", choices=("on", "off"),
         help="pin the fused Pallas window megakernel "
              "(`ops/pallas_window.py`): `on` selects it (interpret "
              "mode off-TPU); unset or `off` = the XLA fused scan",
         default_text="off")
register("GS_PALLAS_TILE", "int", 0, lo=0,
         help="pin the megakernel's edge-tile size (edges per grid "
              "step, power of two ≤ edge_bucket); 0 (default) = the "
              "`pallas_window` tuner's persisted optimum, else the "
              "whole slab off-TPU (interpret unrolls the grid at "
              "trace) / 512 on chip",
         default_text="0 (auto)")
register("GS_PALLAS_CK", "int", 0, lo=0,
         help="pin the megakernel's intersection compare-chunk width "
              "(the K-chunk of the seed kernel's inner loop); 0 "
              "(default) = min(128, k_bucket)",
         default_text="0 (auto)")

# egress (ops/delta_egress.py)
register("GS_EGRESS", "str", "", choices=("full", "delta"),
         help="pin the batched d2h egress: `full` (whole snapshot "
              "vectors) or `delta` (per-window changed-slot wire, "
              "`ops/delta_egress.py`); unset = `full`",
         default_text="full")
register("GS_EGRESS_CAP", "int", None, lo=1,
         help="per-window changed-slot capacity of the delta wire; a "
              "window that overflows it refolds its chunk on the "
              "bit-exact host twin, so any cap stays exact",
         default_text="min(2·eb, vb)")

# flight recorder (utils/telemetry.py)
register("GS_TELEMETRY", "bool", False,
         help="arm the flight recorder (`utils/telemetry.py`): "
              "unified spans/counters/gauges with per-run trace IDs "
              "and per-chunk correlation across every layer; off, "
              "nothing reaches the ring or the ledger and the hot "
              "path is bit-identical (bench A/B sections run disarmed "
              "by default). Armed or not, spans and counters also "
              "land in a live `jax.profiler` capture",
         default_text="0 (off)")
register("GS_TRACE_DIR", "path", None,
         help="directory of the crash-safe JSONL run ledger "
              "(`trace_<id>.jsonl`); durable-class events (kills, "
              "demotions, stage timeouts, checkpoints, resumes) are "
              "appended+fsync'd immediately, buffered spans flush at "
              "exit/SIGTERM/fatal-fault",
         default_text="unset")
register("GS_TRACE_RING", "int", 4096, lo=16,
         help="in-memory ring-buffer capacity (records) — the "
              "\"last N spans\" a wedge still leaves on disk")
register("GS_TRACE_DURABLE", "bool", True,
         help="`0` drops the per-durable-event fsync (append still "
              "happens; only the power-loss window widens)")

# live health plane (utils/metrics.py + utils/healthz.py)
register("GS_METRICS", "bool", False,
         help="arm the streaming metrics registry "
              "(`utils/metrics.py`): stage latency histograms, "
              "window/edge throughput, retry/demotion/fault/"
              "checkpoint counters and the compile & memory watch, "
              "fed from the flight-recorder hooks; off (the default) "
              "every hook is a guarded no-op and the hot path is "
              "bit-identical",
         default_text="0 (off)")
register("GS_METRICS_PORT", "int", 0, lo=0, hi=65535,
         help="serve `/metrics` (Prometheus text) and `/healthz` "
              "(JSON) from a stdlib http daemon thread on this "
              "127.0.0.1 port (`utils/healthz.py`); 0 (default) = no "
              "server — the registry still records when GS_METRICS=1",
         default_text="0 (off)")
register("GS_METRICS_SERIES", "int", 64, lo=1,
         help="label-set cardinality bound per metric name: beyond "
              "it new label sets collapse into one `overflow` series "
              "(each DISTINCT collapsed set counts once in "
              "`gs_metrics_dropped_series_total`), so a tenant-shaped "
              "label can never grow the registry unboundedly")
register("GS_METRICS_COMPILE_BASE", "int", 8, lo=1,
         help="base compile allowance per jitted function in the "
              "recompile watch: a function may compile `base + "
              "log2(max/min observed arg size) + 1` times (the "
              "O(log V) bucket-growth envelope) before a durable "
              "`recompile_storm` event fires")
register("GS_HEALTH_STALE_S", "float", 30.0, lo=0.0,
         help="staleness watchdog deadline: with the metrics plane "
              "armed, no window finalizing for this many seconds "
              "flips `/healthz` to `degraded` and writes a durable "
              "`health_degraded` event (the hung-stream detector); "
              "0 disables the watchdog",
         default_text="30")

# multi-tenant cohort scheduler (core/tenancy.py)
register("GS_TENANT_MAX", "int", 64, lo=1,
         help="admission cap of the multi-tenant cohort scheduler "
              "(`core/tenancy.py`): tenants past it are refused with "
              "a typed `TenantRejected` + a durable `tenant_rejected` "
              "event instead of degrading every admitted stream")
register("GS_TENANT_QUEUE_WINDOWS", "int", 8, lo=1,
         help="per-tenant ingest-queue depth in windows (capacity = "
              "depth x edge_bucket edges): the bounded backpressure "
              "buffer between feed() and the cohort dispatch")
register("GS_TENANT_ADMISSION", "str", "reject",
         choices=("reject", "drop"),
         help="queue-overflow policy: `reject` raises a typed "
              "`TenantBackpressure` naming the tenant (accepting "
              "nothing — the caller owns retry), `drop` accepts what "
              "fits and sheds the rest with a durable event + counter")
register("GS_TENANT_TPD", "int", 0, lo=0,
         help="pin tenants-per-dispatch of the cohort slab; 0 "
              "(default) lets the dispatch autotuner's "
              "tenants-per-dispatch arm choose (all ready tenants in "
              "one vmapped dispatch with GS_AUTOTUNE=0)",
         default_text="0 (auto)")
register("GS_COHORT_RESIDENT", "str", "", choices=("on", "off"),
         help="pin the resident cohort tier (`core/tenancy.py`): a "
              "donated `[N, ...]` stacked-carry super-batch program "
              "per cohort instead of restacking carries every round; "
              "`on` selects it; unset or `off` = per-round restacking",
         default_text="off")
register("GS_COHORT_PALLAS", "str", "", choices=("on", "off"),
         help="pin the tenant-axis Pallas cohort megakernel "
              "(`ops/pallas_window.py`): one `pallas_call` with the "
              "tenant axis as a second grid dimension serves the "
              "whole cohort from VMEM; `on` selects it (interpret "
              "mode off-TPU); unset or `off` = the vmapped XLA "
              "cohort scan",
         default_text="off")

# durable serving front-end (utils/wal.py + core/serve.py)
register("GS_WAL", "bool", True,
         help="`0` is the write-ahead-journal kill switch: every "
              "`enable_wal()` call site (cohort, engines, driver) "
              "degrades to a no-op and the ingest paths stay "
              "bit-identical to a journal-less run; 1 (default) lets "
              "callers that explicitly enable a journal get one")
register("GS_WAL_FSYNC_S", "float", 0.0, lo=0.0,
         help="fsync batching interval of the edge journal: 0 "
              "(default) fsyncs every append (tightest power-loss "
              "window), >0 batches fsyncs to at most one per interval "
              "(appends in between are flushed but not synced)",
         default_text="0 (every append)")
register("GS_WAL_RETAIN", "bool", False,
         help="`1` arms journal retention: every checkpoint FLUSH "
              "(engine/driver auto-checkpoint, cohort "
              "`checkpoint_all()`) calls `truncate_covered()` with "
              "the OLDER of the two kept checkpoint generations' "
              "offsets, so bounded disk never deletes a record a "
              "rotation-fallback recovery would still replay; 0 "
              "(default) keeps every closed segment",
         default_text="0 (off)")
register("GS_WAL_SEGMENT_BYTES", "int", 1 << 26, lo=4096,
         help="journal segment-rotation size: a segment past this "
              "many bytes closes (fsync'd) and appends continue in a "
              "fresh `wal_<n>.seg`; records never split across "
              "segments",
         default_text="67108864 (64 MiB)")
register("GS_SERVE_PORT", "int", 0, lo=0, hi=65535,
         help="TCP port of the serving front-end "
              "(`core/serve.StreamServer`, 127.0.0.1); 0 in code = "
              "OS-assigned ephemeral port (tests print `.port`)",
         default_text="0 (ephemeral)")
register("GS_SERVE_DRAIN_S", "float", 30.0, lo=0.0,
         help="graceful-drain deadline: on SIGTERM the server stops "
              "accepting, waits up to this long for in-flight "
              "requests, pumps every queue dry, checkpoints, seals "
              "the journal and exits 0; 0 = no deadline (wait "
              "forever for in-flight requests)",
         default_text="30")
register("GS_SERVE_IDLE_S", "float", 60.0, lo=0.1,
         help="per-connection deadline of the serving front-end: a "
              "connection idle (no request) this long is closed, and "
              "a response send stalled this long is SHED (durable "
              "`serve_client_shed` event) so a slow client can never "
              "wedge the pump",
         default_text="60")

# end-to-end latency plane (utils/latency.py)
register("GS_LATENCY", "bool", False,
         help="arm the ingest→deliver latency plane "
              "(`utils/latency.py`): admission stamps on every "
              "accepted edge batch (carried through the WAL ts "
              "column so replayed windows keep their original "
              "admission time), per-window stage waterfalls, "
              "per-tenant latency percentiles, the "
              "oldest-unfinalized-edge age gauge and the SLO burn "
              "module; off (the default) every hook is a guarded "
              "no-op and the hot path is bit-identical",
         default_text="0 (off)")
register("GS_LAT_MARKS", "int", 4096, lo=16,
         help="per-lane admission-mark memory bound (batches "
              "remembered between admission and window finalize); a "
              "window whose mark was evicted reports an approximate, "
              "conservative latency instead of growing memory")
register("GS_LAT_PENDING", "int", 1024, lo=16,
         help="bounded finalized-but-undelivered window records the "
              "serving front-end may hold between pump and sink "
              "write; past it the oldest emits as-finalized")
register("GS_SLO_P99_S", "float", 0.0, lo=0.0,
         help="delivered-window end-to-end latency target "
              "(seconds): each window past it burns the error "
              "budget; 0 (default) disables the SLO module",
         default_text="0 (off)")
register("GS_SLO_BUDGET", "float", 0.01, lo=1e-6, hi=1.0,
         help="error budget: the allowed fraction of delivered "
              "windows over the GS_SLO_P99_S target")
register("GS_SLO_WINDOW_S", "float", 60.0, lo=1.0,
         help="sliding window (seconds) the SLO burn rate is "
              "measured over")
register("GS_SLO_BURN", "float", 2.0, lo=0.1,
         help="burn rate ((bad/total)/budget) at or above which the "
              "`/healthz` `latency` section flips `degraded` with a "
              "durable `slo_burn` event (once per episode; recovery "
              "stamps `slo_recovered`)")

# admission sanitizer, dead-letter journal & tenant bulkheads
# (utils/sanitize.py + core/tenancy.py)
register("GS_SANITIZE", "str", "off", choices=("off", "on", "strict"),
         help="admission sanitizer (`utils/sanitize.py`) run at every "
              "ingest boundary BEFORE the journal: `off` (default) is "
              "bit-identical to a pre-sanitizer build, `on` rejects "
              "structurally invalid records (out-of-range / negative "
              "/ int32-overflowing / non-integer ids) with typed "
              "reason codes, `strict` adds the self-loop and "
              "duplicate-flood policies",
         default_text="off")
register("GS_DLQ_DIR", "path", None,
         help="dead-letter journal directory: rejected admission "
              "records are appended as CRC-framed segment records "
              "(origin tenant + source offset + reason + the edges) "
              "for `tools/dlq_report.py` to render and re-inject; "
              "unset/`0` = rejections are counted and dropped",
         default_text="unset")
register("GS_DLQ_RETAIN", "int", 0, lo=0,
         help="closed dead-letter segments kept after rotation "
              "(rotation size is GS_WAL_SEGMENT_BYTES); 0 (default) "
              "keeps every segment",
         default_text="0 (keep all)")
register("GS_QUARANTINE_WINDOWS", "int", 4, lo=0,
         help="clean solo probation windows a quarantined tenant "
              "must finalize before the cohort re-admits it to the "
              "shared vmapped dispatch (`core/tenancy.py` bulkhead); "
              "0 = quarantine is permanent for the process")
register("GS_MAX_BATCH_EDGES", "int", 0, lo=0,
         help="admission batch-size bound: a single feed()/process() "
              "batch longer than this is refused whole with a typed "
              "`BatchRejected` (and journaled to the DLQ when armed); "
              "0 (default) = unbounded",
         default_text="0 (unbounded)")

# async serving pump, sliding windows & event time
# (core/serve.py + core/tenancy.py + ops/windowed_reduce.py +
#  ops/scan_analytics.py + core/driver.py)
register("GS_PUMP", "str", "sync", choices=("sync", "async"),
         help="serving pump mode (`core/serve.StreamServer`): `sync` "
              "(default) pumps inline under the request lock — "
              "bit-identical to the pre-pump build; `async` runs slab "
              "prep → h2d → dispatch → finalize on a dedicated pump "
              "thread so the accept loop and file tails only "
              "sanitize → journal → enqueue under the queue lock "
              "(ingest overlaps compute; same digests, honest "
              "`queue_wait` attribution)",
         default_text="sync")
register("GS_SLIDE", "int", 0, lo=0,
         help="sliding-window slide in edges for the windowing "
              "engines/driver (`slide=` default): the window advances "
              "by this many edges per emission, each edge folds into "
              "its pane ONCE and `window/slide` pane summaries "
              "compose per emission; must be a power of two dividing "
              "the window size; 0 (default) = tumbling "
              "(slide == window)",
         default_text="0 (tumbling)")
register("GS_OOO_BOUND", "int", 0, lo=0,
         help="bounded out-of-orderness (event-time ns) of the "
              "per-tenant reorder buffer ahead of the monotonic "
              "guard: a `feed(ts=)` edge is held until the tenant's "
              "watermark (newest stamp − bound) passes it, then "
              "released in ts order; 0 (default) = off — ts must "
              "arrive non-decreasing exactly as before",
         default_text="0 (off)")
register("GS_SUB_QUEUE", "int", 256, lo=1,
         help="bounded per-connection queue (WindowResult rows) of "
              "the serve wire protocol's `subscribe` op; a "
              "subscriber whose queue overflows is SHED with the "
              "durable `serve_client_shed` event, never wedging the "
              "pump")

# program cost observatory (utils/costmodel.py)
register("GS_COSTMODEL", "bool", False,
         help="arm the program cost observatory "
              "(`utils/costmodel.py`): every wrapped jit/AOT program "
              "captures its XLA `cost_analysis`/`memory_analysis` "
              "(FLOPs, bytes) per abstract shape signature, and "
              "dispatch spans carry program/signature tags the "
              "attribution tools join on; off (the default) every "
              "hook is a guarded no-op and the hot path is "
              "bit-identical (armed, a jit-path program pays ONE "
              "extra AOT compile per new signature)",
         default_text="0 (off)")

# windowed GNN workload (ops/gnn_window.py)
register("GS_GNN_F", "int", 16, lo=1, hi=256,
         help="feature width F of the windowed GNN workload's "
              "per-vertex slab (`ops/gnn_window.py`); engines built "
              "without an explicit feature_dim read it at "
              "construction. F ≤ 64 keeps the dense update exactly "
              "representable on the storage lattice; larger F snaps "
              "weights to a coarser grid (same deterministic shift "
              "on every tier, so parity holds)")
register("GS_GNN_ACT", "str", "relu", choices=("relu", "abs",
                                               "identity"),
         help="activation of the GNN dense update — restricted to "
              "EXACT elementwise ops (relu/abs/identity) so the "
              "numpy twin stays a bit-exactness oracle; read at "
              "engine construction")
register("GS_GNN_PALLAS", "str", "", choices=("on", "off"),
         help="pin the fused Pallas GNN window kernel "
              "(`ops/pallas_window.maybe_gnn_body`): `on` selects it "
              "(interpret mode off-TPU); unset or `off` = the XLA "
              "gather/segment-sum body",
         default_text="off")

# tenant observatory (utils/provenance.py, per-tenant attribution)
register("GS_PROVENANCE", "bool", False,
         help="arm the per-window provenance ledger "
              "(`utils/provenance.py`): every finalize owner appends "
              "a CRC-framed record (tenant, window, wal span, tier + "
              "program, knob fingerprint, summary sha256) that "
              "`tools/replay_window.py` re-derives and diffs on any "
              "tier; disarmed (the default) every emit() is a no-op "
              "and digests are bit-identical to a ledger-less build")
register("GS_PROVENANCE_DIR", "path", None,
         help="directory of the provenance ledger's "
              "`prov_<n>.seg` segments; unset disarms emit() even "
              "with GS_PROVENANCE=1 (nowhere durable to write)")
register("GS_PROVENANCE_RETAIN", "int", 0, lo=0,
         help="closed ledger segments kept behind the open one "
              "(rotation uses GS_WAL_SEGMENT_BYTES); 0 = keep "
              "everything — the audit-trail default; bound it only "
              "when an external archiver drains the records")


# ----------------------------------------------------------------------
# docs rendering (README table; gslint R3 diffs it back)
# ----------------------------------------------------------------------
def _default_cell(knob: Knob) -> str:
    if knob.default_text is not None:
        return knob.default_text
    if knob.kind == "bool":
        return "1" if knob.default else "0"
    return str(knob.default)


def render_table() -> str:
    """The README `GS_*` knob table, one row per registered knob in
    registration order. tests/test_knobs.py (and gslint R3's docs
    check) assert the committed README contains exactly this block —
    regenerate with `python -m tools.gslint --knob-table`."""
    lines = ["| knob | default | meaning |", "|---|---|---|"]
    for knob in REGISTRY.values():
        lines.append("| `%s` | %s | %s |"
                     % (knob.name, _default_cell(knob),
                        " ".join(knob.help.split())))
    return "\n".join(lines)

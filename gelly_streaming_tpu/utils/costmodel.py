"""Program cost observatory — the attribution pillar of the
observability plane (tracing → metrics → **attribution**).

The flight recorder (utils/telemetry.py) says *when* time was spent
and the health plane (utils/metrics.py) says *how much right now*;
neither says *why* the device path sits at ~1M edges/s behind the
dispatch wall. This module closes that gap: for every compiled
program the streaming layers dispatch — the fused scan, its compact
twin, the resident super-batch, the snapshot scan, the triangle
stream programs, the sharded table-mode stream — it captures XLA's
own compiled cost model (`cost_analysis()`: FLOPs, bytes accessed;
`memory_analysis()`: argument/output/temp bytes) keyed by the same
abstract-shape signature the compile watch (metrics.wrap_jit) already
counts compiles by, and joins it with the measured dispatch spans the
flight recorder collects, yielding per program per shape:

- a **bytes-vs-FLOPs boundedness verdict**: arithmetic intensity
  (FLOPs/byte) against the machine balance (peak FLOP/s ÷ peak B/s) —
  below balance the roofline says the program is bytes-bound, above
  it FLOPs-bound;
- an **achieved-vs-roofline fraction**: the roofline-implied minimum
  seconds per dispatch (max of FLOPs/peak and bytes/bandwidth) over
  the measured mean dispatch seconds — a small fraction means the
  time went somewhere the cost model doesn't see (launch overhead,
  host sync, transfer), which is exactly the drill-down
  tools/explain_perf.py ranks suspects for.

Capture paths:

- jit-path programs (wrapped by `metrics.wrap_jit`) call `on_call`
  per dispatch: the FIRST call at a new signature AOT-lowers and
  compiles the function once more to read its analyses (jit's
  internal cache is not reachable from the outside; the extra
  compile is the armed price, documented on GS_COSTMODEL), then every
  call tags the current thread's pending dispatch-span attributes
  (telemetry.tag_dispatch) so the ledger's `ingress.dispatch` /
  `step.snapshot_scan` spans carry `program`/`sig`.
- AOT-path programs (triangles/sharded `_stream_exec`, which already
  hold the compiled executable) are wrapped by `wrap_exec`: capture
  is FREE there (the analyses are read off the existing executable).

A telemetry sink (the same `register_sink` mechanism the metrics
plane rides) accumulates measured seconds per tagged program, so
`report()` serves joined rows live; tools/explain_perf.py performs
the same join offline against a run ledger.

Zero-overhead contract: with `GS_COSTMODEL=0` (the default) every
entry point is a guarded no-op, no tags are bound, and the hot path
is bit-identical — asserted by tests/test_costmodel.py digest parity
on the 524K/32768 CPU row.

Knob (utils/knobs.py): GS_COSTMODEL — 0 (default) = disarmed no-ops;
1 = capture.

Peaks come from one table keyed by JAX's `device_kind` (PEAKS). The
verdicts are computed when rows are reported, for the device the
process runs on unless a caller names one; a device missing from the
table is an error, never a default.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from . import knobs
from . import telemetry


def enabled() -> bool:
    """GS_COSTMODEL arms the observatory; off (the default) every
    entry point — including the telemetry sink — is a guarded no-op."""
    return knobs.get_bool("GS_COSTMODEL")


# Published per-chip peaks, keyed by `jax.devices()[0].device_kind`:
# (FLOP/s, HBM bytes/s). Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 819 GB/s HBM per chip).
PEAKS = {"TPU v5 lite": (197e12, 819e9)}


def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


def peaks(kind: Optional[str] = None) -> Tuple[float, float]:
    """(peak FLOP/s, peak bytes/s) of `kind` (default: this process's
    device) from PEAKS; ValueError for a device the table lacks."""
    kind = kind or device_kind()
    if kind not in PEAKS:
        raise ValueError("no roofline peaks for device kind %r "
                         "(costmodel.PEAKS has %s)"
                         % (kind, sorted(PEAKS)))
    return PEAKS[kind]


# ----------------------------------------------------------------------
# signature rendering (the join key the ledger tags carry)
# ----------------------------------------------------------------------
_DTYPE_ABBR = {
    "int32": "i32", "int64": "i64", "uint16": "u16", "uint32": "u32",
    "float32": "f32", "float64": "f64", "bfloat16": "bf16",
    "bool": "b1", "bool_": "b1", "int8": "i8", "uint8": "u8",
}


def _render_leaf(leaf) -> str:
    if isinstance(leaf, tuple) and leaf:
        if leaf[0] == "arr":
            _tag, shape, dtype = leaf
            return "%s[%s]" % (_DTYPE_ABBR.get(dtype, dtype),
                               ",".join(str(d) for d in shape))
        if leaf[0] == "seq":
            return "(%s)" % ",".join(_render_leaf(e) for e in leaf[1:])
        if leaf[0] == "map":
            return "{%s}" % ",".join(
                "%s=%s" % (k, _render_leaf(v)) for k, v in leaf[1:])
        if leaf[0] == "py":
            return leaf[1]
    return str(leaf)


def sig_key(sig: tuple) -> str:
    """Compact deterministic string of a `metrics.abstract_sig`
    signature — the `sig` attribute dispatch spans carry and the
    cost-registry rows are keyed by (e.g.
    ``i32[64,32768],i32[64,32768],b1[64,32768]``)."""
    return ",".join(_render_leaf(leaf) for leaf in sig)


def _sig_bytes(sig) -> int:
    """Total argument bytes under one abstract signature (used only
    as a fallback when memory_analysis is unavailable)."""
    import numpy as np

    if not isinstance(sig, tuple):
        return 0
    if sig and sig[0] == "arr":
        n = 1
        for d in sig[1]:
            n *= max(int(d), 1)
        try:
            return n * np.dtype(sig[2]).itemsize
        except TypeError:
            return n
    return sum(_sig_bytes(s) for s in sig)


# ----------------------------------------------------------------------
# the process-global registry
# ----------------------------------------------------------------------
class _Registry:
    """All mutable state behind one lock. One instance per process
    (rebuilt by reset())."""

    def __init__(self):
        self.lock = threading.RLock()
        # (program, sig_key) -> cost entry dict
        self.programs: Dict[Tuple[str, str], dict] = {}
        # (program, sig_key) -> {"count": n, "total_s": s} measured
        # dispatch spans (fed by the telemetry sink)
        self.dispatches: Dict[Tuple[str, str], dict] = {}
        # program -> analytic cost template (record_analytic): for
        # these programs the compiler's introspection is KNOWN wrong
        # (a Pallas kernel's interpret lowering, an opaque Mosaic
        # binary), so per-signature capture instantiates the stated
        # model instead of compiling for analyses
        self.analytic: Dict[str, dict] = {}


_REG: Optional[_Registry] = None
_REG_LOCK = threading.Lock()


def _reg() -> _Registry:
    global _REG
    if _REG is None:
        with _REG_LOCK:
            if _REG is None:
                _REG = _Registry()
    return _REG


def reset() -> None:
    """Test/tool hook: drop every captured program and measurement."""
    global _REG
    with _REG_LOCK:
        _REG = None


# ----------------------------------------------------------------------
# capture
# ----------------------------------------------------------------------
def _extract(compiled) -> dict:
    """FLOPs/bytes entry from one AOT-compiled executable's
    cost_analysis()/memory_analysis() (None fields where the backend
    doesn't report them)."""
    out = {"flops": None, "bytes_accessed": None,
           "argument_bytes": None, "output_bytes": None,
           "temp_bytes": None, "generated_code_bytes": None}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            flops = ca.get("flops")
            bts = ca.get("bytes accessed")
            out["flops"] = None if flops is None else int(flops)
            out["bytes_accessed"] = None if bts is None else int(bts)
    except Exception as e:  # gslint: disable=except-hygiene (capability probe: a backend without cost_analysis contributes None fields; the miss is visible in the entry itself)
        out["cost_analysis_error"] = "%s: %s" % (type(e).__name__, e)
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            for field, attr in (
                    ("argument_bytes", "argument_size_in_bytes"),
                    ("output_bytes", "output_size_in_bytes"),
                    ("temp_bytes", "temp_size_in_bytes"),
                    ("generated_code_bytes",
                     "generated_code_size_in_bytes")):
                val = getattr(ma, attr, None)
                out[field] = None if val is None else int(val)
    except Exception as e:  # gslint: disable=except-hygiene (capability probe: memory_analysis is backend-optional; the miss is visible in the entry itself)
        out["memory_analysis_error"] = "%s: %s" % (type(e).__name__, e)
    return out


def classify(entry: dict, kind: Optional[str] = None) -> dict:
    """Attach the roofline verdict to one cost entry IN PLACE against
    the peaks of device `kind` (default: this process's device; an
    unknown device raises): arithmetic intensity, the machine balance
    it is judged against, the bytes/FLOPs `bound` verdict, and the
    roofline-implied minimum seconds per dispatch. Entries without
    both FLOPs and bytes get verdict `unknown` and need no peaks —
    with the flops/bytes keys still PRESENT (null), so every row
    classify() touches satisfies the committed cost_model schema's
    required keys (error-path and armed-mid-stream rows included:
    "not reported" must stay distinguishable from "silently
    dropped")."""
    entry.setdefault("flops", None)
    entry.setdefault("bytes_accessed", None)
    flops, bts = entry.get("flops"), entry.get("bytes_accessed")
    if flops and bts:
        peak_f, peak_b = peaks(kind)
        entry["device_kind"] = kind or device_kind()
        entry["machine_balance_flops_per_byte"] = round(
            peak_f / peak_b, 3)
        intensity = flops / bts
        entry["arith_intensity_flops_per_byte"] = round(intensity, 4)
        entry["bound"] = ("bytes" if intensity < peak_f / peak_b
                          else "flops")
        entry["roofline_s"] = max(flops / peak_f, bts / peak_b)
    else:
        entry["machine_balance_flops_per_byte"] = None
        entry["arith_intensity_flops_per_byte"] = None
        entry["bound"] = "unknown"
        entry["roofline_s"] = None
    return entry


def record_compiled(program: str, compiled, sig: tuple) -> None:
    """Register the cost model of an already-AOT-compiled executable
    (the triangles/sharded `_stream_exec` caches) under
    (program, sig). Idempotent per key; armed only."""
    if not enabled():
        return
    key = (program, sig_key(sig))
    reg = _reg()
    with reg.lock:
        if key in reg.programs:
            return
        # reserve the key before the (lock-free) extraction so a
        # concurrent dispatcher never double-captures
        reg.programs[key] = {"program": program, "sig": key[1],
                             "pending": True}
    if _instantiate_analytic(reg, key):
        return
    entry = _extract(compiled)
    entry.update(program=program, sig=key[1])
    with reg.lock:
        reg.programs[key] = entry
    telemetry.event("costmodel.capture", program=program, sig=key[1],
                    flops=entry.get("flops"),
                    bytes_accessed=entry.get("bytes_accessed"))


def record_analytic(program: str, sig_text: str, flops,
                    bytes_accessed, **extra) -> None:
    """Register a hand-computed cost entry for a program XLA's
    introspection can't see through — the Pallas window megakernel:
    its interpret-mode lowering bears no relation to the chip
    kernel's HBM traffic, and a Mosaic executable exposes no
    cost_analysis — under the same registry/joins as captured
    entries. Rows carry model="analytic" (plus whatever `extra`
    provenance the caller stamps, e.g. the slab-read byte model) so
    a reader can tell a stated model from a compiler measurement.

    Registers TWICE: the documentation row under the caller's
    free-text `sig_text`, AND a program-level TEMPLATE that
    on_call/record_compiled instantiate at each dispatch signature —
    so the ledger spans wrap_jit tags (keyed by the abstract-shape
    sig) join the STATED model, never a capture the registrant just
    declared meaningless (and the armed extra-compile is skipped for
    these programs). Idempotent per (program, sig); armed only."""
    if not enabled():
        return
    key = (program, str(sig_text))
    reg = _reg()
    with reg.lock:
        if key in reg.programs:
            return
        reg.programs[key] = {"program": program, "sig": key[1],
                             "pending": True}
    entry = {"program": program, "sig": key[1], "model": "analytic",
             "flops": None if flops is None else int(flops),
             "bytes_accessed": (None if bytes_accessed is None
                                else int(bytes_accessed))}
    entry.update(extra)
    with reg.lock:
        reg.programs[key] = entry
        reg.analytic[program] = {
            k: v for k, v in entry.items() if k != "sig"}
    telemetry.event("costmodel.capture", program=program, sig=key[1],
                    flops=entry.get("flops"),
                    bytes_accessed=entry.get("bytes_accessed"),
                    model="analytic")


def _instantiate_analytic(reg, key: Tuple[str, str]) -> bool:
    """If `key[0]` has an analytic template, store its instance at
    `key` (the dispatch signature the spans carry) and return True —
    the capture paths then skip compiler introspection entirely."""
    with reg.lock:
        template = reg.analytic.get(key[0])
        if template is None:
            return False
        if key not in reg.programs or \
                reg.programs[key].get("pending"):
            entry = dict(template)
            entry["sig"] = key[1]
            reg.programs[key] = entry
    return True


def on_call(program: str, fn, sig: tuple, args, kwargs) -> None:
    """Per-dispatch hook of a jit-path program (called by
    metrics.wrap_jit with the signature it already computed): tag the
    pending dispatch-span attributes, and on the FIRST call at a new
    signature capture the program's cost model by AOT-lowering and
    compiling `fn` once more (the armed price — jit's internal
    executable cache is not reachable)."""
    if not enabled():
        return
    key = (program, sig_key(sig))
    telemetry.tag_dispatch(program=program, sig=key[1])
    reg = _reg()
    with reg.lock:
        if key in reg.programs:
            return
        reg.programs[key] = {"program": program, "sig": key[1],
                             "pending": True}
    if _instantiate_analytic(reg, key):
        # a stated-model program: no extra AOT compile, the spans
        # join the analytic entry at this very signature
        return
    lower = getattr(fn, "lower", None)
    if lower is None:
        entry = {"program": program, "sig": key[1],
                 "error": "not AOT-lowerable (no .lower)"}
        with reg.lock:
            reg.programs[key] = entry
        return
    try:
        compiled = lower(*args, **kwargs).compile()
        entry = _extract(compiled)
    except Exception as e:
        entry = {"error": "%s: %s" % (type(e).__name__, str(e)[:200])}
        telemetry.event("costmodel.capture_failed", program=program,
                        sig=key[1], error=entry["error"])
    entry.update(program=program, sig=key[1])
    with reg.lock:
        reg.programs[key] = entry
    if "error" not in entry:
        telemetry.event("costmodel.capture", program=program,
                        sig=key[1], flops=entry.get("flops"),
                        bytes_accessed=entry.get("bytes_accessed"))


def wrap_exec(program: str, ex, sig: tuple):
    """Wrap an AOT-compiled executable: armed, each call tags the
    pending dispatch-span attributes and the first call registers the
    executable's cost model (free — no recompile). Disarmed the
    wrapper is one knob check + passthrough, and arming mid-stream
    still captures (the compiled handle rides the closure)."""

    def wrapped(*args, **kwargs):
        if enabled():
            record_compiled(program, ex, sig)
            telemetry.tag_dispatch(program=program, sig=sig_key(sig))
        return ex(*args, **kwargs)

    wrapped.__name__ = program
    wrapped.__wrapped__ = ex
    return wrapped


# ----------------------------------------------------------------------
# the telemetry sink: measured dispatch spans tagged with program/sig
# accumulate here, so report() serves the live join
# ----------------------------------------------------------------------
def _sink(rec: dict) -> None:
    if rec.get("t") != "span":
        return
    attrs = rec.get("a") or {}
    program = attrs.get("program")
    if not program:
        return
    key = (program, attrs.get("sig", "?"))
    reg = _reg()
    with reg.lock:
        d = reg.dispatches.setdefault(key, {"count": 0, "total_s": 0.0})
        d["count"] += 1
        d["total_s"] += float(rec.get("dur", 0.0))  # gslint: disable=host-sync (a telemetry record's host float)


telemetry.register_sink(_sink, enabled)


# ----------------------------------------------------------------------
# reporting
# ----------------------------------------------------------------------
def programs() -> Dict[Tuple[str, str], dict]:
    reg = _reg()
    with reg.lock:
        return {k: dict(v) for k, v in reg.programs.items()}


def join_measure(entry: dict, count: int, total_s: float) -> dict:
    """Attach measured-dispatch economics to one classified cost
    entry (shared by the live report and the offline ledger join in
    tools/explain_perf.py): mean seconds per dispatch, achieved
    GFLOP/s / GB/s, and the achieved-vs-roofline fraction."""
    entry["dispatches"] = count
    entry["measured_total_s"] = round(total_s, 6)
    if not count or total_s <= 0:
        return entry
    mean_s = total_s / count
    entry["measured_mean_s"] = round(mean_s, 6)
    flops, bts = entry.get("flops"), entry.get("bytes_accessed")
    if flops:
        entry["achieved_gflops"] = round(flops / mean_s / 1e9, 3)
    if bts:
        entry["achieved_gbps"] = round(bts / mean_s / 1e9, 3)
    roof = entry.get("roofline_s")
    if roof:
        entry["roofline_frac"] = round(roof / mean_s, 6)
    return entry


def report(kind: Optional[str] = None) -> List[dict]:
    """Joined per-program-per-shape rows: the captured cost model,
    classified against the peaks of device `kind` (default: this
    process's device; an unknown device raises), plus whatever
    measured dispatch seconds the sink has accumulated, sorted by
    measured time then program name — the `programs` rows the
    profiler commits to PERF.json's `cost_model` section."""
    reg = _reg()
    with reg.lock:
        progs = {k: dict(v) for k, v in reg.programs.items()}
        disp = {k: dict(v) for k, v in reg.dispatches.items()}
    rows = []
    for key, entry in progs.items():
        entry.pop("pending", None)
        # a capture still in flight on another thread is served
        # classified (null cost fields) rather than bare
        classify(entry, kind)
        d = disp.pop(key, None)
        if d:
            join_measure(entry, d["count"], d["total_s"])
        else:
            entry["dispatches"] = 0
            entry["measured_total_s"] = 0.0
        rows.append(entry)
    # measured dispatches whose program was never captured (e.g. armed
    # mid-stream after the compile): still reported, cost-less
    for key, d in disp.items():
        rows.append(join_measure(
            classify({"program": key[0], "sig": key[1]}, kind),
            d["count"], d["total_s"]))
    rows.sort(key=lambda r: (-r.get("measured_total_s", 0.0),
                             r.get("program") or "", r.get("sig") or ""))
    return rows

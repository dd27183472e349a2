"""The measurement-driven kernel selections, tested end-to-end on
synthetic PERF.json files (VERDICT r2 item 5: the selection framework
must itself be under test so a committed chip profile provably flips
the defaults).

Covers the three selectors in ops/triangles.py:
  - resolve_intersect_impl (Pallas fused-tile vs XLA winner)
  - _resolve_dense_choice (Pallas fused contraction vs XLA matmul)
  - _tuned_kb (k-sweep-driven starting K per edge bucket)
and the backend-matching guards of _load_matching_perf (a cpu-labeled
file must never drive a chip selection and vice versa).
"""

import json

import jax
import pytest

from gelly_streaming_tpu.ops import triangles
from gelly_streaming_tpu.ops.pallas_intersect import intersect_local_pallas
from gelly_streaming_tpu.ops.triangles import DENSE_LIMIT


@pytest.fixture
def selection_env(tmp_path, monkeypatch):
    """Redirect the selectors at a writable PERF.json, reset their
    once-per-process caches, and let the test pick the apparent
    backend. Restores everything afterwards."""
    perf_path = tmp_path / "PERF.json"
    monkeypatch.setattr(triangles, "_PERF_PATH", str(perf_path))
    monkeypatch.setattr(triangles, "_INTERSECT_CHOICE", None)
    monkeypatch.setattr(triangles, "_INTERSECT_JIT", None)
    monkeypatch.setattr(triangles, "_DENSE_CHOICE", None)
    monkeypatch.setattr(triangles, "_TUNED_KB", {})
    monkeypatch.setattr(triangles, "_TUNED_CHUNK", {})
    monkeypatch.setattr(triangles, "_STREAM_IMPL", None)
    monkeypatch.setattr(triangles, "_STREAM_IMPL_EB", {})
    monkeypatch.setattr(triangles, "_INGRESS", None)
    monkeypatch.setattr(triangles, "_COMPILE_CAPS", {})

    def configure(file_backend, process_backend, **sections):
        perf_path.write_text(
            json.dumps(dict({"backend": file_backend}, **sections)))
        monkeypatch.setattr(jax, "default_backend",
                            lambda: process_backend)

    return configure


INTERSECT_WIN = {"parity_pallas": True, "pallas_vs_xla_compare": 1.20}
DENSE_WIN = [{"num_vertices": 1024, "pallas_speedup": 1.10},
             {"num_vertices": 2048, "pallas_speedup": 1.07}]


def test_intersect_flips_to_pallas_on_winning_chip_rows(selection_env):
    selection_env("tpu", "tpu", intersect=INTERSECT_WIN)
    assert triangles.resolve_intersect_impl() is intersect_local_pallas


@pytest.mark.parametrize("row", [
    {"parity_pallas": True, "pallas_vs_xla_compare": 1.02},  # < 5% win
    {"parity_pallas": False, "pallas_vs_xla_compare": 9.9},  # no parity
    {},                                                      # no data
])
def test_intersect_keeps_xla_compare_without_a_clean_win(
        selection_env, row):
    selection_env("tpu", "tpu", intersect=row)
    assert triangles.resolve_intersect_impl() is triangles.intersect_local


def test_intersect_ignores_cpu_labeled_file_on_chip(selection_env):
    # the same winning rows, recorded on the wrong backend: no flip
    selection_env("cpu", "tpu", intersect=INTERSECT_WIN)
    assert triangles.resolve_intersect_impl() is triangles.intersect_local


def test_intersect_on_cpu_stays_bsearch_despite_chip_rows(selection_env):
    # chip-only selection: a cpu process keeps its measured XLA winner
    selection_env("tpu", "cpu", intersect=INTERSECT_WIN)
    assert (triangles.resolve_intersect_impl()
            is triangles.intersect_local_bsearch)


INGRESS_WIN = [{"probe": "stream_ab", "parity": True, "speedup": 1.31}]


def test_ingress_flips_to_compact_on_winning_rows(selection_env):
    selection_env("tpu", "tpu", ingress_ab=INGRESS_WIN)
    assert triangles.resolve_ingress(65536) == "compact"


@pytest.mark.parametrize("rows", [
    [{"parity": True, "speedup": 1.02}],   # < 5% win
    [{"parity": False, "speedup": 9.9}],   # no parity
    [],                                    # no data
    [{"parity": True, "speedup": 1.31},
     {"parity": True, "speedup": 0.98}],   # must win at EVERY row
])
def test_ingress_stays_standard_without_a_clean_win(selection_env, rows):
    selection_env("tpu", "tpu", ingress_ab=rows)
    assert triangles.resolve_ingress(65536) == "standard"


def test_ingress_vb_gate_overrides_winning_rows(selection_env):
    # ids wider than uint16: the format is lossy there, never selected
    selection_env("tpu", "tpu", ingress_ab=INGRESS_WIN)
    assert triangles.resolve_ingress(1 << 17) == "standard"
    # the memoized win still applies to buckets that DO fit
    assert triangles.resolve_ingress(32768) == "compact"


def test_ingress_ignores_other_backend_rows(selection_env):
    selection_env("cpu", "tpu", ingress_ab=INGRESS_WIN)
    assert triangles.resolve_ingress(65536) == "standard"


def test_compile_cap_raised_by_clean_probe_row(selection_env):
    selection_env("tpu", "tpu", compile_probe=[
        {"program": "triangle_stream", "slots": 1 << 20, "ok": True,
         "compile_s": 41.0}])
    assert triangles.compile_cap("triangle_stream") == 1 << 20
    # ...and the chunk selector sees it: 2^20 / 32768 = 32 windows
    assert triangles._default_chunk(32768) == 32


FUSED_WEDGE_ROWS = [
    {"program": "fused_scan", "slots": 1 << 19, "ok": False,
     "reason": "timeout"},
    {"program": "fused_scan", "slots": 1 << 17, "ok": True,
     "compile_s": 30.0},
]


def test_compile_cap_lowered_by_probed_failure(selection_env):
    selection_env("tpu", "tpu", compile_probe_scan=FUSED_WEDGE_ROWS)
    assert triangles.compile_cap("fused_scan") == 1 << 17
    # no clean row below the failure: quarter of the failing size
    triangles._reset_compile_caps()
    selection_env("tpu", "tpu", compile_probe_scan=[
        {"program": "snapshot_scan", "slots": 1 << 18, "ok": False,
         "reason": "timeout"}])
    assert triangles.compile_cap("snapshot_scan") == 1 << 16


def test_compile_cap_failure_above_proven_size_keeps_the_default(
        selection_env):
    # a 2^20 triangle wedge must not drag the cap below 2^19 — that
    # size compiled clean in the round-4 chip window (the quarter
    # fallback applies only to programs with NO proven size)
    selection_env("tpu", "tpu", compile_probe=[
        {"program": "triangle_stream", "slots": 1 << 20, "ok": False,
         "reason": "timeout"}])
    assert triangles.compile_cap("triangle_stream") == 1 << 19


def test_compile_cap_ignores_inconclusive_rows(selection_env):
    # ok=None (a crash, not a timed-out compile) moves
    # nothing in either direction
    selection_env("tpu", "tpu", compile_probe_scan=[
        {"program": "fused_scan", "slots": 1 << 17, "ok": None,
         "reason": "backend cpu"}])
    assert triangles.compile_cap("fused_scan") == 1 << 19


def test_compile_cap_ignores_other_backend_and_programs(selection_env):
    selection_env("cpu", "tpu", compile_probe=[
        {"program": "triangle_stream", "slots": 1 << 20, "ok": True}])
    assert triangles.compile_cap("triangle_stream") == 1 << 19
    triangles._reset_compile_caps()
    selection_env("tpu", "tpu", compile_probe=[
        {"program": "triangle_stream", "slots": 1 << 20, "ok": True}])
    # another program's rows never move this program's cap
    assert triangles.compile_cap("fused_scan") == 1 << 19


def test_fused_engine_honors_lowered_cap(selection_env):
    # a probed fused-scan wedge at 2^19 with a clean 2^17 row must
    # shrink the engine's windows-per-dispatch on a chip backend
    # (2^17 / eb=8192 -> 16), while the triangle kernel keeps ITS cap
    selection_env("tpu", "tpu", compile_probe_scan=FUSED_WEDGE_ROWS)
    from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine

    eng = StreamSummaryEngine(edge_bucket=8192, vertex_bucket=16384)
    assert eng.MAX_WINDOWS == 16
    assert triangles._default_chunk(8192) == 64  # 2^19 / 8192


def test_capped_chunk_unlimited_off_chip(selection_env):
    selection_env("cpu", "cpu", compile_probe_scan=[
        {"program": "fused_scan", "slots": 1 << 17, "ok": False}])
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel
    assert (triangles.capped_chunk(32768, "fused_scan")
            == TriangleWindowKernel.MAX_STREAM_WINDOWS)


def test_dense_flips_to_pallas_and_doubles_limit(selection_env):
    selection_env("tpu", "tpu", dense=DENSE_WIN)
    assert triangles._resolve_dense_choice() == ("pallas", 2 * DENSE_LIMIT)


def test_dense_requires_a_win_at_every_measured_v(selection_env):
    selection_env("tpu", "tpu", dense=DENSE_WIN + [
        {"num_vertices": 4096, "pallas_speedup": 1.01}])
    assert triangles._resolve_dense_choice() == ("xla", DENSE_LIMIT)


def test_dense_ignores_error_stub_sections(selection_env):
    # a failed profiler section records {"error": ...}; consumers must
    # see no rows, not crash or select on garbage
    selection_env("tpu", "tpu", dense={"error": "timeout"})
    assert triangles._resolve_dense_choice() == ("xla", DENSE_LIMIT)


def test_tuned_kb_picks_fastest_measured_row(selection_env):
    """The fastest measured row wins OUTRIGHT — per_window_ms was
    measured on a run that already paid that K's overflow recounts, so
    an occasionally-overflowing K that wins net is taken (the CPU
    sweep's eb=32768 K=32 case), while a K whose recounts make it slow
    loses on its own measurement."""
    selection_env("cpu", "cpu", window=[{
        "edge_bucket": 8192,
        "k_sweep": [
            {"k_bucket": 32, "per_window_ms": 3.0,
             "overflow_recounts_per_run": 0},
            {"k_bucket": 64, "per_window_ms": 5.0,
             "overflow_recounts_per_run": 0},
            # fastest row WITH its recount cost priced in: wins
            {"k_bucket": 16, "per_window_ms": 1.0,
             "overflow_recounts_per_run": 2},
        ]}])
    assert triangles._tuned_kb(8192) == 16


def test_tuned_kb_recount_heavy_row_loses_on_its_own_measurement(
        selection_env):
    selection_env("cpu", "cpu", window=[{
        "edge_bucket": 8192,
        "k_sweep": [
            # every window recounted: the measurement itself is slow
            {"k_bucket": 16, "per_window_ms": 50.0,
             "overflow_recounts_per_run": 64},
            {"k_bucket": 64, "per_window_ms": 5.0,
             "overflow_recounts_per_run": 0},
        ]}])
    assert triangles._tuned_kb(8192) == 64


def test_tuned_kb_falls_back_to_analytic_on_backend_mismatch(
        selection_env):
    selection_env("tpu", "cpu", window=[{
        "edge_bucket": 8192,
        "k_sweep": [{"k_bucket": 32, "per_window_ms": 3.0,
                     "overflow_recounts_per_run": 0}]}])
    assert triangles._tuned_kb(8192) == min(128, 2 * int(8192 ** 0.5))


def test_tuned_chunk_reads_matching_backend_sweep(selection_env):
    selection_env("cpu", "cpu", window=[{
        "edge_bucket": 8192,
        "chunk_sweep": [
            {"windows_per_dispatch": 32, "per_window_ms": 9.0},
            {"windows_per_dispatch": 128, "per_window_ms": 7.5},
            {"windows_per_dispatch": 64, "per_window_ms": 8.0},
        ]}])
    assert triangles._tuned_chunk(8192) == 128
    # unmeasured bucket: class default
    assert (triangles._tuned_chunk(4096)
            == triangles.TriangleWindowKernel.MAX_STREAM_WINDOWS)


def test_tuned_chunk_merges_chunk_deep_rows(selection_env):
    """chunk_deep rows (the in-window post-probe deep sweep,
    tools/profile_kernels.section_chunk_deep) extend the window
    section's sweep: the fastest row across BOTH sections wins."""
    cap_raise = [{"program": "triangle_stream", "slots": 1 << 20,
                  "ok": True, "compile_s": 40.0}]
    selection_env("tpu", "tpu", window=[{
        "edge_bucket": 32768,
        "chunk_sweep": [
            {"windows_per_dispatch": 8, "per_window_ms": 9.0},
            {"windows_per_dispatch": 16, "per_window_ms": 7.5},
        ]}], chunk_deep=[{
            "edge_bucket": 32768,
            "chunk_sweep": [
                {"windows_per_dispatch": 32, "per_window_ms": 6.1},
            ]}], compile_probe=cap_raise)
    assert triangles._tuned_chunk(32768) == 32
    # a SLOWER deep row must not displace the window section's winner
    triangles._TUNED_CHUNK.clear()
    selection_env("tpu", "tpu", window=[{
        "edge_bucket": 32768,
        "chunk_sweep": [
            {"windows_per_dispatch": 16, "per_window_ms": 7.5}]}],
        chunk_deep=[{
            "edge_bucket": 32768,
            "chunk_sweep": [
                {"windows_per_dispatch": 32, "per_window_ms": 8.8}]}])
    assert triangles._tuned_chunk(32768) == 16


def test_tuned_chunk_clamped_to_current_cap_on_chip(selection_env):
    """A persisted deep-sweep depth measured under a since-lowered cap
    must not drive a dispatch above the CURRENT cap (it would
    recompile the exact oversized program the cap exists to prevent)."""
    selection_env("tpu", "tpu", chunk_deep=[{
        "edge_bucket": 32768,
        "chunk_sweep": [{"windows_per_dispatch": 32,
                         "per_window_ms": 6.0}]}],
        compile_probe=[{"program": "triangle_stream", "slots": 1 << 18,
                        "ok": False, "reason": "timeout"}])
    # cap fell to 2^16 (failure/4, no clean rows): 2^16/32768 = 2
    assert triangles.compile_cap("triangle_stream") == 1 << 16
    assert triangles._tuned_chunk(32768) == 2


def test_compile_cap_contradiction_trusts_clean_row_above_failure(
        selection_env):
    """A clean probe row LARGER than a failure is contradictory
    evidence; the measured success wins (a compile that finished is
    direct proof of the shape, a timeout can be a transient) —
    ADVICE r4: the cap must not drop below a proven-clean size."""
    selection_env("tpu", "tpu", compile_probe=[
        {"program": "triangle_stream", "slots": 1 << 20, "ok": True,
         "compile_s": 44.0},
        {"program": "triangle_stream", "slots": 1 << 19, "ok": False,
         "reason": "timeout"}])
    assert triangles.compile_cap("triangle_stream") == 1 << 20


def test_rows_clear_bar_rejects_malformed_rows():
    """parity True with a missing/zero rate on either side must FAIL
    the gate, not pass vacuously (ADVICE r4: 0 >= margin*0)."""
    bar = triangles.rows_clear_bar
    assert bar([{"parity": True, "a": 110, "b": 100}], "a", "b")
    assert not bar([{"parity": True}], "a", "b")            # no rates
    assert not bar([{"parity": True, "a": 110}], "a", "b")  # no denom
    assert not bar([{"parity": True, "b": 100}], "a", "b")  # no numer
    assert not bar([{"parity": True, "a": 0, "b": 0}], "a", "b")
    # callable denominators get the same guard
    assert not bar([{"parity": True, "a": 110}], "a", lambda r: 0.0)
    assert bar([{"parity": True, "a": 110}], "a", lambda r: 100.0)


def test_tuned_chunk_backend_mismatch_keeps_default(selection_env):
    selection_env("tpu", "cpu", window=[{
        "edge_bucket": 8192,
        "chunk_sweep": [{"windows_per_dispatch": 128,
                         "per_window_ms": 1.0}]}])
    assert (triangles._tuned_chunk(8192)
            == triangles.TriangleWindowKernel.MAX_STREAM_WINDOWS)


def test_sweep_rows_missing_value_key_are_skipped(selection_env):
    """A malformed/hand-edited PERF.json row with per_window_ms but a
    missing or zero value key must not crash the selector or select a
    degenerate K/chunk (ADVICE r3): such rows are skipped, and the
    surviving fastest row is clamped to a positive int."""
    selection_env("cpu", "cpu", window=[{
        "edge_bucket": 8192,
        "k_sweep": [
            {"per_window_ms": 0.5},                       # no k_bucket
            {"k_bucket": 0, "per_window_ms": 0.7},        # zero
            {"k_bucket": None, "per_window_ms": 0.9},     # null
            {"k_bucket": 64, "per_window_ms": 5.0},
        ],
        "chunk_sweep": [
            {"per_window_ms": 0.1},                       # no value key
            {"windows_per_dispatch": 0, "per_window_ms": 0.2},
        ]}])
    assert triangles._tuned_kb(8192) == 64
    # every chunk row malformed -> the class default stands
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel
    assert (triangles._tuned_chunk(8192)
            == TriangleWindowKernel.MAX_STREAM_WINDOWS)


HOST_WIN = [{"edge_bucket": 8192, "parity": True,
             "host_edges_per_s": 2_000_000,
             "device_edges_per_s": 800_000},
            {"edge_bucket": 32768, "parity": True,
             "host_edges_per_s": 1_500_000,
             "device_edges_per_s": 900_000}]


def test_stream_impl_chip_routes_per_bucket(selection_env):
    """On a TPU backend the tier is per edge bucket: a bucket whose
    chip-labeled rows show the host tier winning (small windows,
    dispatch-latency-bound — VERDICT r4: 0.44× at 8192) routes to
    host, while a bucket with device-winning rows keeps the chip
    path. Unmeasured buckets default to device."""
    selection_env("tpu", "tpu", host_stream=[
        {"edge_bucket": 8192, "parity": True,
         "host_edges_per_s": 1_200_000, "device_edges_per_s": 500_000},
        {"edge_bucket": 32768, "parity": True,
         "host_edges_per_s": 400_000, "device_edges_per_s": 770_000},
    ])
    assert triangles._resolve_stream_impl(8192) == "host"
    assert triangles._resolve_stream_impl(32768) == "device"
    assert triangles._resolve_stream_impl(65536) == "device"  # no rows
    assert triangles._resolve_stream_impl(None) == "device"


def test_stream_impl_chip_ignores_cpu_rows(selection_env):
    # cpu-labeled wins must not route the chip path anywhere
    selection_env("cpu", "tpu", host_stream=[
        {"edge_bucket": 8192, "parity": True,
         "host_edges_per_s": 1_200_000,
         "device_edges_per_s": 500_000}])
    assert triangles._resolve_stream_impl(8192) == "device"


def test_stream_impl_flips_to_host_on_winning_cpu_rows(selection_env):
    selection_env("cpu", "cpu", host_stream=HOST_WIN)
    assert triangles._resolve_stream_impl() == "host"


def test_stream_impl_stays_device_on_chip(selection_env):
    # the host tier NEVER applies on a TPU backend, whatever the file
    selection_env("tpu", "tpu", host_stream=HOST_WIN)
    assert triangles._resolve_stream_impl() == "device"


@pytest.mark.parametrize("rows", [
    [],                                               # unmeasured
    [dict(HOST_WIN[0], parity=False)],                # parity failure
    [dict(HOST_WIN[0], host_edges_per_s=810_000)],    # < 5% win
    HOST_WIN + [dict(HOST_WIN[1], edge_bucket=65536,  # loses at one eb
                     host_edges_per_s=100_000)],
])
def test_stream_impl_needs_a_clean_win_everywhere(selection_env, rows):
    selection_env("cpu", "cpu", host_stream=rows)
    assert triangles._resolve_stream_impl() == "device"


def test_stream_impl_ignores_tpu_labeled_file_on_cpu(selection_env):
    selection_env("tpu", "cpu", host_stream=HOST_WIN)
    assert triangles._resolve_stream_impl() == "device"


NATIVE_WIN = [dict(r, native_parity=True,
                   native_edges_per_s=3 * r["host_edges_per_s"])
              for r in HOST_WIN]


def test_stream_impl_prefers_native_on_winning_rows(selection_env):
    """Committed rows where the C++ tier beats BOTH the numpy tier and
    the device kernel at every bucket flip the CPU fallback to it
    (requires the built library — present in this repo)."""
    from gelly_streaming_tpu import native

    assert native.triangles_available()
    selection_env("cpu", "cpu", host_stream=NATIVE_WIN)
    assert triangles._resolve_stream_impl() == "native"


@pytest.mark.parametrize("spoil", [
    dict(native_parity=False),               # parity failure
    dict(native_edges_per_s=0),              # missing measurement
    dict(native_edges_per_s=1_550_000),      # < 5% over the numpy tier
])
def test_stream_impl_native_needs_a_clean_win_everywhere(
        selection_env, spoil):
    rows = [NATIVE_WIN[0], dict(NATIVE_WIN[1], **spoil)]
    selection_env("cpu", "cpu", host_stream=rows)
    assert triangles._resolve_stream_impl() == "host"


def test_stream_impl_survives_other_backend_profile(
        selection_env, tmp_path):
    """A chip profile run takes over PERF.json; the CPU fallback's
    selections must keep reading this backend's committed rows from
    the PERF_cpu.json archive (VERDICT r4: the single-file design
    silently deselected the host tier the moment the chip was
    profiled)."""
    import json as _json

    selection_env("tpu", "cpu", window=[])  # PERF.json is chip-labeled
    (tmp_path / "PERF_cpu.json").write_text(_json.dumps(
        {"backend": "cpu", "host_stream": HOST_WIN}))
    assert triangles._resolve_stream_impl() == "host"


def test_winning_ingress_rows_flip_a_fresh_kernel(selection_env):
    """Integration: committed winning ingress_ab rows make a FRESH
    unpinned kernel dispatch compact, with counts identical to the
    standard form (the adoption path bench would take on chip)."""
    import numpy as np

    selection_env("cpu", "cpu", ingress_ab=INGRESS_WIN)
    from gelly_streaming_tpu.ops.triangles import TriangleWindowKernel

    auto = TriangleWindowKernel(edge_bucket=128, vertex_bucket=256)
    assert auto.ingress == "compact"
    rng = np.random.default_rng(2)
    src = rng.integers(0, 256, 500).astype(np.int32)
    dst = rng.integers(0, 256, 500).astype(np.int32)
    std = TriangleWindowKernel(edge_bucket=128, vertex_bucket=256,
                               ingress="standard")
    assert (auto._count_stream_device(src, dst)
            == std._count_stream_device(src, dst))

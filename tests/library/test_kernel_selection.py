"""The kernel selections of ops/triangles.py, each made from what the
process observes (the backend, the edge and vertex buckets) or from an
explicit constructor pin — never from a file:

  - resolve_xla_intersect (broadcast compare on chip, bsearch on CPU)
  - _tuned_kb (the analytic starting K per edge bucket)
  - capped_chunk / _default_chunk (COMPILE_CAP on chip, the class
    maximum off-chip)
  - _resolve_stream_impl (always the device program)
  - triangle_count's dense/sparse split at DENSE_LIMIT
  - the stream wire format (standard unless `ingress="compact"` pins
    it, and only where ids fit uint16)
"""

import jax
import numpy as np
import pytest

from gelly_streaming_tpu.ops import triangles
from gelly_streaming_tpu.ops.triangles import (COMPILE_CAP, DENSE_LIMIT,
                                               TriangleWindowKernel)


@pytest.fixture
def backend(monkeypatch):
    """Let the test pick the apparent backend; restored afterwards."""

    def configure(name):
        monkeypatch.setattr(jax, "default_backend", lambda: name)

    return configure


@pytest.mark.parametrize("name,want", [
    ("cpu", "intersect_local_bsearch"),
    ("tpu", "intersect_local"),
])
def test_intersect_is_chosen_by_backend(backend, name, want):
    backend(name)
    assert triangles.resolve_xla_intersect() is getattr(triangles, want)


def test_capped_chunk_unlimited_off_chip(backend):
    backend("cpu")
    assert (triangles.capped_chunk(32768)
            == TriangleWindowKernel.MAX_STREAM_WINDOWS)
    assert (triangles._default_chunk(1 << 20)
            == TriangleWindowKernel.MAX_STREAM_WINDOWS)


@pytest.mark.parametrize("eb,want", [
    (4096, 64),          # the class maximum binds below the cap
    (8192, 64),          # 2^19 / 8192
    (32768, 16),         # 2^19 / 32768
    (1 << 20, 1),        # past the cap: one window per dispatch
])
def test_compile_cap_bounds_the_chunk_on_chip(backend, eb, want):
    backend("tpu")
    assert COMPILE_CAP == 1 << 19
    assert triangles.capped_chunk(eb) == max(1, COMPILE_CAP // eb)
    assert triangles._default_chunk(eb) == want


def test_fused_engine_honors_lowered_cap(backend):
    # on a chip backend the fused scan's windows-per-dispatch is the
    # class maximum cut to the compile cap (2^19 / eb=32768 -> 16)
    backend("tpu")
    from gelly_streaming_tpu.ops.scan_analytics import StreamSummaryEngine

    eng = StreamSummaryEngine(edge_bucket=32768, vertex_bucket=65536)
    assert eng.MAX_WINDOWS == min(StreamSummaryEngine.MAX_WINDOWS, 16)


@pytest.mark.parametrize("eb,want", [
    (256, 32),           # 2·√256
    (4096, 128),         # 2·√4096 = 128
    (8192, 128),         # capped at 128
    (32768, 128),
])
def test_tuned_kb_is_the_analytic_bound(eb, want):
    assert triangles._tuned_kb(eb) == want
    kern = TriangleWindowKernel(edge_bucket=eb, vertex_bucket=2 * eb)
    assert kern.kb == want


def test_stream_impl_stays_device_on_chip(backend):
    backend("tpu")
    for eb in (None, 4096, 32768):
        assert triangles._resolve_stream_impl(eb) == "device"


@pytest.mark.parametrize("eb", [None, 4096, 32768])
def test_stream_impl_is_device_on_cpu(backend, eb):
    # the CPU runs the chip's program, not a host tier
    backend("cpu")
    assert triangles._resolve_stream_impl(eb) == "device"


def test_dense_path_up_to_dense_limit(monkeypatch):
    calls = []
    monkeypatch.setattr(triangles, "triangle_count_dense",
                        lambda s, d, v: calls.append(("dense", v)) or 0)
    monkeypatch.setattr(triangles, "triangle_count_sparse",
                        lambda s, d, v: calls.append(("sparse", v)) or 0)
    for v in (3, DENSE_LIMIT, DENSE_LIMIT + 1):
        triangles.triangle_count(np.array([0]), np.array([1]), v)
    assert calls == [("dense", 3), ("dense", DENSE_LIMIT),
                     ("sparse", DENSE_LIMIT + 1)]


def test_ingress_is_standard_unless_pinned():
    assert TriangleWindowKernel(128, 256).ingress == "standard"
    assert TriangleWindowKernel(128, 1 << 17).ingress == "standard"
    assert (TriangleWindowKernel(128, 256, ingress="compact").ingress
            == "compact")


def test_ingress_vb_gate_refuses_a_lossy_compact_pin():
    # ids wider than uint16: the compact format is lossy there
    with pytest.raises(ValueError, match="compact ingress is lossy"):
        TriangleWindowKernel(128, 1 << 17, ingress="compact")


def test_compact_pin_counts_match_standard():
    """A kernel pinned to compact ingress dispatches the compact wire
    with counts identical to the standard form."""
    rng = np.random.default_rng(2)
    src = rng.integers(0, 256, 500).astype(np.int32)
    dst = rng.integers(0, 256, 500).astype(np.int32)
    cmp_ = TriangleWindowKernel(edge_bucket=128, vertex_bucket=256,
                                ingress="compact")
    std = TriangleWindowKernel(edge_bucket=128, vertex_bucket=256)
    assert (cmp_._count_stream_device(src, dst)
            == std._count_stream_device(src, dst))

"""Native host-runtime kernels: parser / window assigner / interner,
cross-checked against the Python fallbacks."""

import os

import numpy as np
import pytest

from gelly_streaming_tpu import native
from gelly_streaming_tpu.utils.interning import IncrementalInterner


def test_native_builds():
    if not native.available():
        pytest.skip("no C++ toolchain — fallbacks in use")


def test_parse_edge_file(tmp_path):
    p = tmp_path / "edges.txt"
    p.write_text("1 2 100\n3\t4\t200\n\nbad line\n5 6\n-7 8 300\n")
    src, dst, ts = native.parse_edge_file(str(p))
    np.testing.assert_array_equal(src, [1, 3, 5, -7])
    np.testing.assert_array_equal(dst, [2, 4, 6, 8])
    np.testing.assert_array_equal(ts, [100, 200, -1, 300])


def test_parse_large_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    n = 50_000
    src = rng.integers(0, 1 << 40, n)
    dst = rng.integers(0, 1 << 40, n)
    ts = np.arange(n)
    p = tmp_path / "big.txt"
    with open(p, "w") as f:
        for row in zip(src, dst, ts):
            f.write("%d %d %d\n" % row)
    s, d, t = native.parse_edge_file(str(p))
    np.testing.assert_array_equal(s, src)
    np.testing.assert_array_equal(d, dst)
    np.testing.assert_array_equal(t, ts)


def test_parse_crlf_lines_match_python_fallback(tmp_path):
    """CRLF-terminated lines (with and without timestamps) parse the same
    through the native parser and the Python fallback."""
    p = tmp_path / "crlf.txt"
    p.write_bytes(b"1 2\r\n3 4 200\r\n5 6\r")
    for parse in (native.parse_edge_file,
                  lambda f: native._parse_edge_bytes_py(open(f, 'rb').read())):
        src, dst, ts = parse(str(p))
        np.testing.assert_array_equal(src, [1, 3, 5])
        np.testing.assert_array_equal(dst, [2, 4, 6])
        np.testing.assert_array_equal(ts, [-1, 200, -1])


def test_parse_trailing_tokens_match_python_fallback(tmp_path):
    """Lines with extra non-numeric columns keep their first three fields
    identically in the native parser and the Python fallback."""
    p = tmp_path / "annot.txt"
    p.write_text("1 2 100 label\n3 4 200 x y z\n5 6x 300\n7 8\n")
    expected = ([1, 3, 7], [2, 4, 8], [100, 200, -1])
    src, dst, ts = native.parse_edge_file(str(p))
    np.testing.assert_array_equal(src, expected[0])
    np.testing.assert_array_equal(dst, expected[1])
    np.testing.assert_array_equal(ts, expected[2])
    # and the pure-Python path agrees even when the native lib exists
    s, d, t = native._parse_edge_bytes_py(p.read_bytes())
    np.testing.assert_array_equal(s, expected[0])
    np.testing.assert_array_equal(d, expected[1])
    np.testing.assert_array_equal(t, expected[2])


def test_assign_windows():
    ts = np.array([0, 99, 100, 250, 999, 1000])
    np.testing.assert_array_equal(
        native.assign_windows(ts, 100), [0, 0, 100, 200, 900, 1000]
    )


def test_native_interner_matches_python():
    if not native.available():
        pytest.skip("no native lib")
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 500, 5000)
    nat = native.NativeInterner()
    py = IncrementalInterner()
    np.testing.assert_array_equal(nat.intern_array(ids), py.intern_array(ids))
    assert len(nat) == len(py)
    dense = np.arange(len(nat), dtype=np.int32)
    assert list(nat.ids_of(dense)) == py.ids_of(dense)


def test_iter_edge_chunks_prefetch_matches_sync(tmp_path):
    """The producer-thread prefetch path yields byte-identical chunks
    in order, propagates parse errors, and shuts its thread down when
    the consumer abandons mid-stream."""
    import threading

    import numpy as np

    from gelly_streaming_tpu.io.sources import iter_edge_chunks

    p = tmp_path / "edges.txt"
    rng = np.random.default_rng(2)
    rows = ["%d %d %d" % (rng.integers(0, 99), rng.integers(0, 99), t)
            for t in range(5000)]
    p.write_text("\n".join(rows) + "\n")

    sync = list(iter_edge_chunks(str(p), chunk_bytes=4096, prefetch=0))
    pre = list(iter_edge_chunks(str(p), chunk_bytes=4096, prefetch=3))
    assert len(sync) == len(pre) > 1
    for a, b in zip(sync, pre):
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)

    # abandon mid-stream: the producer thread must exit
    before = threading.active_count()
    it = iter_edge_chunks(str(p), chunk_bytes=512, prefetch=1)
    next(it)
    it.close()
    for _ in range(100):
        if threading.active_count() <= before:
            break
        import time
        time.sleep(0.02)
    assert threading.active_count() <= before

    # a missing file raises in the CONSUMER, not silently in the thread
    import pytest

    with pytest.raises(OSError):
        list(iter_edge_chunks(str(tmp_path / "missing.txt"), prefetch=2))


# ----------------------------------------------------------------------
# native snapshot tier (gs_snapshot_windows): the host form of the
# driver's batched snapshot scan
# ----------------------------------------------------------------------

def _tier_drivers(**kw):
    from gelly_streaming_tpu import native as native_mod
    from gelly_streaming_tpu.core.driver import StreamingAnalyticsDriver

    if not native_mod.snapshot_available():
        import pytest

        pytest.skip("libgsnative lacks gs_snapshot_windows")
    return (StreamingAnalyticsDriver(snapshot_tier="scan", **kw),
            StreamingAnalyticsDriver(snapshot_tier="native", **kw))


def _assert_results_equal(ra, rb):
    assert len(ra) == len(rb)
    for x, y in zip(ra, rb):
        np.testing.assert_array_equal(x.degrees, y.degrees)
        np.testing.assert_array_equal(x.cc_labels, y.cc_labels)
        np.testing.assert_array_equal(x.bipartite_odd, y.bipartite_odd)
        assert x.triangles == y.triangles


def test_snapshot_tier_parity_count_windows():
    """Count-based windows incl. vertex-bucket growth mid-stream and a
    partial final window: every per-window snapshot identical across
    tiers."""
    rng = np.random.default_rng(5)
    kw = dict(window_ms=0, edge_bucket=256, vertex_bucket=64)
    a, b = _tier_drivers(**kw)
    for n, hi in ((1024, 50), (1000, 2000)):  # growth on the 2nd batch
        src = rng.integers(0, hi, n)
        dst = rng.integers(0, hi, n)
        _assert_results_equal(a.run_arrays(src, dst),
                              b.run_arrays(src, dst))


def test_snapshot_tier_parity_event_time():
    """Event-time windows (varying lengths) through stream_file."""
    rng = np.random.default_rng(8)
    n = 4000
    src = rng.integers(0, 300, n)
    dst = rng.integers(0, 300, n)
    ts = np.sort(rng.integers(0, 5000, n))
    import tempfile

    with tempfile.NamedTemporaryFile("w", suffix=".txt",
                                     delete=False) as f:
        f.write("".join(f"{s} {d} {t}\n"
                        for s, d, t in zip(src, dst, ts)))
        path = f.name
    a, b = _tier_drivers(window_ms=400)
    _assert_results_equal(list(a.stream_file(path)),
                          list(b.stream_file(path)))


def test_snapshot_tier_checkpoint_interop(tmp_path):
    """A checkpoint taken under one tier resumes under the OTHER with
    an identical continuation — the carried layouts are shared."""
    rng = np.random.default_rng(13)
    n = 6000
    src = rng.integers(0, 200, n)
    dst = rng.integers(0, 200, n)
    p = tmp_path / "s.txt"
    p.write_text("".join(f"{s} {d}\n" for s, d in zip(src, dst)))
    kw = dict(window_ms=0, edge_bucket=512, vertex_bucket=256)

    a_full, b_full = _tier_drivers(**kw)
    want = a_full.run_file(str(p))
    _assert_results_equal(want, b_full.run_file(str(p)))

    for first, second in (("native", "scan"), ("scan", "native")):
        from gelly_streaming_tpu.core.driver import (
            StreamingAnalyticsDriver)

        ck = str(tmp_path / f"{first}.ckpt")
        a = StreamingAnalyticsDriver(snapshot_tier=first, **kw)
        a.enable_auto_checkpoint(ck, every_n_windows=2)
        seen = 0
        for _res in a.stream_file(str(p), chunk_bytes=4096):
            seen += 1
            if seen == 7:
                break
        b = StreamingAnalyticsDriver(snapshot_tier=second, **kw)
        assert b.try_resume(ck)
        done = b.windows_done
        rest = list(b.stream_file(str(p), chunk_bytes=4096,
                                  resume=True))
        _assert_results_equal(rest, want[done:])


def test_snapshot_tier_resolver_gates(monkeypatch):
    """resolve_snapshot_tier: the scan on every backend, the resident
    tier only under the GS_RESIDENT=on pin. The native tier is reached
    only by the `snapshot_tier=` pin or the demotion ladder."""
    import jax

    from gelly_streaming_tpu.core import driver as drv_mod

    monkeypatch.delenv("GS_RESIDENT", raising=False)
    for backend in ("cpu", "tpu"):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert drv_mod.resolve_snapshot_tier() == "scan"
        monkeypatch.setenv("GS_RESIDENT", "off")
        assert drv_mod.resolve_snapshot_tier() == "scan"
        monkeypatch.setenv("GS_RESIDENT", "on")
        assert drv_mod.resolve_snapshot_tier() == "resident"
        monkeypatch.delenv("GS_RESIDENT")


def test_snapshot_tier_delta_parity():
    """emit_deltas on the native tier: delta streams identical to the
    scan tier's device-computed masks, window by window — INCLUDING
    across chunk boundaries (shrunken _SCAN_CHUNK so the chunk-start
    `prevs` copy is taken from in-place-mutated carried state) and
    across mid-stream vertex-bucket growth."""
    rng = np.random.default_rng(17)
    kw = dict(window_ms=0, edge_bucket=256, vertex_bucket=512,
              analytics=("degrees", "cc", "bipartite"),
              emit_deltas=True)
    a, b = _tier_drivers(**kw)
    a._SCAN_CHUNK = b._SCAN_CHUNK = 2  # many chunks per batch
    for n, hi in ((1024, 500), (768, 500), (1025, 1600)):
        # 3rd batch grows the vertex bucket mid-stream and ends on a
        # partial window (only the FINAL batch may: count-based
        # tumbling semantics)
        src = rng.integers(0, hi, n)
        dst = rng.integers(0, hi, n)
        ra, rb = a.run_arrays(src, dst), b.run_arrays(src, dst)
        assert len(ra) == len(rb)
        for x, y in zip(ra, rb):
            for field in ("delta_degrees", "delta_cc",
                          "delta_bipartite"):
                dx, dy = getattr(x, field), getattr(y, field)
                assert (dx is None) == (dy is None), field
                if dx is not None:
                    np.testing.assert_array_equal(dx[0], dy[0])
                    np.testing.assert_array_equal(dx[1], dy[1])


def test_library_name_keyed_on_source(tmp_path, monkeypatch):
    """The loader's library carries a hash of ingest.cpp: an edited
    source maps to a new library (rebuilt on first load), never to one
    built from another revision."""
    import shutil

    for name in ("ingest.cpp", "Makefile"):
        shutil.copy(os.path.join(native._DIR, name), tmp_path)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    first = native._lib_path()
    assert os.path.dirname(first) == str(tmp_path)
    assert os.path.basename(first).startswith("libgsnative-")
    assert native._lib_path() == first
    with open(tmp_path / "ingest.cpp", "a") as f:
        f.write("\n// edited\n")
    assert native._lib_path() != first


def test_makefile_builds_for_any_host():
    """The checkout's library must load on whichever machine runs it:
    no -march=native."""
    with open(os.path.join(native._DIR, "Makefile")) as f:
        assert "march=native" not in f.read()

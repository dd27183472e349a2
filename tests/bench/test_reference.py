"""The copied references and generator at small size: the reference
agrees with PR 21's union-find reference, with `run_arrays`, and with a
loopback `StreamServer`; the copied generator is the bench's."""

import numpy as np
import pytest

from benchmark import reference, streams
from benchmark.entries import driver as driver_entry

EB, VB = 256, 1024


@pytest.fixture(scope="module")
def stream():
    s, d = streams.make_stream(8 * EB, VB, seed=2 ** 35 + 3)
    return s.astype(np.int32), d.astype(np.int32)


def test_make_stream_is_the_bench_copy():
    import bench

    for seed in (7, 2 ** 34 + 1):
        a = streams.make_stream(3000, 777, seed=seed)
        b = bench.make_stream(3000, 777, seed=seed)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))


def test_fold_matches_pr21_union_find(stream):
    import chip_smoke

    want = chip_smoke.ref_driver_windows(*stream, EB, VB)
    got = reference.fold_windows(*stream, EB, VB, range(len(want)))
    for w, ref in enumerate(want):
        for key in ("ids", "deg", "comp", "odd"):
            assert np.array_equal(np.asarray(got[w][key]),
                                  np.asarray(ref[key])), (w, key)
        assert got[w]["triangles"] == ref["triangles"]


def test_fold_matches_run_arrays(stream):
    from gelly_streaming_tpu import StreamingAnalyticsDriver

    drv = StreamingAnalyticsDriver(window_ms=0, vertex_bucket=VB,
                                   edge_bucket=EB)
    res = drv.run_arrays(stream[0][:4 * EB], stream[1][:4 * EB])
    res += drv.run_arrays(stream[0][4 * EB:], stream[1][4 * EB:])
    refs = reference.fold_windows(*stream, EB, VB, range(len(res)))
    for w, r in enumerate(res):
        assert not any(driver_entry._compare(r, refs[w]).values()), w


def test_summaries_match_loopback_server():
    from gelly_streaming_tpu.core.serve import ServeClient, StreamServer
    from gelly_streaming_tpu.core.tenancy import TenantCohort

    eb, vb = 256, 512
    server = StreamServer(TenantCohort(edge_bucket=eb, vertex_bucket=vb),
                          port=0).start()
    data = {}
    try:
        client = ServeClient(server.port)
        sub = ServeClient(server.port)
        assert sub.subscribe("*")["ok"]
        for i in range(3):
            s, d = streams.make_stream((3 + i) * eb, vb, seed=100 + i)
            data["t%d" % i] = (s, d)
            client.admit("t%d" % i)
            for lo in range(0, len(s), 64):
                assert client.feed("t%d" % i, s[lo:lo + 64],
                                   d[lo:lo + 64])["ok"]
        server.pump_once()
        rows = [sub.next_window(timeout=30) for _ in range(3 + 4 + 5)]
        client.close()
        sub.close()
    finally:
        server.close()
    for tid, (s, d) in data.items():
        got = {r["window"]: r["summary"] for r in rows if r["tenant"] == tid}
        want = reference.summaries(s, d, eb, vb, range(len(s) // eb))
        assert got == want, tid


def test_schedule_offers_the_same_work_for_every_seed():
    traffic = {"feed_edges": 512, "rate_edges_per_s": 50000, "tenants": 64,
               "tenant_zipf_theta": 0.99}
    a_due, a_t = streams.serve_schedule(traffic, 5.0, 1)
    b_due, b_t = streams.serve_schedule(traffic, 5.0, 2 ** 31 + 5)
    assert len(a_due) == len(b_due) == round(50000 * 5 / 512)
    assert np.array_equal(np.bincount(a_t, minlength=64),
                          np.bincount(b_t, minlength=64))
    assert abs(a_due[-1] - b_due[-1]) <= np.diff(a_due).max()
    assert not np.array_equal(a_t, b_t)
    assert a_due[0] == 0 and np.all(np.diff(a_due) > 0)

"""Auxiliary subsystems: tracing, checkpoint/resume (SURVEY.md §5 build
items — all absent from the reference)."""

import os

import numpy as np

from gelly_streaming_tpu import SimpleEdgeStream
from gelly_streaming_tpu.models.iterative_cc import \
    TpuIterativeConnectedComponents
from gelly_streaming_tpu.utils import checkpoint
from gelly_streaming_tpu.utils.candidates import Candidates, edge_to_candidate
from gelly_streaming_tpu.utils.disjoint_set import DisjointSet

from .conftest import long_long_edges


def test_tracing_reports_per_operator(env):
    env.enable_tracing()
    graph = SimpleEdgeStream(env.from_collection(long_long_edges()), env)
    sink = graph.get_degrees().collect()
    env.execute()
    report = env.trace_report()
    assert report, "tracing produced no rows"
    ops = {row["op"].split("#")[0] for row in report}
    assert "source" in ops and "flat_map" in ops
    total_records = sum(r["records"] for r in report)
    assert total_records > 0


def test_checkpoint_roundtrip_tree(tmp_path):
    tree = {
        "arr": np.arange(10, dtype=np.int32),
        "nested": {"f": 1.5, "s": "hello", "l": [1, 2, 3], "none": None},
        "tup": (np.ones(3), False),
    }
    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, tree)
    back = checkpoint.restore(path)
    np.testing.assert_array_equal(back["arr"], tree["arr"])
    assert back["nested"] == tree["nested"]
    np.testing.assert_array_equal(back["tup"][0], tree["tup"][0])
    assert back["tup"][1] is False


def test_checkpoint_colliding_paths(tmp_path):
    """Keys whose flattened path strings coincide ("a.b" vs nested a→b,
    int 1 vs str "1") must survive independently."""
    tree = {
        "a": {"b": np.zeros(3, np.int32)},
        "a.b": np.ones(3, np.int32),
        1: np.full(2, 7, np.int32),
        "1": np.full(2, 9, np.int32),
        "x": [np.array([1])],
        "x[0]": np.array([2]),
    }
    path = str(tmp_path / "collide.npz")
    checkpoint.save(path, tree)
    back = checkpoint.restore(path)
    np.testing.assert_array_equal(back["a"]["b"], np.zeros(3))
    np.testing.assert_array_equal(back["a.b"], np.ones(3))
    np.testing.assert_array_equal(back[1], [7, 7])
    np.testing.assert_array_equal(back["1"], [9, 9])
    np.testing.assert_array_equal(back["x"][0], [1])
    np.testing.assert_array_equal(back["x[0]"], [2])


def test_disjoint_set_checkpoint():
    ds = DisjointSet()
    ds.union(1, 2)
    ds.union(2, 3)
    ds.union(8, 9)
    ds2 = DisjointSet()
    ds2.load_state_dict(ds.state_dict())
    assert repr(ds2) == repr(ds)
    # resumed state keeps merging correctly
    ds2.union(3, 8)
    assert len(ds2.components()) == 1


def test_candidates_checkpoint():
    cand = Candidates(True)
    cand = cand.merge(edge_to_candidate(1, 2))
    cand = cand.merge(edge_to_candidate(1, 3))
    cand2 = Candidates(True)
    cand2.load_state_dict(cand.state_dict())
    assert repr(cand2) == repr(cand)


def test_iterative_cc_checkpoint_resume(tmp_path):
    model = TpuIterativeConnectedComponents()
    model.process_batch(np.array([1, 3]), np.array([2, 4]))
    path = str(tmp_path / "cc.npz")
    checkpoint.save(path, model.state_dict())

    resumed = TpuIterativeConnectedComponents()
    resumed.load_state_dict(checkpoint.restore(path))
    changed = resumed.process_batch(np.array([2]), np.array([3]))
    assert dict(changed) == {3: 1, 4: 1}


def test_sharded_engine_checkpoint():
    from gelly_streaming_tpu.parallel.sharded import ShardedWindowEngine

    eng = ShardedWindowEngine(num_vertices_bucket=32)
    eng.degrees(np.array([1, 2]), np.array([2, 3]))
    state = eng.state_dict()
    eng2 = ShardedWindowEngine(num_vertices_bucket=32)
    eng2.load_state_dict(state)
    out = eng2.degrees(np.array([1]), np.array([2]))
    assert out[1] == 2 and out[2] == 3


def test_time_units_complete():
    """Flink Time surface: every unit form produces the same ms value
    (reference: org.apache.flink.streaming.api.windowing.time.Time)."""
    from gelly_streaming_tpu import Time

    assert Time.of(2, "minutes").milliseconds == 120_000
    assert Time.minutes(2).milliseconds == 120_000
    assert Time.hours(1).milliseconds == Time.of(1, "h").milliseconds \
        == 3_600_000
    assert Time.days(1).milliseconds == Time.of(24, "hours").milliseconds
    assert Time.seconds(3).milliseconds == Time.of(3000).milliseconds


def test_ingress_ab_parity_failure_is_evidence_not_a_crash(monkeypatch):
    """ADVICE r4: a parity failure between wire formats must commit a
    {parity: false} row with no speedup claim instead of crashing the
    tool and losing the profiler section's probe rows."""
    import jax
    import jax.numpy as jnp

    from tools import ingress_ab as ab
    from gelly_streaming_tpu.ops import triangles as tri

    class FakeKernel:
        def __init__(self, edge_bucket, vertex_bucket, ingress):
            self.kb = 32
            self.MAX_STREAM_WINDOWS = 4
            self.ingress = ingress

        def warm_chunks(self):
            pass

        def _count_stream_device(self, src, dst):
            # formats disagree: one count differs
            return [1, 2] if self.ingress == "standard" else [1, 3]

    monkeypatch.setattr(tri, "TriangleWindowKernel", FakeKernel)
    results = []
    ab.stream_ab(jax, jnp, 1024, results)
    (row,) = results
    assert row["parity"] is False
    assert "speedup" not in row


def test_compile_cache_dir_honours_env(monkeypatch):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR when
    set, which the helper leaves to JAX alone; else one fixed path in
    the checkout (the path is part of the cache key)."""
    import jax

    from gelly_streaming_tpu.core import platform

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert platform.CACHE_DIR == os.path.join(repo, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert platform.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert platform.enable_compile_cache() == platform.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == platform.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
